"""Time the grouped expert kernel alone, on the chip, at the routed cells'
shapes.

    python scripts/expert_kernel_sweep.py [--old PATH] [--seed N]
                                          [--rehearse]

For each routed configuration the benchmark serves (experts, width,
hidden, gated or not) it times one `grouped_mlp` call at the cell's wave
shape (its lanes x top-k picks) and at its whole chunk's picks, with
picks drawn as a cell's routing draws them when the lanes choose
independently: each token picks k distinct experts at random. It prints
one JSON line a shape: the experts that hold rows, the bytes of their
matrices, the seconds and the rate those bytes arrive at. A call's time
is the difference of two loops of the same jitted program (10 and 2
calls), so dispatch and read-back cancel. `--old PATH` also times the
module at PATH (the parent's `ops/pallas/grouped_mlp.py`) on the same
picks, and prints the largest difference between the two outputs, pick
by pick. Where the parent cut a cell's chunk into segments (kanana2's
512 tokens: two of 341, the second padded with 170 tokens whose picks
are all expert 0's, as `nlp/moe.py` padded them), its form is those
calls, timed one after the other and summed.

Needs a TPU (`--rehearse`: tiny shapes on any backend, for the plumbing
alone).
"""
import argparse
import importlib.util
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.pallas import grouped_mlp as gm  # noqa: E402

#: cell: experts, width, hidden, gated, top-k, (tokens of a wave, tokens
#: of a chunk), tokens of a segment the parent cut a chunk into (None:
#: one call)
CONFIGS = {
    "laguna": (256, 512, 2048, True, 8, (16, 512), None),
    "xing4": (64, 1024, 3584, True, 4, (16, 512), None),
    "kanana2": (128, 768, 2048, True, 6, (32, 512), 341),
    "nemotron": (128, 1856, 2688, False, 6, (128, 128), None),
}


def emit(**row):
    print(json.dumps(row), flush=True)


def load_old(path):
    spec = importlib.util.spec_from_file_location("old_grouped_mlp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(experts, width, hidden, gated, k, tokens, seed):
    """(picks [tokens k], rows x [tokens k, hidden], up, gate or None,
    down): each of `tokens` tokens picks k distinct experts at random; a
    pick's row is its own, in token order."""
    rng = np.random.default_rng(seed)
    picks = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def mat(key):
        return (jax.random.normal(key, (experts, width, hidden), jnp.float32)
                * 0.02).astype(jnp.bfloat16)

    x = jax.random.normal(keys[0], (tokens * k, hidden), jnp.bfloat16)
    return (picks.reshape(-1), x, mat(keys[1]),
            mat(keys[2]) if gated else None, mat(keys[3]))


def sort(picks, x, experts):
    """(x sorted by expert, group sizes, the sort's order)."""
    order = np.argsort(picks, kind="stable")
    return (x[order], jnp.asarray(np.bincount(picks, minlength=experts),
                                  jnp.int32), order)


def segments(picks, x, k, seg):
    """The parent's calls for a chunk of picks: segments of `seg` tokens,
    the last padded with tokens whose k picks are expert 0 and whose
    rows are zeros; (picks, rows, real picks) each."""
    out = []
    for lo in range(0, len(picks), seg * k):
        p, r = picks[lo:lo + seg * k], x[lo:lo + seg * k]
        pad = seg * k - len(p)
        out.append((np.concatenate([p, np.zeros(pad, p.dtype)]),
                    jnp.pad(r, ((0, pad), (0, 0))), len(p)))
    return out


def timed(mod, x, up, gate, down, sizes):
    """Seconds of one call (None and the error if it does not compile)."""
    @jax.jit
    def run(n, x, up, gate, down, sizes):
        def body(_, x):
            out = mod.grouped_mlp(x, up, down, sizes, gate=gate)
            return x + (out * 1e-3).astype(x.dtype)
        return jax.lax.fori_loop(0, n, body, x)

    def once(n):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(n, x, up, gate, down, sizes).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    try:
        run(1, x, up, gate, down, sizes).block_until_ready()
    except Exception as e:   # noqa: BLE001 - whatever the compiler says
        return None, repr(e)[:300]
    return (once(10) - once(2)) / 8, None


def calls_of(mod, calls, experts, up, gate, down):
    """(seconds of the calls one after the other, their outputs in pick
    order, error): `calls` are (picks, rows, real picks) each."""
    total, outs = 0.0, []
    for picks, rows, real in calls:
        xs, sizes, order = sort(picks, rows, experts)
        s, err = timed(mod, xs, up, gate, down, sizes)
        if err is not None:
            return None, None, err
        total += s
        out = np.empty((len(picks), rows.shape[1]), np.float32)
        out[order] = np.asarray(mod.grouped_mlp(xs, up, down, sizes,
                                                gate=gate))
        outs.append(out[:real])
    return total, np.concatenate(outs), None


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--old", default=None, metavar="PATH",
                    help="the parent's grouped_mlp.py, timed beside")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any backend")
    args = ap.parse_args()
    configs = CONFIGS
    if args.rehearse:
        configs = {"tiny": (16, 256, 128, True, 4, (4, 32), 12),
                   "tiny-plain": (16, 256, 128, False, 4, (4, 32), None)}
    elif jax.default_backend() != "tpu":
        sys.exit("needs a TPU (or --rehearse)")
    forms = [("change", gm)]
    if args.old:
        forms.append(("parent", load_old(args.old)))
    for name, (e, w, h, gated, k, shapes, seg) in configs.items():
        mats = 3 if gated else 2
        for shape, tokens in zip(("wave", "chunk"), shapes):
            picks, x, up, gate, down = make(e, w, h, gated, k, tokens,
                                            args.seed)
            whole = [(picks, x, len(picks))]
            outs = []
            for form, mod in forms:
                split = form == "parent" and shape == "chunk" and seg
                calls = segments(picks, x, k, seg) if split else whole
                s, out, err = calls_of(mod, calls, e, up, gate, down)
                if err is None:
                    outs.append(out)
                held = sum(np.count_nonzero(np.bincount(p, minlength=e))
                           for p, _, _ in calls)
                nbytes = held * mats * w * h * 2
                emit(cell=name, shape=shape, form=form, picks=tokens * k,
                     calls=len(calls), experts=e, held=held,
                     touched_bytes=nbytes,
                     ms=None if s is None else round(s * 1e3, 4),
                     touched_gb_s=None if not s else round(nbytes / s / 1e9,
                                                           1),
                     device=jax.devices()[0].device_kind, error=err)
            if len(outs) == 2:
                # the same products in the same order: the outputs agree
                emit(cell=name, shape=shape, max_abs_diff=float(
                    np.max(np.abs(outs[0] - outs[1]))),
                    max_abs=float(np.max(np.abs(outs[1]))))


if __name__ == "__main__":
    main()
