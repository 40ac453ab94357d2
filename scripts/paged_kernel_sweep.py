"""Time the paged K/V attention kernel alone, on the chip, over tiles.

    python scripts/paged_kernel_sweep.py [--only wave|chunk|steps] [--old PATH]
                                         [--rows 1024,2048] [--rehearse]

For each of the four K/V configurations the benchmark serves (heads,
kv-heads, head size, table length, lanes, chunk) it times one attention
call (`nn.paged_attention.attend`, kernel "pallas": the query's
re-layout, the kernel, the output's) at the cell's wave and chunk shapes
with `_tile` pinned to each (kv-heads, pages, queries) a step in turn,
and prints one JSON line a timing: what `_tile`'s rule was written from
(PERF.md, PR 36). A call's time is the difference of two loops of the
same jitted program (10 and 2 calls), so dispatch and read-back cancel.
`--old PATH` also times the module at PATH (the parent's file) with its
own rule; `--rows` the query tiles' rows to try in the chunk form.
Needs a TPU (`--rehearse`: tiny shapes on any backend, for the plumbing
alone); writes chiprun_out/paged_sweep.jsonl as well.
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

from paddle_tpu.nn import paged_attention as pa  # noqa: E402

BS = 16
#: name: query heads, kv-heads, head size, pages a lane, lanes, chunk,
#: window, pages a lane attends in the cell's waves (mean, furthest)
CONFIGS = {
    "gpt2s": (12, 12, 64, 64, 256, 128, None, (14, 40)),
    "mistral": (32, 8, 128, 160, 64, 128, 4096, (23, 110)),
    "nemotron": (32, 2, 128, 128, 128, 128, None, (25, 90)),
    "granite": (32, 8, 64, 1088, 32, 512, None, (472, 1050)),
}
OUT = []
#: rows (group members x queries) of a chunk's query tile to time
ROWS = (256, 512, 1024, 2048)


def emit(**row):
    OUT.append(row)
    print(json.dumps(row), flush=True)


def load_old(path):
    spec = importlib.util.spec_from_file_location("old_paged_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(name, lanes, c, seed=0):
    h, hkv, d, nblk, _, _, window, _ = CONFIGS[name]
    rng = np.random.default_rng(seed)
    nb = lanes * nblk + 1
    pool = jax.random.normal(jax.random.PRNGKey(seed), (nb, hkv, BS, 2 * d),
                             jnp.bfloat16)
    tables = 1 + rng.permutation(lanes * nblk).reshape(lanes, nblk)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (lanes, h, c, d),
                          jnp.bfloat16)
    return q, pool, jnp.asarray(tables, jnp.int32), d ** -0.5, window


def timer(mod, q, pool, tables, scale, window):
    """A function start -> (seconds of one attend call, None), or (None,
    the error) if the call does not compile. One compile for all starts."""
    @jax.jit
    def run(n, q, pool, tables, start):
        def body(_, q):
            out = mod.attend(q, pool, tables, start, scale, window=window,
                             kernel="pallas")
            return q + (out * 1e-3).astype(q.dtype)
        return jax.lax.fori_loop(0, n, body, q)

    def once(n, start):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(n, q, pool, tables, start).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    def at(start):
        try:
            run(1, q, pool, tables, start).block_until_ready()
        except Exception as e:   # noqa: BLE001 - whatever the compiler says
            return None, repr(e)[:300]
        return (once(10, start) - once(2, start)) / 8, None
    return at


def timed(mod, q, pool, tables, start, scale, window):
    return timer(mod, q, pool, tables, scale, window)(start)


def pinned(tile):
    pa._tile = lambda c, *_: (tile[0], tile[1], tile[2] or c)


def wave_starts(name, lanes, rng):
    """Positions of a wave's lanes: lognormal about the cell's mean
    pages, the furthest lane at the cell's furthest."""
    mean, far = CONFIGS[name][7]
    pages = np.clip(rng.lognormal(np.log(mean), 0.5, lanes), 1, far)
    pages[0] = far
    return (pages * BS - 1 - rng.integers(0, BS, lanes)).astype(np.int32)


def sweep_wave(old):
    rng = np.random.default_rng(1)
    for name, (h, hkv, d, nblk, lanes, _, _, _) in CONFIGS.items():
        q, pool, tables, scale, window = make(name, lanes, 1)
        start = jnp.asarray(wave_starts(name, lanes, rng))
        attended = int(np.sum(np.asarray(start) // BS + 1))
        if old is not None:
            t, err = timed(old, q, pool, tables, start, scale, window)
            emit(form="wave", config=name, tile="parent", s=t, err=err,
                 pages_attended=attended)
        for heads in sorted({hkv, max(hkv // 2, 1)}):
            for pages in (1, 4, 8, 16, 32, 64):
                if heads < hkv and pages < 16:
                    continue
                pinned((heads, min(pages, nblk), None))
                t, err = timed(pa, q, pool, tables, start, scale, window)
                emit(form="wave", config=name, tile=[heads, pages, 1], s=t,
                     err=err, pages_attended=attended,
                     gbps=None if t is None else
                     attended * hkv * BS * 2 * d * 2 / t / 1e9)


def sweep_steps():
    """What a visited step costs by its keys, a skipped one, and a lane's
    fixed cost: every lane at one position (all steps visited), then one
    lane far and the rest near (the rest skip)."""
    for name, (h, hkv, d, nblk, lanes, _, _, _) in CONFIGS.items():
        q, pool, tables, scale, window = make(name, lanes, 1)
        for pages in (8, 16, 32, 64):
            pinned((hkv, min(pages, nblk), None))
            at = timer(pa, q, pool, tables, scale, window)
            for attended in sorted({1, 32, 64, nblk}):
                start = jnp.full((lanes,), attended * BS - 1, jnp.int32)
                t, err = at(start)
                emit(form="steps", config=name, pages=pages,
                     every_lane_attends=attended, s=t, err=err,
                     us_a_lane=None if t is None else t / lanes * 1e6)
            start = np.full((lanes,), BS - 1, np.int32)
            start[0] = nblk * BS - 1
            t, err = at(jnp.asarray(start))
            emit(form="steps", config=name, pages=pages,
                 one_lane_attends=nblk, the_rest=1, s=t, err=err,
                 us_a_lane=None if t is None else t / lanes * 1e6)


def sweep_chunk(old):
    for name, (h, hkv, d, nblk, _, c, _, _) in CONFIGS.items():
        rep = h // hkv
        q, pool, tables, scale, window = make(name, 1, c)
        starts = sorted({0, (nblk * BS // 3) // c * c, nblk * BS - c})
        tiles = []
        for rows in ROWS:
            cq = rows // rep
            if cq < 8 or cq > c or c % cq:
                continue
            for pages in (1, 4, 8, 16, 32, 64):
                tiles.append((1, min(pages, nblk), cq))
        if not tiles:        # a chunk of one kv-head under 256 rows
            tiles = [(1, min(pages, nblk), c)
                     for pages in (1, 4, 8, 16, 32, 64)]
        if hkv > 1:          # kv-heads sharing a step, cross-head masked
            tiles += [(2, 16, c), (2, 32, c)]
        if old is not None:
            at = timer(old, q, pool, tables, scale, window)
            for st in starts:
                t, err = at(jnp.asarray([st], jnp.int32))
                emit(form="chunk", config=name, tile="parent", start=st, s=t,
                     err=err)
        for tile in tiles:
            pinned(tile)
            at = timer(pa, q, pool, tables, scale, window)
            for st in starts:
                t, err = at(jnp.asarray([st], jnp.int32))
                emit(form="chunk", config=name, tile=list(tile), start=st,
                     rows=rep * tile[2], s=t, err=err)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["wave", "chunk", "steps"])
    ap.add_argument("--old")
    ap.add_argument("--rows", type=lambda v: tuple(map(int, v.split(","))),
                    help="rows of a chunk's query tile, e.g. 1024,2048")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any backend: the plumbing only")
    args = ap.parse_args()
    global ROWS
    ROWS = args.rows or ROWS
    if args.rehearse:
        for name, (h, hkv, d, nblk, lanes, c, window, _) in CONFIGS.items():
            CONFIGS[name] = (h, hkv, d, 40, 2, min(c, 64), window, (3, 6))
    elif jax.default_backend() != "tpu":
        sys.exit("needs a TPU: a time from another backend says nothing")
    emit(device=jax.devices()[0].device_kind)
    old = load_old(args.old) if args.old else None
    rule = pa._tile
    if args.only in (None, "wave"):
        sweep_wave(old)
    if args.only in (None, "steps"):
        sweep_steps()
    if args.only in (None, "chunk"):
        sweep_chunk(old)
    pa._tile = rule
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_sweep.jsonl", "a") as f:
        f.writelines(json.dumps(r) + "\n" for r in OUT)


if __name__ == "__main__":
    main()
