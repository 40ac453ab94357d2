"""Kind `train`: one compiled training step, over and over, on batches
made from the seed.

The cell's file says which step (`TrainStep` on one chip, or
`ShardedTrainStep` over the mesh it names), the optimizer, the global
batch and the sequence length. `attempted` and `failed` count steps.
"""
import math
import time

import numpy as np

from .. import harness


def _build_step(ctx, model):
    import jax
    cell = ctx.cell
    opt_spec = cell["optimizer"]
    opt = harness.import_attr(opt_spec["class"])(
        parameters=model.parameters(), **opt_spec.get("kwargs", {}))
    loss_fn = harness.import_attr(ctx.config["program"]["loss"])
    if not cell.get("mesh"):
        from paddle_tpu.jit import TrainStep
        return TrainStep(model, loss_fn, opt, donate=True), None
    from paddle_tpu.distributed.mesh import make_mesh
    from paddle_tpu.distributed.sharded import ShardedTrainStep
    mesh = make_mesh(dict(cell["mesh"]))
    # The step makes the whole optimizer state (float32 moments for every
    # parameter) on the default device before it shards it: 16 GB for
    # 2.0 B parameters, more than one chip holds. Made on the host
    # instead, it is sharded from there. Only a change to the program
    # can remove the detour (PERF.md, set-up).
    import jax.numpy as jnp
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        step = ShardedTrainStep(model, loss_fn, opt, mesh=mesh,
                                zero_stage=int(cell.get("zero_stage", 0)))
        # the step holds its own sharded copies; the model's unsharded
        # originals would sit on chip 0 (4 GB) for nothing. Parked as
        # zeros on the host; the check makes them again from the seed.
        for p in model.parameters():
            p.set_value(jnp.zeros(p.shape, p.dtype))
    return step, mesh


def _batches(ctx, vocab):
    """`n_batches` fixed [batch, seq] id arrays from the seed, cycled."""
    rng = np.random.default_rng([ctx.seed, 0xBA7C])
    cell = ctx.cell
    return [rng.integers(0, vocab, (int(cell["batch"]), int(cell["seq"])),
                         dtype=np.int32)
            for _ in range(int(cell.get("n_batches", 4)))]


def _planned_temporaries(step, mesh, ids):
    """Bytes the compiler plans for the step beyond its arguments, per
    chip (`harness.memory_peak_bytes` says why they are asked for). The
    program's own audit spec lowers the very callable the step
    dispatches; the compile is a cache hit."""
    import contextlib
    from paddle_tpu.tools.xprof import registry
    make = (registry.sharded_train_step_spec if mesh is not None
            else registry.train_step_spec)
    spec = make(step, (ids,), (ids,))
    with mesh if mesh is not None else contextlib.nullcontext():
        plan = spec["jitted"].lower(*spec["args"]).compile().memory_analysis()
    return max(0, plan.peak_memory_in_bytes - plan.argument_size_in_bytes)


def _check_against_reference(ctx, model, ids):
    """The float32 reference over the whole first batch, outside the
    window. Two things are held to it:

      * the program's forward (the attention and matmul path the step
        trains through), on the first sequence, at a seeded sample of
        positions: worst |logit difference| against a tolerance in
        bfloat16 steps of the largest reference logit;
      * the step itself: the reference's next-token cross-entropy over
        every sequence of the batch (logits at t against the token at
        t + 1, the last position of each sequence left out, the mean the
        program's loss is) is what the step's first loss, taken from the
        timed program before any update, has to equal. That covers the
        head and loss the step really runs (chunked or dense) and, on a
        mesh, the sharded forward.

    Returns (worst |logit difference|, its tolerance, largest |reference
    logit|, reference loss)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mesh_mod
    mesh_mod.set_mesh(None)          # one device: the plain kernel call
    weights = harness.install_weights(model, ctx.config, ctx.seed)
    check = ctx.cell["check"]
    ids = np.asarray(ids)
    rows = np.sort(np.random.default_rng([ctx.seed, 0xC4EC]).choice(
        ids.shape[1], int(check["positions"]), replace=False))
    model.eval()
    params, buffers = model.functional_state()
    fwd = jax.jit(lambda p, b, x: model.functional_call(p, b, x)[0]._data)
    got = np.asarray(fwd(params, buffers, jnp.asarray(ids[:1]))[0][rows]
                     .astype(jnp.float32))

    @jax.jit
    def ce_sum(logits, labels):
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(lp, labels[:, None], axis=1).sum()

    ref = harness.reference_for(ctx.config)
    sh = harness.shapes(ctx.config)
    rw = ref.from_state_dict(weights, sh["layers"])
    total, want = 0.0, None
    for b in range(ids.shape[0]):     # one sequence at a time: [S, V] logits
        lo = ref.forward(rw, ids[b:b + 1], ctx.config)[0]
        total += float(ce_sum(lo[:-1], jnp.asarray(ids[b, 1:])))
        if b == 0:
            want = np.asarray(lo[rows])
    ref_loss = total / (ids.shape[0] * (ids.shape[1] - 1))
    top = float(np.abs(want).max())
    tol = float(check["logit_tol_bf16_steps"]) * 2.0 ** -8 * top
    return float(np.abs(got - want).max()), tol, top, ref_loss


def run(ctx):
    import jax
    import jax.numpy as jnp
    cell = ctx.cell
    sh = harness.shapes(ctx.config)
    model, weights = harness.build_model(ctx.config, ctx.seed, ctx.phase)
    with ctx.phase("step"):
        step, mesh = _build_step(ctx, model)
    del weights                       # the check makes them again
    batches = _batches(ctx, sh["vocab"])
    tokens_per_step = int(cell["batch"]) * int(cell["seq"])

    def go(i):
        ids = batches[i % len(batches)]
        return step(ids, ids)

    # warm-up: the first step compiles, the second may compile again for
    # the donated buffers' layouts; go on until a step compiles nothing
    with ctx.phase("warmup"):
        warm_losses = []
        for i in range(int(cell.get("max_warmup_steps", 6))):
            before = len(ctx.compiles.times)
            loss = go(i).numpy()
            warm_losses.append(float(loss))
            if i >= 2 and len(ctx.compiles.times) == before:
                break
    ctx.note("warmup", losses=warm_losses,
             compiles=len(ctx.compiles.times))

    # one step in flight beyond the one being waited for, as a training
    # loop that reads its loss a step late would have
    losses, done_t, pending = [], [], []

    def collect():
        losses.append(float(pending.pop(0).numpy()))
        done_t.append(time.perf_counter())

    def dispatch():
        pending.append(go(len(losses) + len(pending)))
        if len(pending) > 1:
            collect()

    def drain():
        while pending:
            collect()

    t0 = ctx.open_window()
    traced = not ctx.trace
    while time.perf_counter() - t0 < ctx.seconds:
        if not traced and time.perf_counter() - t0 >= 0.4 * ctx.seconds:
            traced = True
            drain()
            with ctx.traced_window():
                for _ in range(int(cell.get("trace_steps", 10))):
                    with ctx.span("bench/step"):
                        dispatch()
                with ctx.span("bench/step"):
                    drain()
            continue
        dispatch()
    drain()
    t1 = ctx.close_window()

    bad = [x for x in losses if not math.isfinite(x)]
    band = float(cell["check"]["step1_loss_band"])
    # what the file allows the arithmetic, plus half a step of the type
    # the program returns its loss in (bfloat16 at 11: 0.031)
    first = warm_losses[0]
    loss_tol = float(cell["check"]["step1_loss_tol"]) + (
        2.0 ** (math.floor(math.log2(abs(first)))
                - jnp.finfo(loss.dtype).nmant - 1)
        if math.isfinite(first) and first else 0.0)
    used = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    with ctx.phase("check"):
        temporaries = _planned_temporaries(step, mesh, batches[0])
        mem = harness.memory_peak_bytes(used, temporaries)
        ctx.note("memory", peak_bytes=mem,
                 planned_temporaries_bytes=temporaries)
        del step                      # frees its state before the check
        worst, tol, top, ref_loss = _check_against_reference(
            ctx, model, batches[0])
    step1_ok = (abs(first - math.log(sh["vocab"])) <= band
                and abs(first - ref_loss) <= loss_tol)
    ctx.note("check", step1_loss=first, reference_loss=ref_loss,
             loss_tol=loss_tol, ln_vocab=math.log(sh["vocab"]), band=band,
             worst_logit_diff=worst, tol=tol, top_logit=top,
             last_loss=losses[-1] if losses else None)
    return {
        "attempted": len(losses), "failed": len(bad),
        "correct": (not bad and step1_ok and worst <= tol
                    and bool(losses)),
        "checks": {"step1_loss_vs_reference": [abs(first - ref_loss),
                                               loss_tol],
                   "step1_loss_vs_ln_vocab": [
                       abs(first - math.log(sh["vocab"])), band],
                   "worst_logit_diff": [worst, tol],
                   "nonfinite_losses": [len(bad), 0],
                   "steps_min": [len(losses), 1]},
        "memory_peak_bytes": mem,
        "obs": {"kind": "train", "window": (t0, t1), "steps": len(losses),
                "step_done_t": done_t, "tokens_per_step": tokens_per_step,
                "tokens": len(losses) * tokens_per_step,
                "seq": int(cell["seq"]), "batch": int(cell["batch"]),
                "losses": losses},
    }
