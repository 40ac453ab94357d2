"""Chip smoke: the two main paths of paddle_tpu on one TPU chip, in one
process, through the entry points a user calls.

    python chip_smoke.py                 # one chip: train, then serve
    python chip_smoke.py --multichip     # four chips: the sharded step only
    python chip_smoke.py --rehearse-cpu  # sandbox rehearsal, tiny sizes

train  GPT-2-small at full width (depth 12, vocab 32768, bf16, batch
       8 x 1024), AdamW, `jit.TrainStep(donate=True)`, 5 steps on one
       fixed batch made from --seed.
serve  the same width behind `inference.create_llm_predictor` (paged
       engine, 8 slots, max_len 1024, 128-token prefill chunks, block
       16), 6 greedy requests of 32 new tokens; the answers are held
       against a second predictor on the `reference` paged core and
       against the model's own dense forward.
multichip  `ShardedTrainStep` on a dp2 x mp2 mesh over the four local
       chips (ZeRO-1, global batch 8), 3 steps, step-1 loss against a
       single-device `TrainStep` on the same seed and batch.

Every phase is fatal: a failed check raises and the exit code is not 0.
Without a TPU the script refuses to run. `--rehearse-cpu` is the only way
round that; it shrinks the sizes and never prints the success line.
Earlier lines are per-phase JSON; the last line is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
import argparse
import json
import sys
import time

import numpy as np

# losses agree to this between one device and the dp2 x mp2 mesh: both
# run bf16 weights and activations, and the mesh changes the order of
# every reduction (rehearsal on four virtual CPU devices: 1e-6)
MULTICHIP_LOSS_RTOL = 2e-2


def check(cond, msg):
    """Raise on a failed check (not `assert`: must survive python -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def sizes(rehearse):
    if rehearse:
        return dict(vocab=512, hidden=64, layers=2, heads=4, seq=128,
                    batch=2, slots=4, max_len=256, chunk=32, block=16,
                    prefix=32, tails=(8, 40, 0, 30, 60, 16), new=8,
                    pad=128)
    return dict(vocab=32768, hidden=768, layers=12, heads=12, seq=1024,
                batch=8, slots=8, max_len=1024, chunk=128, block=16,
                prefix=128, tails=(64, 256, 0, 200, 352, 100), new=32,
                pad=512)


def build_model(sz, seed):
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    pt.seed(seed)
    cfg = GPTConfig(vocab_size=sz["vocab"], hidden_size=sz["hidden"],
                    num_layers=sz["layers"], num_heads=sz["heads"],
                    max_seq_len=max(sz["seq"], sz["max_len"]),
                    dropout=0.0, attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)
    return model


def build_trainable(sz, seed, batch):
    """(model, AdamW over it, one fixed [batch, seq] batch of token ids)."""
    import paddle_tpu as pt
    model = build_model(sz, seed)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    ids = np.random.RandomState(seed).randint(
        0, sz["vocab"], (batch, sz["seq"])).astype("int32")
    return model, opt, ids


def on_platform(arrays, platform):
    return all(d.platform == platform for a in arrays for d in a.devices())


def compiles():
    """Backend compilations this process has made so far."""
    from paddle_tpu.utils import telemetry
    return int(telemetry.value("xla_compile_seconds", default=0) or 0)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_steps(step, ids, n):
    """n steps on one batch -> (losses, seconds per step incl. compile,
    compile count after each step)."""
    losses, secs, ncomp = [], [], []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(ids, ids)
        losses.append(float(loss.numpy()))      # waits for the device
        secs.append(round(time.perf_counter() - t0, 3))
        ncomp.append(compiles())
    return losses, secs, ncomp, loss


# ------------------------------------------------------------------ train
def phase_train(sz, seed, platform):
    import jax
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    from paddle_tpu.tools.xprof import train_step_spec

    model, opt, ids = build_trainable(sz, seed, sz["batch"])
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)

    losses, secs, ncomp, loss = train_steps(step, ids, 5)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[4] < losses[0], f"loss did not fall: {losses}")
    check(ncomp[4] == ncomp[1],
          f"compiled after step 2: counts per step {ncomp}")
    check(on_platform(list(step.params.values()) + [loss._data], platform),
          f"parameters or loss not on {platform}")

    # the optimized program of the step that just ran: on the chip the
    # attention must be the Pallas flash kernels, not the jnp reference
    spec = train_step_spec(step, (ids,), (ids,))
    n_kernels = spec["jitted"].lower(*spec["args"]).compile().as_text().count(
        "tpu_custom_call")
    if platform == "tpu":
        check(n_kernels >= 3, f"flash kernels missing from the step's "
              f"HLO: {n_kernels} tpu_custom_call")
    emit("train", losses=losses, step_seconds=secs,
         compiles_per_step=ncomp, tpu_custom_calls=n_kernels,
         params=sum(int(np.prod(a.shape)) for a in step.params.values()),
         peak_bytes=peak_bytes(jax.devices()[0]))


# ------------------------------------------------------------------ serve
def make_prompts(sz, seed):
    """Six prompts; the first and the last share a `prefix`-token head."""
    rng = np.random.RandomState(seed + 1)
    shared = rng.randint(0, sz["vocab"], sz["prefix"]).tolist()
    prompts = []
    for i, tail in enumerate(sz["tails"]):
        head = shared if i in (0, 5) else rng.randint(
            0, sz["vocab"], sz["prefix"] // 4).tolist()
        prompts.append(head + rng.randint(0, sz["vocab"], tail).tolist())
    return prompts


def serve(model, sz, prompts, paged_kernel):
    """Answer the prompts through the front door. Five are submitted
    together; the sixth arrives three rounds later, because a prompt
    block enters the prefix cache only once it is computed, so two
    requests admitted in one round cannot share it."""
    from paddle_tpu import inference
    cfg = inference.Config().enable_llm_engine(
        num_slots=sz["slots"], max_len=sz["max_len"],
        prefill_len=sz["chunk"], paged=True, block_size=sz["block"],
        paged_kernel=paged_kernel)
    pred = inference.create_llm_predictor(cfg, model=model)
    t0 = time.perf_counter()
    reqs = [pred.submit(prompt=p, max_tokens=sz["new"]) for p in prompts[:5]]
    pred.run(max_waves=3)
    reqs.append(pred.submit(prompt=prompts[5], max_tokens=sz["new"]))
    pred.run()
    secs = time.perf_counter() - t0
    health, snap = pred.health(), pred.metrics.snapshot()
    err = pred.scheduler.last_error
    pred.close()
    check(not snap["faults"] and health["status"] == "ok" and err is None,
          f"engine {health['status']}, faults {snap['faults']}, "
          f"last_error {err!r}")
    for r in reqs:
        check(r.finish_reason == "max_tokens"
              and len(r.output_tokens) == sz["new"],
              f"request not answered in full: {r!r} {r.error}")
    return [r.output_tokens for r in reqs], health, snap, secs


def phase_serve(sz, seed, platform):
    import jax
    import jax.numpy as jnp

    model = build_model(sz, seed)
    prompts = make_prompts(sz, seed)
    c0 = compiles()
    toks, health, snap, secs = serve(model, sz, prompts, None)
    c1 = compiles()
    ref_toks, ref_health, _, _ = serve(model, sz, prompts, "reference")
    check(ref_health["paged_kernel"] == "reference", "reference not pinned")
    check(health["decode_compiles"] == 1 and health["prefill_compiles"] == 1,
          f"programs recompiled: {health}")
    check(snap["prefix_hits"] >= sz["prefix"] // sz["block"],
          f"shared prefix missed the cache: {snap['prefix_hits']} hits")
    check(on_platform(jax.tree_util.tree_leaves(model.functional_state()),
                      platform), f"weights not on {platform}")

    # Referee: the model's own dense forward (the training path's
    # attention). bf16 logits tie often enough that two correct cores
    # part ways within a few dozen tokens, and a stream that has parted
    # says nothing after that. So: every served token must be within
    # `tol` of the dense forward's best logit at its position, and where
    # the reference core's stream first differs, its token must be too.
    model.eval()
    params, buffers = model.functional_state()
    dense = jax.jit(lambda p, b, x: model.functional_call(p, b, x)[0]._data)
    worst, parted = 0.0, []
    for prompt, out, ref in zip(prompts, toks, ref_toks):
        n = len(prompt)
        ctx = np.zeros((1, sz["pad"]), np.int32)
        ctx[0, :n + len(out) - 1] = prompt + out[:-1]
        lo = np.asarray(dense(params, buffers, ctx)[0, n - 1:n - 1 + len(out)]
                        .astype(jnp.float32))
        check(np.isfinite(lo).all(), "non-finite logits from dense forward")
        # eight bf16 steps at the size of the largest logit
        tol = 8 * 2.0 ** -8 * float(np.abs(lo).max())
        gaps = lo.max(axis=1) - lo[np.arange(len(out)), out]
        d = next((i for i in range(len(out)) if out[i] != ref[i]), None)
        parted.append(d)
        if d is not None:
            gaps = np.append(gaps, lo[d].max() - lo[d, ref[d]])
        worst = max(worst, float(gaps.max()))
        check(gaps.max() <= tol, f"served token is not the dense forward's "
              f"choice: logit gap {gaps.max():.4f} > tol {tol:.4f}")
    emit("serve", paged_kernel=health["paged_kernel"],
         requests=len(toks), tokens=[len(t) for t in toks],
         first_tokens=[t[0] for t in toks], seconds_incl_compile=round(secs, 3),
         compiles=c1 - c0, prefix_hits=snap["prefix_hits"],
         faults=snap["faults"], status=health["status"],
         first_difference_from_reference_core=parted,
         worst_logit_gap_to_dense=round(worst, 5),
         peak_bytes=peak_bytes(jax.devices()[0]))


# -------------------------------------------------------------- multichip
def phase_multichip(sz, seed, platform):
    import jax
    from paddle_tpu.distributed.mesh import make_mesh
    from paddle_tpu.distributed.sharded import ShardedTrainStep
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss

    check(len(jax.devices()) >= 4, f"need 4 devices, have {jax.devices()}")
    # one device first: the mesh, once installed, changes how the model
    # traces (the flash kernel is then called per shard)
    model, opt, ids = build_trainable(sz, seed, 8)
    one = float(TrainStep(model, gpt_pretrain_loss, opt, donate=True)(
        ids, ids).numpy())

    mesh = make_mesh({"dp": 2, "mp": 2})
    model, opt, _ = build_trainable(sz, seed, 8)
    step = ShardedTrainStep(model, gpt_pretrain_loss, opt, mesh=mesh,
                            zero_stage=1)
    losses, secs, _, _ = train_steps(step, ids, 3)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(abs(losses[0] - one) <= MULTICHIP_LOSS_RTOL * abs(one),
          f"step-1 loss {losses[0]} on the mesh vs {one} on one device")

    # spread, read from the step's live state: an mp-sharded weight and
    # its dp x mp-sharded Adam slot must each sit on four devices, in
    # shards of the declared fraction
    name = next(n for n in step.params if n.endswith("mlp.fc_in.weight"))
    w = step.params[name]
    slot = next(iter(step.opt_state[name].values()))
    spread = {}
    for label, x, frac in (("param", w, 2), ("opt_slot", slot, 4)):
        devs = {s.device for s in x.addressable_shards}
        shard = x.addressable_shards[0].data.shape
        spread[label] = {"spec": str(x.sharding.spec), "devices": len(devs),
                         "shape": list(x.shape), "shard": list(shard)}
        check(len(devs) == 4, f"{label} {name} on {len(devs)} devices")
        check(int(np.prod(shard)) * frac == int(np.prod(x.shape)),
              f"{label} {name} shard {shard} is not 1/{frac} of {x.shape}")
        check(all(d.platform == platform for d in devs),
              f"{label} not on {platform}")
    emit("multichip", mesh={"dp": 2, "mp": 2}, zero_stage=1, losses=losses,
         one_device_step1_loss=one, loss_rtol=MULTICHIP_LOSS_RTOL,
         step_seconds=secs, sharded=name, spread=spread,
         peak_bytes=[peak_bytes(d) for d in jax.devices()[:4]])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run the four-chip sharded step and nothing else")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox rehearsal at tiny sizes; never prints "
                         "the success line")
    args = ap.parse_args()

    from paddle_tpu.utils import compile_cache, telemetry
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
                 f"({dev.device_kind}); --rehearse-cpu rehearses off-chip")
    compile_cache.enable()
    telemetry.install_compile_tracking()
    sz = sizes(args.rehearse_cpu and dev.platform != "tpu")
    phases = (phase_multichip,) if args.multichip else (phase_train,
                                                        phase_serve)
    for phase in phases:
        phase(sz, args.seed, dev.platform)
    if dev.platform != "tpu":
        print("rehearsal passed on", dev.platform, "- not a chip run",
              file=sys.stderr)
        return
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
