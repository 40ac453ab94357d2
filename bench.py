"""Benchmark: GPT-2-small training throughput on the local TPU chip.

One process, one chip. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": {...}}.
vs_baseline is model-FLOPs utilisation against the device's published
bf16 peak (utils/flight_recorder.py's table; see BASELINE.md — the
reference repo publishes no numbers). Without a TPU, or on a device that
is not in the peaks table, it fails: there is no CPU fallback, and a CPU
number is never printed under this metric's name. `chip_smoke.py` is the
check that the system starts on the chip; this is the one timing.

Warm-up absorbs both slow first steps (the initial compile and the
one-time recompile for the donated buffers' on-device layouts) before
the measured window.
"""
import json
import sys
import time

import numpy as np

METRIC = "gpt2s-1024ctx train tokens/sec/chip"

_note_t0 = None


def _note(msg):
    """Progress to stderr (stdout is reserved for the one JSON line)."""
    global _note_t0
    if _note_t0 is None:
        _note_t0 = time.time()
    print(f"[bench +{time.time()-_note_t0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def run():
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.utils import compile_cache, flight_recorder as fr

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {device}")
    peaks = fr.device_peaks(dev)
    if peaks is None:
        sys.exit(f"bench: no published peak for {dev.device_kind!r} in "
                 f"utils/flight_recorder.py's table")
    compile_cache.enable()
    _note(f"device={device}")

    pt.seed(0)
    # sized to fit one v5e chip comfortably in bf16
    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=1024, dropout=0.0,
                    attn_dropout=0.0)
    batch, seq, iters = 8, 1024, 30
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32")

    model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)  # bf16 params: MXU-native
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)

    # flight recorder (memory-only): instruments warmup + a post-window
    # verification step. The measured window runs UNinstrumented — the
    # per-step block_until_ready the recorder adds must not perturb the
    # tracked perf number.
    recorder = fr.FlightRecorder(ring_size=256)
    step.attach_flight_recorder(recorder)

    # warmup: step 1 compiles; step 2 recompiles once for the donated
    # on-device buffer layouts; step 3 confirms steady state
    _note("model built; warmup (compile)")
    for i in range(3):
        loss = step(ids, ids)
        float(loss.numpy())
        _note(f"warm {i} done")
    step.detach_flight_recorder()

    # anomaly plane armed at steady state (utils/anomaly): the warmup
    # recompile is already banked as baseline, so a healthy bench must
    # report ZERO fired alerts — the rollup rides the BENCH JSON
    from paddle_tpu.utils import anomaly, timeseries
    sampler = timeseries.MetricsSampler(interval_s=0.0)
    alert_mgr = anomaly.AlertManager(rules=anomaly.default_train_rules())
    alert_mgr.evaluate()    # seed detector baselines pre-window
    sampler.sample()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, ids)
    final = float(loss.numpy())           # one device sync at the end
    dt = (time.perf_counter() - t0) / iters
    if not np.isfinite(final):
        raise RuntimeError(f"non-finite loss in bench: {final}")

    # one instrumented steady-state step -> journal MFU/sentinel rollup
    step.attach_flight_recorder(recorder)
    float(step(ids, ids).numpy())
    step.detach_flight_recorder()
    sampler.sample()
    alert_mgr.evaluate()    # a recompile inside the window fires here

    # compile-level state of the measured program (xprof audit): flops/
    # bytes from the lowering, fusion/memory from the compiled HLO —
    # the persistent cache makes the AOT compile a disk hit, and any
    # failure degrades to an error note rather than losing the bench
    _note("hlo audit (compile-level rollup)")
    try:
        from paddle_tpu.tools import xprof
        audit_snap = xprof.snapshot_programs(
            [xprof.train_step_spec(step, (ids,), (ids,))])
        xprof.publish(audit_snap, recorder=recorder)
        hlo_rollup = xprof.rollup(audit_snap)
    except Exception as e:  # noqa: BLE001 - best-effort bench annotation
        hlo_rollup = {"error": f"{type(e).__name__}: {e}"}

    tokens_per_sec = batch * seq / dt
    # model FLOPs per token (fwd+bwd ~ 6 * params for transformer)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tflops = tokens_per_sec * 6 * n_params / 1e12
    mfu = tflops * 1e12 / peaks[0]

    print(json.dumps({
        "metric": METRIC,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu, 4),
        "device": device,
        "detail": {"step_ms": round(dt * 1e3, 2), "loss": round(final, 3),
                   "model_tflops": round(tflops, 2), "params": n_params,
                   "batch": batch,
                   "flight_recorder": fr.rollup(recorder.events()),
                   "hlo_audit": hlo_rollup,
                   "alerts": alert_mgr.summary()},
    }))


if __name__ == "__main__":
    run()
