"""Scaling-efficiency harness for the BASELINE.md 8->256-chip metric.

Sweeps the flagship training step over CPU-mesh sizes n in {8,16,32}
(each in a fresh subprocess — the virtual device count is fixed at
backend init), extracts the collective operations from the partitioned
HLO (counts, per-device operand bytes, replica-group spans), and fits a
communication cost model to extrapolate DP scaling efficiency to a 256
chip v5e pod slice. Writes docs/perf/SCALING.md + scaling_probe.json.

The extrapolation is a MODEL, clearly labelled: per-device grad
allreduce bytes are ~constant in n (ring: 2*(n-1)/n * B), so the DP
efficiency floor is set by the allreduce time vs per-step compute at a
stated ICI bandwidth — the methodology BASELINE.md's TBD row asks for.

Usage:
  python scripts/scaling_probe.py           # full sweep + report
  python scripts/scaling_probe.py --one 16 dp 8 mp 2   # single config
"""
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")


def _shape_bytes(shape_str):
    """'f32[128,512]' -> bytes; handles tuple shapes '(f32[2], f32[3])'."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def _group_span(line, n_dev):
    """Devices spanned by one collective group on this line."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)", line)
    if m:                      # iota form: [ngroups, group_size]<=[n]
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return n_dev


def analyze_hlo(txt, n_dev):
    """Collective census of a partitioned HLO module: per kind -> count,
    per-device operand bytes, span histogram."""
    out = {k: {"count": 0, "bytes": 0, "spans": {}} for k in _COLLECTIVES}
    for ln in txt.splitlines():
        s = ln.strip()
        m = re.match(r"%?[\w.\-]+ = (\([^)]*\)|[\w\[\],]+) ([\w\-]+)\(", s)
        if not m:
            continue
        kind = m.group(2)
        if kind.rstrip("-start").rstrip("-done") in _COLLECTIVES:
            kind = kind.replace("-start", "").replace("-done", "")
        if kind not in _COLLECTIVES:
            continue
        if "-done" in s.split("(")[0]:
            continue            # avoid double counting async pairs
        rec = out[kind]
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(m.group(1))
        span = _group_span(s, n_dev)
        rec["spans"][str(span)] = rec["spans"].get(str(span), 0) + 1
    return out


def run_one(n_dev, axes):
    """Compile the sharded step on an n_dev CPU mesh; return census."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    from paddle_tpu.distributed.mesh import make_mesh
    from paddle_tpu.distributed.sharded import ShardedTrainStep

    make_mesh(axes)
    pt.seed(0)
    # gpt2s layer geometry (hidden 768) but 2 layers / small vocab so the
    # 32-device CPU compile stays fast; per-layer collective structure is
    # what matters and it is layer-count invariant
    cfg = GPTConfig(vocab_size=2048, hidden_size=768, num_layers=2,
                    num_heads=12, max_seq_len=256, dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    step = ShardedTrainStep(model, gpt_pretrain_loss, opt, zero_stage=1,
                            donate=False)
    dp = axes.get("dp", 1)
    ids = np.random.RandomState(0).randint(0, 2048,
                                           (2 * dp, 256)).astype("int32")
    inputs = step._shard_batch((ids,))
    labels = step._shard_batch((ids,))
    lowered = step._compiled.lower(
        step.params, step.buffers, step.opt_state, step.grad_acc,
        jax.random.PRNGKey(0), jnp.float32(1e-4), jnp.int32(1),
        inputs, labels)
    txt = lowered.compile().as_text()
    census = analyze_hlo(txt, n_dev)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return {"n": n_dev, "axes": axes, "params": n_params,
            "collectives": census}


# ------------------------------------------------------------- cost model

V5E_PEAK_TFLOPS = 197.0          # bf16 per chip
V5E_ICI_GBPS = 45.0              # assumed per-direction ring bandwidth/chip
MEASURED_MFU = 0.414             # last on-chip measurement (PERF.md, r2)


def dp_efficiency(grad_bytes, step_flops, n, mfu=MEASURED_MFU,
                  bw=V5E_ICI_GBPS * 1e9, overlap=0.5):
    """Ring-allreduce cost model: t_comm = 2B(n-1)/n / bw; efficiency =
    t_compute / (t_compute + (1-overlap) * t_comm)."""
    t_compute = step_flops / (V5E_PEAK_TFLOPS * 1e12 * mfu)
    t_comm = 2.0 * grad_bytes * (n - 1) / n / bw
    return t_compute / (t_compute + (1.0 - overlap) * t_comm)


def main():
    if "--one" in sys.argv:
        i = sys.argv.index("--one")
        n = int(sys.argv[i + 1])
        kv = sys.argv[i + 2:]
        axes = {kv[j]: int(kv[j + 1]) for j in range(0, len(kv), 2)}
        print(json.dumps(run_one(n, axes)), flush=True)
        return

    sweeps = [
        (8, {"dp": 8}), (16, {"dp": 16}), (32, {"dp": 32}),
        (8, {"dp": 4, "mp": 2}), (16, {"dp": 8, "mp": 2}),
        (32, {"dp": 16, "mp": 2}),
    ]
    results = []
    for n, axes in sweeps:
        args = [sys.executable, os.path.abspath(__file__), "--one", str(n)]
        for k, v in axes.items():
            args += [k, str(v)]
        print(f"[scaling] n={n} axes={axes} ...", file=sys.stderr,
              flush=True)
        p = subprocess.run(args, capture_output=True, text=True,
                           timeout=1800,
                           env={**os.environ,
                                "PYTHONPATH": REPO + ":" + os.environ.get(
                                    "PYTHONPATH", "")})
        if p.returncode != 0:
            print(f"[scaling] FAILED: {p.stderr[-800:]}", file=sys.stderr)
            continue
        results.append(json.loads(p.stdout.strip().splitlines()[-1]))

    out_json = os.path.join(REPO, "docs", "perf", "scaling_probe.json")
    with open(out_json, "w") as f:
        json.dump(results, f, indent=1)
    _write_report(results)
    print(json.dumps({"summary": "scaling_probe", "rows": len(results)}))


def _write_report(results):
    lines = [
        "# Scaling methodology: 8 -> 256 chips",
        "",
        "BASELINE.md's scaling-efficiency row needs multi-pod hardware this",
        "environment does not have (one v5e chip). This report",
        "provides what CAN be produced honestly: the partitioned-HLO",
        "collective census of the real training step at n = 8/16/32",
        "(virtual CPU mesh — the SPMD partitioner emits the same program",
        "structure it would for TPU meshes), plus a stated-assumption cost",
        "model extrapolating DP efficiency to 256 chips.",
        "",
        "Step config: GPT (hidden 768, 12 heads, seq 256, 2 layers),",
        "AdamW + ZeRO-1, bf16-ready; per-layer collective structure is",
        "layer-count invariant, so the census scales linearly in depth.",
        "",
        "## Collective census (per-device, one training step)",
        "",
        "| n | mesh | all-reduce | AR bytes/dev | all-gather | AG bytes | "
        "reduce-scatter | RS bytes | permute/a2a |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in results:
        c = r["collectives"]
        mesh = "x".join(f"{k}{v}" for k, v in r["axes"].items())
        ar, ag, rs = c["all-reduce"], c["all-gather"], c["reduce-scatter"]
        pa = (c["collective-permute"]["count"] + c["all-to-all"]["count"])
        lines.append(
            f"| {r['n']} | {mesh} | {ar['count']} | {ar['bytes']:,} | "
            f"{ag['count']} | {ag['bytes']:,} | {rs['count']} | "
            f"{rs['bytes']:,} | {pa} |")
    lines += [
        "",
        "Key observation to verify in the table: pure-DP per-device",
        "all-reduce bytes stay ~constant as n grows (ring allreduce moves",
        "2B(n-1)/n per device) — the property that makes DP scaling",
        "efficiency flat-ish in n until the latency term bites.",
        "",
        "## Cost-model extrapolation (stated assumptions)",
        "",
        f"- v5e peak {V5E_PEAK_TFLOPS} bf16 TFLOP/s/chip; measured MFU "
        f"{MEASURED_MFU} (PERF.md round-2 on-chip measurement)",
        f"- ICI ring bandwidth {V5E_ICI_GBPS} GB/s per direction per chip",
        "- 50% compute/comm overlap (XLA latency-hiding scheduler;",
        "  conservative — measured overlap is usually higher)",
        "- gradient bytes = bf16 grads of the gpt2s 124M param model",
        "",
        "| n | predicted DP efficiency |",
        "|---|---|",
    ]
    # gpt2s-scale grads in bf16
    grad_bytes = 124e6 * 2
    step_flops = 6 * 124e6 * 8 * 1024     # b=8, s=1024 tokens
    for n in (8, 16, 32, 64, 128, 256):
        eff = dp_efficiency(grad_bytes, step_flops, n)
        lines.append(f"| {n} | {eff:.3f} |")
    lines += [
        "",
        "Per-chip throughput at 256 chips is predicted at "
        f"{dp_efficiency(grad_bytes, step_flops, 256):.1%} of the",
        "single-chip rate for pure DP at gpt2s scale; larger models push",
        "this UP (compute grows faster than grad bytes). The census rows",
        "above are measured program structure; only the time model is",
        "assumption-based. Refresh with scripts/scaling_probe.py.",
        "",
    ]
    path = os.path.join(REPO, "docs", "perf", "SCALING.md")
    with open(path, "w") as f:
        f.write("\n".join(lines))


if __name__ == "__main__":
    main()
