"""Offered-load sweep for the continuous-batching serving engine.

Poisson arrivals (exponential inter-arrival gaps) with mixed prompt and
output lengths are submitted from a producer thread while the scheduler
drives decode waves; per load point we report tokens/s, p50/p99 TTFT,
and slot occupancy — one JSON line per point in the same
{"metric", "value", "unit", "detail"} shape as bench.py, plus a
BENCH_serving.json rollup next to the existing BENCH_*.json files.

    python scripts/bench_serving.py                    # default sweep
    python scripts/bench_serving.py --loads 2,8,32 --requests 24
    python scripts/bench_serving.py --family llama --slots 8
"""
import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp

from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()

import paddle_tpu as pt
from paddle_tpu.serving import (DisaggFleetRouter, FleetRouter,
                                PagedServingEngine, Scheduler,
                                ServingEngine, SLOPolicy, Tenant)
from paddle_tpu.utils import anomaly, profiler, telemetry, timeseries

t0 = time.time()


def log(m):
    print(f"[{time.time()-t0:7.1f}s] {m}", flush=True)


def build_model(family, hidden, layers, heads, vocab, max_seq_len, bf16):
    pt.seed(0)
    if family == "llama":
        from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers, num_heads=heads,
                          num_kv_heads=max(1, heads // 4),
                          max_seq_len=max_seq_len)
        model = LlamaForCausalLM(cfg)
    else:
        from paddle_tpu.nlp import GPTConfig, GPTForPretraining
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=max_seq_len, dropout=0.0,
                        attn_dropout=0.0)
        model = GPTForPretraining(cfg)
    if bf16:
        model.to(dtype=jnp.bfloat16)
    return model, cfg


def run_load(sched, load_rps, n_requests, vocab, prompt_range,
             output_range, seed, shared_prefix=()):
    """Submit n_requests at Poisson rate load_rps from a producer thread
    while this thread drives the wave loop until everything drains.
    shared_prefix tokens are prepended to EVERY prompt (the shared
    system-prompt pattern — on a paged engine with prefix sharing these
    blocks dedupe and the per-row prefix-hit rate shows it)."""
    waves_before = telemetry.value("serving_decode_waves_total",
                                   default=0)
    rng = np.random.RandomState(seed)
    shared_prefix = list(shared_prefix)
    reqs, done_submitting = [], threading.Event()

    def producer():
        for _ in range(n_requests):
            time.sleep(rng.exponential(1.0 / load_rps))
            p = shared_prefix + rng.randint(
                0, vocab, (rng.randint(*prompt_range),)).tolist()
            try:
                reqs.append(sched.submit(
                    prompt=p, max_tokens=int(rng.randint(*output_range))))
            except ValueError:
                pass        # shed (max_queue) — counted by the scheduler
        done_submitting.set()

    th = threading.Thread(target=producer, daemon=True)
    t_start = time.time()
    th.start()
    # drive waves until the producer is done and the system drains;
    # idle-spin politely while slots and queue are briefly empty
    while True:
        pending = sched.step()
        if pending == 0:
            # re-check the queue AFTER seeing the producer finished: a
            # final submit can land between step() and is_set()
            if done_submitting.is_set() and sched.queue_depth() == 0:
                break
            time.sleep(0.001)
    th.join()
    wall = time.time() - t_start
    snap = sched.metrics.snapshot()
    snap["wall_s"] = wall
    snap["offered_load_rps"] = load_rps
    snap["n_requests"] = len(reqs)
    # decode economics for the speculative comparison: rounds per
    # generated DECODE token (the first token of each request comes
    # from prefill, not a wave) — 1/lanes-ish for the plain engine,
    # measurably lower when speculation accepts drafts
    waves = telemetry.value("serving_decode_waves_total",
                            default=0) - waves_before
    decode_tokens = snap["tokens_generated"] - snap["requests_completed"]
    snap["decode_waves"] = waves
    snap["decode_rounds_per_token"] = (waves / decode_tokens
                                       if decode_tokens else None)
    return snap


def _agg(snaps, key, how):
    vals = [s[key] for s in snaps if s.get(key) is not None]
    if not vals:
        return None
    return how(vals)


def fleet_snapshot(router, reqs, wall):
    """One load point's fleet-wide view: per-replica serving snapshots
    summed where additive (tokens, prefix hits, faults), worst-case
    where they are percentiles, plus the router's own tallies
    (affinity hit rate, migrations, rebalances)."""
    # retired replicas (killed, degraded-replaced, drained away) did
    # real work this load point — the rollup must include it
    snaps = ([r.scheduler.metrics.snapshot() for r in router.replicas]
             + router.retired_metric_snapshots())
    rs = router.metrics.snapshot()
    faults = {}
    for s in snaps:
        for k, n in s["faults"].items():
            faults[k] = faults.get(k, 0) + n
    hits = _agg(snaps, "prefix_hits", sum) or 0
    misses = _agg(snaps, "prefix_misses", sum) or 0
    completed = _agg(snaps, "requests_completed", sum) or 0
    tokens = _agg(snaps, "tokens_generated", sum) or 0
    # same denominator as the single-engine rows: first-to-last-token
    # span (fleet-wide: min(first) to max(last)), NOT wall time — wall
    # includes Poisson inter-arrival idle, which would deflate fleet
    # tokens/s vs the dense/paged rows it is compared against
    first = _agg(snaps, "first_token_time", min)
    last = _agg(snaps, "last_token_time", max)
    span = (last - first) if first is not None and last is not None \
        else None
    out = {
        "requests_completed": completed,
        "tokens_generated": tokens,
        "tokens_per_s": (tokens / span if span else None),
        # worst replica's percentile: the fleet's service level is its
        # slowest member's, not an average that hides a hot replica
        "ttft_p50_s": _agg(snaps, "ttft_p50_s", max),
        "ttft_p99_s": _agg(snaps, "ttft_p99_s", max),
        "tpot_p50_s": _agg(snaps, "tpot_p50_s", max),
        "tpot_p99_s": _agg(snaps, "tpot_p99_s", max),
        "latency_p50_s": _agg(snaps, "latency_p50_s", max),
        "latency_p99_s": _agg(snaps, "latency_p99_s", max),
        "slot_occupancy": _agg(
            snaps, "slot_occupancy", lambda v: sum(v) / len(v)),
        "queue_depth_peak": _agg(snaps, "queue_depth_peak", max),
        # router-level: one refusal per REQUEST (summing the replica
        # counters would count every candidate the dispatch walked)
        "rejected": rs["rejected"],
        "faults": faults,
        "wave_retries": _agg(snaps, "wave_retries", sum) or 0,
        "block_utilization": _agg(
            snaps, "block_utilization", lambda v: sum(v) / len(v)),
        "prefix_hits": hits,
        "prefix_misses": misses,
        "prefix_hit_rate": (hits / (hits + misses)
                            if hits + misses else None),
        "prefix_hits_per_request": (hits / completed if completed
                                    else None),
        "wall_s": wall,
        "n_requests": len(reqs),
        "router": rs,
        "replicas_final": len(router.replicas),
    }
    return out


def run_load_fleet(router, load_rps, n_requests, vocab, prompt_range,
                   output_range, seed, shared_prefix=(),
                   tenant_names=None):
    """Fleet analog of run_load: Poisson submits against the router
    from a producer thread while this thread drives every replica's
    wave loop through router.step(). With tenant_names, each submit is
    billed to a seed-deterministic tenant and the snapshot grows a
    per-tenant latency table (the same arrival seed on a matched
    baseline fleet bills the same prompts to the same tenants)."""
    rng = np.random.RandomState(seed)
    shared_prefix = list(shared_prefix)
    reqs, done_submitting = [], threading.Event()

    def producer():
        for _ in range(n_requests):
            time.sleep(rng.exponential(1.0 / load_rps))
            p = shared_prefix + rng.randint(
                0, vocab, (rng.randint(*prompt_range),)).tolist()
            kw = {}
            if tenant_names:
                kw["tenant"] = tenant_names[rng.randint(
                    len(tenant_names))]
            try:
                reqs.append(router.submit(
                    prompt=p, max_tokens=int(rng.randint(*output_range)),
                    **kw))
            except ValueError:
                pass        # shed fleet-wide — counted by the replicas
        done_submitting.set()

    th = threading.Thread(target=producer, daemon=True)
    t_start = time.time()
    th.start()
    while True:
        pending = router.step()
        if pending == 0:
            if done_submitting.is_set() and router.outstanding() == 0:
                break
            time.sleep(0.001)
    th.join()
    wall = time.time() - t_start
    snap = fleet_snapshot(router, reqs, wall)
    snap["offered_load_rps"] = load_rps
    if tenant_names:
        per = {}
        for name in tenant_names:
            cohort = [r for r in reqs if r.tenant == name]
            ttfts = [r.ttft for r in cohort if r.ttft is not None]
            per[name] = {
                "requests": len(cohort),
                "completed": sum(1 for r in cohort
                                 if r.finish_reason
                                 not in ("error", "rejected")),
                "ttft_p50_ms": (None if not ttfts else round(
                    float(np.percentile(ttfts, 50)) * 1e3, 2)),
                "ttft_p99_ms": (None if not ttfts else round(
                    float(np.percentile(ttfts, 99)) * 1e3, 2)),
            }
        snap["tenants"] = per
    return snap


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="gpt", choices=["gpt", "llama"])
    ap.add_argument("--loads", default="2,8,32",
                    help="offered loads (requests/s), comma-separated")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per load point")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: overflow is shed "
                         "with finish_reason 'rejected' (per-row "
                         "'rejected' counts show shedding onset vs "
                         "offered load)")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prefill-len", type=int, default=64,
                    help="dense engine: prompt padding bucket; paged "
                         "engine: the prefill CHUNK length")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the block-table paged KV cache "
                         "(PagedServingEngine): HBM scales with "
                         "--num-blocks, utilization with actual tokens")
    ap.add_argument("--kernel", default=None,
                    choices=["reference", "lax", "pallas"],
                    help="paged: pin the paged-attention kernel "
                         "(nn/paged_attention dispatch; default: the "
                         "engine's auto choice). With a fused kernel "
                         "(lax/pallas) on a plain --paged sweep, each "
                         "load point first runs a matched "
                         "kernel=reference baseline row with the same "
                         "arrival seed, and the fused row reports "
                         "tokens/s, TPOT and program bytes_accessed "
                         "deltas against it")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged: pool size incl. scratch (default "
                         "slots*max_len/block_size + 1 = dense-"
                         "equivalent capacity; smaller oversubscribes)")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-k/verify-once speculative decoding over "
                         "the paged engine (implies --paged): each load "
                         "point runs a matched NON-speculative baseline "
                         "row first, and the speculative row reports "
                         "acceptance rate, accepted tokens/wave, decode "
                         "rounds/token and TPOT deltas against it")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="speculative: draft model depth (same family/"
                         "vocab as the target)")
    ap.add_argument("--draft-hidden", type=int, default=None,
                    help="speculative: draft hidden size (default "
                         "hidden // 2)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative: draft tokens proposed per slot "
                         "per wave (the verify chunk is k+1 wide)")
    ap.add_argument("--max-preemptions", type=int, default=16,
                    help="paged: preemption-by-recompute budget per "
                         "request before it resolves 'error' (an "
                         "oversubscribed sweep preempts on purpose; "
                         "each cycle nets tokens, so a higher budget "
                         "just trades latency, never livelock)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="serve through a FleetRouter over N replica "
                         "engines (serving/fleet): per-row router stats "
                         "— affinity hit rate, migrations, rebalances — "
                         "roll up into the output JSON")
    ap.add_argument("--router-policy", default="affinity",
                    choices=["affinity", "least_loaded", "round_robin"],
                    help="fleet routing policy (round_robin is the A/B "
                         "baseline: with --shared-prefix, affinity "
                         "should show strictly higher prefix hits per "
                         "request)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="serve through a disaggregated prefill/decode "
                         "fleet (serving/fleet/disagg): role-pinned "
                         "replicas with block-level KV handoff (implies "
                         "--paged). Each load point first runs a matched "
                         "UNIFIED fleet of the same total size with the "
                         "same arrival seed; the disagg row reports "
                         "handoff blocks/bytes and TTFT/tokens-per-s "
                         "deltas against it")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="disaggregate: prefill-role replica count")
    ap.add_argument("--decode-replicas", type=int, default=1,
                    help="disaggregate: decode-role replica count")
    ap.add_argument("--tenants", default=None,
                    help="multi-tenant QoS spec 'name:weight:priority"
                         "[,name:weight:priority...]' (e.g. "
                         "'premium:4:10,bulk:1:0'): submits are billed "
                         "to seed-deterministic tenants, every tenant "
                         "gets the sweep's --slo-* targets as its SLO "
                         "tier, and per-tenant attainment/TTFT tables "
                         "ride each row")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="fleet: autoscale ceiling (default --replicas "
                         "= no scale-up)")
    ap.add_argument("--scale-up-queue-depth", type=float, default=None,
                    help="fleet: queued requests per routable replica "
                         "that trigger a scale-up (default: autoscale "
                         "disabled)")
    ap.add_argument("--slo-ttft-p99", type=float, default=None,
                    help="SLO target: p99 TTFT in seconds — per-row "
                         "attainment + burn-rate peaks roll into "
                         "BENCH_serving.json (comparable across paged/"
                         "fleet configs); with --replicas the fleet "
                         "autoscaler consumes the burn rate")
    ap.add_argument("--slo-tpot-p99", type=float, default=None,
                    help="SLO target: p99 inter-token latency (TPOT) "
                         "in seconds")
    ap.add_argument("--slo-objective", type=float, default=0.99,
                    help="fraction of requests that must meet each SLO "
                         "latency target (error budget = 1 - objective)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many fixed tokens to every "
                         "prompt (shared system prompt) — with --paged "
                         "the prefix-hit rate per row shows the blocks "
                         "deduping")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--out", default=os.path.join(_REPO,
                                                  "BENCH_serving.json"))
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus) + /healthz on this "
                         "port during the sweep (0 picks a free port)")
    ap.add_argument("--trace-out", default=None,
                    help="record the sweep and write a chrome trace here "
                         "(request lifecycle spans + decode waves; view "
                         "in chrome://tracing / ui.perfetto.dev)")
    args = ap.parse_args()

    model, _cfg = build_model(args.family, args.hidden, args.layers,
                              args.heads, args.vocab, args.max_len,
                              args.bf16)
    if args.speculative:
        args.paged = True
        draft_model, _ = build_model(
            args.family, args.draft_hidden or max(16, args.hidden // 2),
            args.draft_layers, max(1, args.heads // 2), args.vocab,
            args.max_len, args.bf16)

    def make_paged(paged_kernel=None):
        return PagedServingEngine(model, num_slots=args.slots,
                                  max_len=args.max_len,
                                  block_size=args.block_size,
                                  num_blocks=args.num_blocks,
                                  prefill_chunk_len=args.prefill_len,
                                  paged_kernel=paged_kernel
                                  or args.kernel)

    def make_engine():
        if args.speculative:
            from paddle_tpu.serving import SpeculativePagedEngine
            return SpeculativePagedEngine(
                model, draft_model, spec_k=args.spec_k,
                num_slots=args.slots, max_len=args.max_len,
                block_size=args.block_size, num_blocks=args.num_blocks,
                prefill_chunk_len=args.prefill_len,
                paged_kernel=args.kernel)
        if args.paged:
            return make_paged()
        return ServingEngine(model, num_slots=args.slots,
                             max_len=args.max_len,
                             prefill_len=args.prefill_len)

    def make_slo():
        if args.slo_ttft_p99 is None and args.slo_tpot_p99 is None:
            return None
        return SLOPolicy(ttft_p99_s=args.slo_ttft_p99,
                         tpot_p99_s=args.slo_tpot_p99,
                         objective=args.slo_objective)

    if args.speculative and args.replicas is not None:
        raise SystemExit("--speculative measures against a matched "
                         "single-engine baseline; combine it with "
                         "--replicas in separate sweeps")
    if args.disaggregate and args.replicas is not None:
        raise SystemExit("--disaggregate sizes its fleet from "
                         "--prefill-replicas/--decode-replicas; drop "
                         "--replicas")

    def make_tenants():
        """One FRESH Tenant list per router (each router builds its own
        QoSManager; weights/priorities parsed from --tenants, the
        sweep's --slo-* targets applied as every tenant's tier)."""
        if args.tenants is None:
            return None
        out = []
        for entry in args.tenants.split(","):
            parts = entry.strip().split(":")
            if not parts[0]:
                raise SystemExit(f"--tenants: bad entry {entry!r}")
            out.append(Tenant(
                parts[0],
                weight=float(parts[1]) if len(parts) > 1 else 1.0,
                priority=int(parts[2]) if len(parts) > 2 else 0,
                slo=make_slo()))
        return out

    tenant_names = ([t.name for t in make_tenants() or []]
                    or None)
    router = None
    unified_router = None
    if args.disaggregate:
        args.paged = True             # handoff ships KV *blocks*
        n_total = args.prefill_replicas + args.decode_replicas
        router = DisaggFleetRouter(
            make_engine,
            prefill_replicas=args.prefill_replicas,
            decode_replicas=args.decode_replicas,
            qos=make_tenants(),
            policy=args.router_policy,
            min_replicas=n_total, max_replicas=n_total,
            scheduler_kwargs={"max_queue": args.max_queue,
                              "max_preemptions": args.max_preemptions})
        # the matched baseline: same total replica count, same tenancy,
        # same arrival seed per load point — only the topology differs,
        # so the disagg row's deltas isolate what disaggregation buys
        unified_router = DisaggFleetRouter(
            make_engine, prefill_replicas=0, decode_replicas=0,
            unified_replicas=n_total,
            qos=make_tenants(),
            policy=args.router_policy,
            min_replicas=n_total, max_replicas=n_total,
            scheduler_kwargs={"max_queue": args.max_queue,
                              "max_preemptions": args.max_preemptions})
        engine = router.replicas[0].engine
        log(f"disagg fleet up: {args.prefill_replicas} prefill + "
            f"{args.decode_replicas} decode replicas, "
            f"policy={args.router_policy}"
            + (f", tenants={','.join(tenant_names)}"
               if tenant_names else ""))
    elif args.replicas is not None:
        router = FleetRouter(
            make_engine, replicas=args.replicas,
            policy=args.router_policy,
            # the configured count is the sweep's floor: burn-driven
            # surplus drains (slo) must not shrink a row's fleet below
            # what the row claims to measure
            min_replicas=args.replicas,
            max_replicas=args.max_replicas or args.replicas,
            scale_up_queue_depth=args.scale_up_queue_depth,
            slo=make_slo(),
            scheduler_kwargs={"max_queue": args.max_queue,
                              "max_preemptions": args.max_preemptions})
        engine = router.replicas[0].engine
        log(f"fleet up: {args.replicas} replicas, "
            f"policy={args.router_policy}"
            + (f", autoscale to {args.max_replicas}"
               if args.scale_up_queue_depth is not None else ""))
    else:
        engine = make_engine()
    baseline_engine = None
    if args.speculative:
        # the matched non-speculative baseline: same target model, same
        # pool/chunk geometry — each load point runs it first with the
        # same arrival seed, so the speculative row's deltas compare
        # like against like
        baseline_engine = make_paged()
        Scheduler(baseline_engine).generate([1, 2, 3], max_tokens=4)
    kernel_baseline_engine = None
    if (args.kernel in ("lax", "pallas") and args.paged
            and not args.speculative and router is None):
        # the matched gather-then-attend baseline: same model and pool
        # geometry, kernel pinned to the reference pair — each load
        # point runs it first with the same arrival seed so the fused
        # row's deltas compare like against like (the PR 15 pattern)
        kernel_baseline_engine = make_paged(paged_kernel="reference")
        Scheduler(kernel_baseline_engine).generate([1, 2, 3],
                                                   max_tokens=4)
    if args.paged:
        log(f"paged pool: {engine.block_pool.usable} usable blocks x "
            f"{engine.block_size} tokens (dense equivalent would be "
            f"{args.slots * args.max_len // args.block_size})")

    if args.metrics_port is not None:
        srv = engine.start_metrics_server(port=args.metrics_port)
        log(f"metrics exporter live at {srv.url}/metrics "
            f"(and /healthz, /metrics.json)")

    # warm the programs so every load point measures execution only
    if router is not None:
        for rep in router.replicas:
            Scheduler(rep.engine).generate([1, 2, 3], max_tokens=4)
        if args.disaggregate:
            # one request through the router itself so the handoff
            # gather/scatter programs compile during warmup, not inside
            # the first measured load point
            router.generate(list(range(1, 5)), max_tokens=4)
        router.reset_metrics()        # warmup schedulers replaced too
        if unified_router is not None:
            for rep in unified_router.replicas:
                Scheduler(rep.engine).generate([1, 2, 3], max_tokens=4)
            unified_router.reset_metrics()
    else:
        sched = Scheduler(engine)
        sched.generate([1, 2, 3], max_tokens=4)
    log(f"warmup done (decode compiles={engine.decode_compiles}, "
        f"prefill compiles={engine.prefill_compiles})")

    # anomaly plane (utils/anomaly): the sampler rides every wave for
    # /metrics/history, but alert rules evaluate only at load-point
    # BOUNDARIES — a bench sweeps offered load on purpose, so per-wave
    # scoring would flag the idle->load ramp itself as a step change.
    # Warmup compiles are already banked as baseline; a clean matched
    # baseline sweep must roll up ZERO fired alerts in BENCH JSON.
    sampler = timeseries.MetricsSampler()
    alert_mgr = anomaly.AlertManager(rules=anomaly.default_serving_rules())
    alert_mgr.evaluate()              # seed detector baselines pre-sweep
    sampler.sample()
    if router is not None:
        router.attach_timeseries(sampler)

    if args.trace_out:
        profiler.start_profiler()     # record AFTER warmup: steady state

    shared_prefix = []
    if args.shared_prefix:
        shared_prefix = np.random.RandomState(7).randint(
            0, args.vocab, (args.shared_prefix,)).tolist()

    # static compile-level comparison for the kernel A/B: the fused
    # programs' bytes_accessed vs the reference engine's — one number
    # per program for the whole sweep (it is a property of the compiled
    # program, not of a load point), attached to every fused row
    kernel_bytes = None
    if kernel_baseline_engine is not None:
        from paddle_tpu.tools import xprof
        fused_roll = xprof.rollup(xprof.snapshot_programs(
            xprof.engine_program_specs(engine)))
        ref_roll = xprof.rollup(xprof.snapshot_programs(
            xprof.engine_program_specs(kernel_baseline_engine)))
        kernel_bytes = {}
        for name, m in fused_roll.items():
            fb = m.get("bytes_accessed")
            rb = ref_roll.get(name, {}).get("bytes_accessed")
            kernel_bytes[name] = {
                "fused": fb, "reference": rb,
                "saved_frac": (None if not fb or not rb
                               else round(1.0 - fb / rb, 4))}
        log("kernel A/B bytes_accessed: " + ", ".join(
            f"{n} {v['reference']}->{v['fused']}"
            for n, v in kernel_bytes.items()))

    rows = []
    kind = "paged" if args.paged else "dense"
    if args.paged and args.kernel:
        kind = f"paged[{args.kernel}]"
    if args.speculative:
        kind = f"spec[k={args.spec_k},draft={args.draft_layers}L]"
        if args.kernel:
            kind = (f"spec[k={args.spec_k},"
                    f"draft={args.draft_layers}L,{args.kernel}]")
    if args.disaggregate:
        kind = (f"disagg[{args.prefill_replicas}p+"
                f"{args.decode_replicas}d x{kind}:"
                f"{args.router_policy}]")
    elif router is not None:
        kind = (f"fleet[{args.replicas}x{kind}:"
                f"{args.router_policy}]")
    for i, load in enumerate(float(x) for x in args.loads.split(",")):
        out_hi = max(5, min(64, args.max_len - args.prefill_len))
        base_snap = None
        if baseline_engine is not None:
            base_sched = Scheduler(baseline_engine,
                                   max_queue=args.max_queue,
                                   max_preemptions=args.max_preemptions)
            base_snap = run_load(base_sched, load, args.requests,
                                 args.vocab,
                                 prompt_range=(4, args.prefill_len),
                                 output_range=(4, out_hi), seed=100 + i,
                                 shared_prefix=shared_prefix)
        kern_snap = None
        if kernel_baseline_engine is not None:
            kb_sched = Scheduler(kernel_baseline_engine,
                                 max_queue=args.max_queue,
                                 max_preemptions=args.max_preemptions)
            kern_snap = run_load(kb_sched, load, args.requests,
                                 args.vocab,
                                 prompt_range=(4, args.prefill_len),
                                 output_range=(4, out_hi), seed=100 + i,
                                 shared_prefix=shared_prefix)
        uni_snap = None
        if unified_router is not None:
            unified_router.reset_metrics()
            uni_snap = run_load_fleet(
                unified_router, load, args.requests, args.vocab,
                prompt_range=(4, args.prefill_len),
                output_range=(4, out_hi), seed=100 + i,
                shared_prefix=shared_prefix, tenant_names=tenant_names)
        if router is not None:
            router.reset_metrics()           # fresh tallies per point
            snap = run_load_fleet(router, load, args.requests,
                                  args.vocab,
                                  prompt_range=(4, args.prefill_len),
                                  output_range=(4, out_hi), seed=100 + i,
                                  shared_prefix=shared_prefix,
                                  tenant_names=tenant_names)
        else:
            # fresh metrics (and a fresh SLO window) per load point
            sched = Scheduler(engine, max_queue=args.max_queue,
                              max_preemptions=args.max_preemptions,
                              slo=make_slo())
            sched.attach_timeseries(sampler)
            snap = run_load(sched, load, args.requests, args.vocab,
                            prompt_range=(4, args.prefill_len),
                            output_range=(4, out_hi), seed=100 + i,
                            shared_prefix=shared_prefix)
        if router is not None:
            # a degraded replica may have been replaced mid-sweep:
            # compile-once must hold on every engine in the CURRENT
            # rotation, and the paged detail row below must read a
            # live pool, not the retired replica 0's
            engines = [rep.engine for rep in router.replicas]
            assert all(e.decode_compiles <= 1 for e in engines), \
                "decode step recompiled"
            engine = engines[0]
        else:
            assert engine.decode_compiles <= 1, "decode step recompiled"
        sampler.sample()
        alert_mgr.evaluate()          # quiesced boundary: rule check only
        row = {
            "metric": f"serving {args.family} {kind} tokens/s "
                      f"@{load:g}req/s x{args.slots}slots",
            "value": round(snap["tokens_per_s"] or 0.0, 1),
            "unit": "tokens/s",
            "detail": {
                "ttft_p50_ms": round((snap["ttft_p50_s"] or 0) * 1e3, 2),
                "ttft_p99_ms": round((snap["ttft_p99_s"] or 0) * 1e3, 2),
                "tpot_p50_ms": round((snap.get("tpot_p50_s") or 0) * 1e3,
                                     3),
                "tpot_p99_ms": round((snap.get("tpot_p99_s") or 0) * 1e3,
                                     3),
                "slot_occupancy": round(snap["slot_occupancy"], 4),
                "queue_depth_peak": snap["queue_depth_peak"],
                # resilience tallies THIS load point: shedding onset vs
                # offered load reads straight off the row sequence
                "rejected": snap["rejected"],
                "faults": snap["faults"],
                "wave_retries": snap["wave_retries"],
                "requests": snap["n_requests"],
                "wall_s": round(snap["wall_s"], 2),
                "offered_load_rps": load,
                "backend": jax.default_backend(),
                "num_slots": args.slots,
                "max_len": args.max_len,
                "prefill_len": args.prefill_len,
            },
        }
        if args.paged:
            # paged cache economics per load point: utilization is HBM
            # held by ACTUAL tokens (vs the dense layout's slot
            # occupancy just above), hit rate is the shared-prefix dedup
            row["detail"].update({
                "block_size": engine.block_size,
                "blocks_usable": engine.block_pool.usable,
                "block_utilization": round(
                    snap["block_utilization"] or 0.0, 4),
                "prefix_hits": snap["prefix_hits"],
                "prefix_misses": snap["prefix_misses"],
                "prefix_hit_rate": (None if snap["prefix_hit_rate"]
                                    is None
                                    else round(snap["prefix_hit_rate"],
                                               4)),
                "shared_prefix_len": args.shared_prefix,
            })
        if args.speculative:
            # the speculative economics vs the matched baseline row that
            # ran first with the same arrival seed: acceptance rate IS
            # the speedup knob, rounds/token is what it buys
            def _delta_ms(key):
                a, b = snap.get(key), base_snap.get(key)
                return (None if a is None or b is None
                        else round((a - b) * 1e3, 3))
            row["detail"]["spec"] = {
                "spec_k": args.spec_k,
                "draft_layers": args.draft_layers,
                "acceptance_rate": (
                    None if snap["spec_acceptance_rate"] is None
                    else round(snap["spec_acceptance_rate"], 4)),
                "accepted_per_wave": (
                    None if snap["spec_accepted_per_wave"] is None
                    else round(snap["spec_accepted_per_wave"], 3)),
                "decode_rounds_per_token": (
                    None if snap["decode_rounds_per_token"] is None
                    else round(snap["decode_rounds_per_token"], 4)),
                "baseline_decode_rounds_per_token": (
                    None if base_snap["decode_rounds_per_token"] is None
                    else round(base_snap["decode_rounds_per_token"], 4)),
                "tpot_p50_delta_ms": _delta_ms("tpot_p50_s"),
                "tpot_p99_delta_ms": _delta_ms("tpot_p99_s"),
            }
            base_row = {
                "metric": f"serving {args.family} paged-baseline "
                          f"tokens/s @{load:g}req/s x{args.slots}slots",
                "value": round(base_snap["tokens_per_s"] or 0.0, 1),
                "unit": "tokens/s",
                "detail": {
                    "ttft_p50_ms": round(
                        (base_snap["ttft_p50_s"] or 0) * 1e3, 2),
                    "tpot_p50_ms": round(
                        (base_snap.get("tpot_p50_s") or 0) * 1e3, 3),
                    "tpot_p99_ms": round(
                        (base_snap.get("tpot_p99_s") or 0) * 1e3, 3),
                    "decode_rounds_per_token": (
                        None
                        if base_snap["decode_rounds_per_token"] is None
                        else round(base_snap["decode_rounds_per_token"],
                                   4)),
                    "offered_load_rps": load,
                    "requests": base_snap["n_requests"],
                    "wall_s": round(base_snap["wall_s"], 2),
                },
            }
            rows.append(base_row)
            print(json.dumps(base_row), flush=True)
        if args.kernel is not None and args.paged:
            row["detail"]["kernel"] = {"paged_kernel": args.kernel}
        if kern_snap is not None:
            # the fused-vs-reference economics at THIS load point, vs
            # the matched reference row that ran first with the same
            # arrival seed: the compile-level bytes win (static, from
            # kernel_bytes) should surface as a lower measured HBM
            # residency per token at equal correctness
            def _kdelta(key, scale=1.0, nd=4):
                a, b = snap.get(key), kern_snap.get(key)
                return (None if a is None or b is None
                        else round((a - b) * scale, nd))
            row["detail"]["kernel"].update({
                "baseline_kernel": "reference",
                "tokens_per_s_delta": _kdelta("tokens_per_s", nd=1),
                "tpot_p50_delta_ms": _kdelta("tpot_p50_s", 1e3, 3),
                "tpot_p99_delta_ms": _kdelta("tpot_p99_s", 1e3, 3),
                "bytes_accessed": kernel_bytes,
            })
            kern_row = {
                "metric": f"serving {args.family} paged[reference] "
                          f"tokens/s @{load:g}req/s x{args.slots}slots",
                "value": round(kern_snap["tokens_per_s"] or 0.0, 1),
                "unit": "tokens/s",
                "detail": {
                    "paged_kernel": "reference",
                    "ttft_p50_ms": round(
                        (kern_snap["ttft_p50_s"] or 0) * 1e3, 2),
                    "tpot_p50_ms": round(
                        (kern_snap.get("tpot_p50_s") or 0) * 1e3, 3),
                    "tpot_p99_ms": round(
                        (kern_snap.get("tpot_p99_s") or 0) * 1e3, 3),
                    "offered_load_rps": load,
                    "requests": kern_snap["n_requests"],
                    "wall_s": round(kern_snap["wall_s"], 2),
                },
            }
            rows.append(kern_row)
            print(json.dumps(kern_row), flush=True)
        if router is not None:
            # router stats per load point: the affinity-vs-round_robin
            # A/B reads straight off prefix_hits_per_request across
            # two sweeps with different --router-policy
            rs = snap["router"]
            row["detail"].update({
                "replicas": (args.prefill_replicas
                             + args.decode_replicas
                             if args.disaggregate else args.replicas),
                "replicas_final": snap["replicas_final"],
                "router_policy": args.router_policy,
                "routed": rs["routed"],
                "affinity_hit_rate": (
                    None if rs["affinity_hit_rate"] is None
                    else round(rs["affinity_hit_rate"], 4)),
                "migrations": rs["migrations"],
                "rebalances": rs["rebalances"],
                "replica_restarts": rs["replica_restarts"],
                "dispatch_retries": rs["dispatch_retries"],
                "prefix_hits_per_request": (
                    None if snap["prefix_hits_per_request"] is None
                    else round(snap["prefix_hits_per_request"], 4)),
            })
        if tenant_names and "tenants" in snap:
            # per-tenant service level THIS load point: arrival-side
            # TTFT percentiles from the request stream, window-side
            # attainment/burn from the QoS manager (None without one)
            tenants_detail = {name: dict(stats)
                              for name, stats in snap["tenants"].items()}
            qos = getattr(router, "qos", None)
            if qos is not None:
                for name, srow in qos.summary().items():
                    if name in tenants_detail:
                        tenants_detail[name].update(
                            attainment=srow["attainment"],
                            burn_rate=srow["burn_rate"],
                            weight=srow["weight"],
                            priority=srow["priority"])
            row["detail"]["tenants"] = tenants_detail
        if args.disaggregate:
            # the disaggregation economics vs the matched unified fleet
            # that ran first with the same arrival seed: handoffs moved
            # BYTES (blocks gathered once, scattered once) instead of
            # burning decode rounds on chunked re-prefill
            def _ddelta(key, scale=1.0, nd=3):
                a, b = snap.get(key), uni_snap.get(key)
                return (None if a is None or b is None
                        else round((a - b) * scale, nd))
            row["detail"]["disagg"] = {
                "prefill_replicas": args.prefill_replicas,
                "decode_replicas": args.decode_replicas,
                "handoffs": rs["handoffs"],
                "handoff_blocks": rs["handoff_blocks"],
                "handoff_bytes": rs["handoff_bytes"],
                "tokens_per_s_delta": _ddelta("tokens_per_s", nd=1),
                "ttft_p50_delta_ms": _ddelta("ttft_p50_s", 1e3, 2),
                "ttft_p99_delta_ms": _ddelta("ttft_p99_s", 1e3, 2),
                "tpot_p50_delta_ms": _ddelta("tpot_p50_s", 1e3, 3),
            }
            n_total = args.prefill_replicas + args.decode_replicas
            uni_row = {
                "metric": f"serving {args.family} fleet-unified "
                          f"baseline tokens/s @{load:g}req/s "
                          f"x{args.slots}slots",
                "value": round(uni_snap["tokens_per_s"] or 0.0, 1),
                "unit": "tokens/s",
                "detail": {
                    "replicas": n_total,
                    "router_policy": args.router_policy,
                    "ttft_p50_ms": round(
                        (uni_snap["ttft_p50_s"] or 0) * 1e3, 2),
                    "ttft_p99_ms": round(
                        (uni_snap["ttft_p99_s"] or 0) * 1e3, 2),
                    "tpot_p50_ms": round(
                        (uni_snap.get("tpot_p50_s") or 0) * 1e3, 3),
                    "offered_load_rps": load,
                    "requests": uni_snap["n_requests"],
                    "wall_s": round(uni_snap["wall_s"], 2),
                },
            }
            if "tenants" in uni_snap:
                uni_row["detail"]["tenants"] = uni_snap["tenants"]
            rows.append(uni_row)
            print(json.dumps(uni_row), flush=True)
        slo_eng = (router.slo_engine if router is not None
                   else sched.slo_engine)
        if slo_eng is not None:
            # SLO attainment + burn-rate peak per load point: "at what
            # offered load does the latency promise break" reads off
            # the row sequence, comparable across paged/fleet configs
            row["detail"]["slo"] = dict(
                slo_eng.summary(),
                ttft_p99_s=args.slo_ttft_p99,
                tpot_p99_s=args.slo_tpot_p99,
                objective=args.slo_objective)
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.trace_out:
        profiler.stop_profiler(profile_path=args.trace_out)
        log(f"wrote chrome trace {args.trace_out}")

    # compile-level state of THIS engine's two programs (xprof audit):
    # the perf trajectory in BENCH_serving.json records what the
    # compiler made of the decode wave/prefill, not just wall-clock —
    # audited after the sweep so it cannot perturb a load point
    try:
        from paddle_tpu.tools import xprof
        audit_snap = xprof.snapshot_programs(
            xprof.engine_program_specs(engine))
        xprof.publish(audit_snap)
        hlo_rollup = xprof.rollup(audit_snap)
        log(f"hlo audit: " + ", ".join(
            f"{name} fusions={m['fusion_count']}"
            for name, m in hlo_rollup.items()))
    except Exception as e:  # noqa: BLE001 - best-effort bench annotation
        hlo_rollup = {"error": f"{type(e).__name__}: {e}"}

    # process-wide resilience totals for the whole sweep (per-point
    # tallies ride each row's detail): future load benches show where
    # shedding sets in and whether any fault path fired under load
    resilience = {
        # fleet runs: per-row router-level counts (one per REQUEST) —
        # the process-wide serving counter ticks once per candidate
        # replica the dispatch walked, inflating by up to the replica
        # count and contradicting the rows in the same file
        "rejected_total": (sum(r["detail"].get("rejected", 0)
                               for r in rows)
                           if router is not None else
                           telemetry.value("serving_rejected_total",
                                           default=0)),
        "wave_retries_total": telemetry.value("serving_wave_retries_total",
                                              default=0),
        "callback_errors_total": telemetry.value(
            "serving_callback_errors_total", default=0),
        "faults_total": sum(sum(r["detail"].get("faults", {}).values())
                            for r in rows),
    }
    with open(args.out, "w") as f:
        json.dump({"cmd": " ".join(sys.argv), "rows": rows,
                   "hlo_audit": hlo_rollup,
                   "resilience": resilience,
                   "alerts": alert_mgr.summary(),
                   "telemetry": telemetry.snapshot()}, f, indent=1)
    log(f"wrote {args.out}")
    if router is not None:
        router.shutdown()
    if unified_router is not None:
        unified_router.shutdown()
    engine.stop_metrics_server()


if __name__ == "__main__":
    main()
