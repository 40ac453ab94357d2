#!/usr/bin/env python
"""chaos_serving — drive the serving/training resilience layer through
every chaos fault class and assert the post-fault invariants.

Same positive-control discipline as hlo_audit/jxaudit: each scenario
arms a deterministic `utils.chaos` fault, runs a request stream, and
checks the engine RECOVERED — poisoned slot isolated (healthy slots
token-identical to a fault-free run), transient wave error retried
within budget, failed prefill contained, callback exception counted,
queue overflow shed, drain graceful, checkpoint crash survivable, a
KILLED FLEET REPLICA's in-flight requests finished token-identically
on a survivor (replica_failover), a router dispatch fault rerouted —
all with the decode wave still compiled exactly once. `--inject`
proves the runner itself: it disables one resilience property and must
exit 1.

    python scripts/chaos_serving.py                   # all scenarios
    python scripts/chaos_serving.py --smoke           # tier-1 entry
    python scripts/chaos_serving.py --scenario replica_failover
    python scripts/chaos_serving.py --inject drop-isolation   # exit 1
    python scripts/chaos_serving.py --inject no-migration     # exit 1
    python scripts/chaos_serving.py --json --journal chaos.jsonl

Exit codes: 0 every invariant holds, 1 violated invariant, 2 internal
error. Tier-1 runs --smoke and both injections in-process
(tests/test_chaos.py).
"""
import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()

import numpy as np

import paddle_tpu as pt
from paddle_tpu.serving import Scheduler, ServingEngine
from paddle_tpu.utils import (anomaly, chaos, flight_recorder,
                              telemetry, timeseries)

# canonical tiny scale == tests/test_serving.py fixture, so tier-1
# shares one persistent-cache compile of the decode wave/prefill
VOCAB, HIDDEN, LAYERS, HEADS, KV_HEADS = 128, 64, 2, 4, 2
SLOTS, MAX_LEN, PREFILL_LEN = 4, 64, 16
MAX_TOKENS = 6

_CACHE = {}


def get_model():
    """One canonical tiny LLaMA per process — every engine (dense,
    paged, and each fleet replica) serves the same weights, so the
    persistent cache shares compiles and fleet migration's
    identical-weights precondition holds by construction."""
    if "model" not in _CACHE:
        from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
        pt.seed(7)
        cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=HIDDEN,
                          num_layers=LAYERS, num_heads=HEADS,
                          num_kv_heads=KV_HEADS, max_seq_len=MAX_LEN)
        _CACHE["model"] = LlamaForCausalLM(cfg)
    return _CACHE["model"]


def get_engine():
    """One engine per process (scenarios reset its health; compile-once
    across ALL of them is itself the final invariant)."""
    if "engine" not in _CACHE:
        engine = ServingEngine(get_model(), num_slots=SLOTS,
                               max_len=MAX_LEN, prefill_len=PREFILL_LEN)
        Scheduler(engine).generate([1, 2, 3], max_tokens=2)   # warm
        _CACHE["engine"] = engine
        _CACHE["compiles_after_warm"] = telemetry.compile_count(
            "serving_decode_wave")
    return _CACHE["engine"]


def get_paged_engine():
    """One PAGED engine per process (cache_exhaustion scenario) — same
    canonical model scale as tests/test_serving_paged.py, so tier-1
    shares one persistent-cache compile of the paged programs."""
    if "paged_engine" not in _CACHE:
        engine = _paged_factory()
        Scheduler(engine).generate([1, 2, 3], max_tokens=2)   # warm
        _CACHE["paged_engine"] = engine
    return _CACHE["paged_engine"]


def _paged_factory():
    """Fleet replica factory: the canonical paged engine shape over the
    shared model (each replica owns its caches/block pool)."""
    from paddle_tpu.serving import PagedServingEngine
    return PagedServingEngine(
        get_model(), num_slots=SLOTS, max_len=MAX_LEN,
        block_size=8, num_blocks=33, prefill_chunk_len=PREFILL_LEN)


def _prompts(n=SLOTS):
    return [np.random.RandomState(100 + i)
            .randint(0, VOCAB, (4 + i % 3,)).tolist() for i in range(n)]


def _run_stream(engine, prompts, **submit_kw):
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=MAX_TOKENS, **submit_kw)
            for p in prompts]
    sched.run()
    return sched, reqs


def _reference(engine, prompts):
    """Fault-free greedy outputs for `prompts` (greedy decode ignores
    the PRNG stream, so the reference is engine-state-independent)."""
    key = ("ref", tuple(tuple(p) for p in prompts))
    if key not in _CACHE:
        _, reqs = _run_stream(engine, prompts)
        _CACHE[key] = [r.output_tokens for r in reqs]
    return _CACHE[key]


def _check(violations, cond, msg):
    if not cond:
        violations.append(msg)


# ---------------------------------------------------------------------------
# scenarios — each returns a list of violated invariants (empty = pass)
# ---------------------------------------------------------------------------

def scenario_nan_slot(engine, inject):
    """Poisoned slot: NaN logits in one lane retire ONLY that request
    (finish_reason "error"); healthy lanes stream token-identically to
    a fault-free run. --inject drop-isolation poisons EVERY lane while
    the invariants still expect isolation — the checker must fail."""
    v = []
    prompts = _prompts()
    ref = _reference(engine, prompts)
    payload = list(range(SLOTS)) if inject == "drop-isolation" else 1
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.DECODE_WAVE_NAN, action="payload", payload=payload,
        times=(2,))])
    with chaos.active(monkey):
        sched, reqs = _run_stream(engine, prompts)
    _check(v, monkey.fired, "nan injection never fired")
    _check(v, reqs[1].finish_reason == "error",
           f"poisoned slot finished {reqs[1].finish_reason!r}, "
           "expected 'error'")
    for i in (0, 2, 3):
        _check(v, reqs[i].output_tokens == ref[i],
               f"healthy slot {i} output diverged from the fault-free "
               "run — poison leaked across lanes")
    _check(v, sched.metrics.snapshot()["faults"].get("nonfinite", 0) >= 1,
           "serving_faults_total{kind=nonfinite} did not move")
    return v


def scenario_wave_error(engine, inject):
    """Transient decode-wave exception: retried with backoff, stream
    completes, outputs untouched. --inject no-retry zeroes the retry
    budget so the engine degrades — the completion invariant fails."""
    v = []
    prompts = _prompts()
    ref = _reference(engine, prompts)
    retries = 0 if inject == "no-retry" else 3
    monkey = chaos.ChaosMonkey([chaos.Fault(chaos.DECODE_WAVE,
                                            times=(2,))])
    with chaos.active(monkey):
        sched = Scheduler(engine, wave_retries=retries,
                          retry_backoff_s=0.001)
        reqs = [sched.submit(prompt=p, max_tokens=MAX_TOKENS)
                for p in prompts]
        sched.run()
    snap = sched.metrics.snapshot()
    for i, r in enumerate(reqs):
        _check(v, r.output_tokens == ref[i],
               f"request {i} did not recover within the retry budget "
               f"(finish={r.finish_reason!r})")
    _check(v, snap["wave_retries"] >= 1,
           "serving_wave_retries_total did not move")
    _check(v, engine.health_state == "ok",
           f"engine health {engine.health_state!r} after a transient "
           "fault, expected 'ok'")
    return v


def scenario_slow_wave(engine, inject):
    """Injected wave latency: slow is not broken — everything completes
    with outputs untouched."""
    v = []
    prompts = _prompts()
    ref = _reference(engine, prompts)
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.DECODE_WAVE, action="delay", delay_s=0.02, times=(2, 3))])
    with chaos.active(monkey):
        _, reqs = _run_stream(engine, prompts)
    _check(v, len(monkey.fired) == 2, "slow-wave injection never fired")
    for i, r in enumerate(reqs):
        _check(v, r.output_tokens == ref[i],
               f"request {i} output diverged under injected latency")
    return v


def scenario_prefill_error(engine, inject):
    """Failing prefill: the admission fails ONLY its request; the slot
    is not leaked and later admissions land in it."""
    v = []
    prompts = _prompts()
    ref = _reference(engine, prompts)
    monkey = chaos.ChaosMonkey([chaos.Fault(chaos.PREFILL, times=(2,))])
    with chaos.active(monkey):
        sched, reqs = _run_stream(engine, prompts)
    _check(v, reqs[1].finish_reason == "error",
           f"failed-prefill request finished {reqs[1].finish_reason!r}, "
           "expected 'error'")
    for i in (0, 2, 3):
        _check(v, reqs[i].output_tokens == ref[i],
               f"request {i} output diverged after a neighbour's "
               "prefill failure")
    _check(v, len(engine.free_slots()) == SLOTS,
           "slot leaked by the failed prefill")
    _check(v, sched.metrics.snapshot()["faults"].get("prefill_error", 0)
           == 1, "serving_faults_total{kind=prefill_error} did not move")
    return v


def scenario_callback_error(engine, inject):
    """Injected exception in a client on_token callback: contained to
    `callback_error`, counted, and the request still completes."""
    v = []
    before = telemetry.value("serving_callback_errors_total", default=0)
    monkey = chaos.ChaosMonkey([chaos.Fault(chaos.CALLBACK, times=(1,),
                                            max_fires=1)])
    seen = []
    with chaos.active(monkey):
        _, reqs = _run_stream(engine, _prompts(2),
                              on_token=lambda r, t: seen.append((r, t)))
    after = telemetry.value("serving_callback_errors_total", default=0)
    _check(v, isinstance(reqs[0].callback_error, chaos.ChaosError),
           "injected callback exception was not contained into "
           "callback_error")
    _check(v, len(reqs[0].output_tokens) == MAX_TOKENS,
           "request with a failing callback did not complete")
    _check(v, after - before == 1,
           f"serving_callback_errors_total moved {after - before}, "
           "expected 1")
    _check(v, all(len(r.output_tokens) == MAX_TOKENS for r in reqs),
           "a client callback fault leaked into the wave loop")
    return v


def scenario_overflow_shed(engine, inject):
    """Bounded admission queue: overflow sheds with finish_reason
    'rejected' (a clean ValueError), accepted work completes."""
    from paddle_tpu.serving import Request
    v = []
    sched = Scheduler(engine, max_queue=2)
    accepted, shed = [], []
    for p in _prompts(6):
        req = Request(prompt=p, max_tokens=MAX_TOKENS)
        try:
            sched.submit(request=req)
            accepted.append(req)
        except ValueError:
            shed.append(req)
    sched.run()
    snap = sched.metrics.snapshot()
    _check(v, len(accepted) == 2, f"accepted {len(accepted)}, expected "
           "max_queue=2 to bound admission")
    _check(v, len(shed) == 4 and all(r.finish_reason == "rejected"
                                     for r in shed),
           "shed requests did not resolve with finish_reason 'rejected'")
    _check(v, snap["rejected"] == 4,
           f"serving_rejected_total moved {snap['rejected']}, expected 4")
    _check(v, all(r.done and r.finish_reason != "rejected"
                  for r in accepted),
           "an accepted request did not complete after shedding")
    return v


def scenario_drain(engine, inject):
    """Graceful drain: accepted requests (queued or in-slot) complete,
    new submits shed, /healthz says 'draining'."""
    v = []
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=MAX_TOKENS)
            for p in _prompts(6)]                 # 4 slots + 2 queued
    sched.step()
    sched.drain()
    _check(v, engine.health_state == "draining",
           f"health {engine.health_state!r} after drain(), expected "
           "'draining'")
    from paddle_tpu.serving import Request
    late = Request(prompt=[1, 2], max_tokens=2)
    try:
        sched.submit(request=late)
        _check(v, False, "submit() accepted work while draining")
    except ValueError:
        pass
    _check(v, late.finish_reason == "rejected",
           f"post-drain submit resolved {late.finish_reason!r}, "
           "expected 'rejected'")
    sched.run()
    _check(v, all(r.done and r.finish_reason not in ("rejected", "error")
                  for r in reqs),
           "an accepted request did not complete through drain")
    return v


def scenario_ckpt_crash(engine, inject):
    """Crash during checkpoint write: the previous checkpoint stays the
    manifest's 'latest' and Model.load_latest resumes from it."""
    from paddle_tpu import hapi
    from paddle_tpu.framework import serialization
    v = []
    with tempfile.TemporaryDirectory(prefix="chaos_ckpt_") as d:
        pt.seed(0)
        net1 = pt.nn.Linear(4, 2)
        hapi.Model(net1).save(os.path.join(d, "step1"), training=False)
        want = {k: t.numpy().copy() for k, t in net1.state_dict().items()}
        pt.seed(99)
        crashed = False
        monkey = chaos.ChaosMonkey([chaos.Fault(chaos.CHECKPOINT_WRITE,
                                                times=(1,))])
        try:
            with chaos.active(monkey):
                hapi.Model(pt.nn.Linear(4, 2)).save(
                    os.path.join(d, "step2"), training=False)
        except chaos.ChaosError:
            crashed = True
        _check(v, crashed, "checkpoint-write fault never fired")
        _check(v, not os.path.exists(os.path.join(d, "step2.pdparams")),
               "torn write reached the destination checkpoint file")
        doc = serialization.read_manifest(d)
        _check(v, doc is not None and doc["path"] == "step1",
               f"manifest no longer points at the complete checkpoint: "
               f"{doc!r}")
        net3 = pt.nn.Linear(4, 2)
        prefix = hapi.Model(net3).load_latest(d)
        _check(v, prefix is not None and prefix.endswith("step1"),
               f"load_latest resumed from {prefix!r}, expected step1")
        if prefix is not None:
            same = all(np.allclose(net3.state_dict()[k].numpy(), want[k])
                       for k in want)
            _check(v, same, "resumed weights differ from the last "
                   "complete checkpoint")
    return v


def scenario_cache_exhaustion(engine, inject):
    """Paged KV pool exhaustion at admission: the allocator reporting
    'no free blocks' is CAPACITY — the request waits at the queue head
    for in-flight work to free blocks (or sheds 'rejected' when nothing
    could), and every request still completes with outputs untouched.
    --inject alloc-crash swaps the payload fault for a RAISE out of the
    allocator (a crashing allocator, not an exhausted one): that request
    resolves 'error' and the completes-via-requeue invariant must catch
    it."""
    v = []
    prompts = _prompts()
    ref = _paged_reference(prompts)
    paged = get_paged_engine()
    action = "raise" if inject == "alloc-crash" else "payload"
    # invocation 2: the FIRST admission holds blocks, so the second
    # admission's exhaustion has in-flight work to wait behind
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.CACHE_ALLOC, action=action, payload=True, times=(2,))])
    with chaos.active(monkey):
        sched, reqs = _run_stream(paged, prompts)
    snap = sched.metrics.snapshot()
    _check(v, monkey.fired, "cache_alloc injection never fired")
    for i, r in enumerate(reqs):
        _check(v, r.finish_reason not in ("error", None),
               f"request {i} resolved {r.finish_reason!r} — exhaustion "
               "must shed/queue via requeue, never crash a request")
        if r.finish_reason == "max_tokens":
            _check(v, r.output_tokens == ref[i],
                   f"request {i} output diverged after the allocator "
                   "requeue")
    _check(v, snap["faults"].get("cache_exhausted", 0) >= 1,
           "serving_faults_total{kind=cache_exhausted} did not move")
    _check(v, paged.health_state == "ok",
           f"paged engine health {paged.health_state!r} after capacity "
           "pressure, expected 'ok'")
    _check(v, paged.decode_compiles == 1,
           "paged decode wave recompiled under allocator faults")
    return v


def _paged_reference(prompts):
    """Fault-free greedy outputs from ONE paged engine — the fleet must
    match these bitwise whatever the routing/failover did (identical
    weights + greedy decode = engine-count-independent trajectory)."""
    paged = get_paged_engine()
    for s in paged.active_slots():
        paged.retire_slot(s)
    paged.set_health_state("ok")
    key = ("paged_ref", tuple(tuple(p) for p in prompts))
    if key not in _CACHE:
        _, ref_reqs = _run_stream(paged, prompts)
        _CACHE[key] = [r.output_tokens for r in ref_reqs]
    return _CACHE[key]


def get_spec_engine():
    """One SPECULATIVE paged engine per process (spec_rollback
    scenario): the canonical paged scale plus a 1-layer draft, so
    tier-1 shares compiles with tests/test_serving_spec.py."""
    if "spec_engine" not in _CACHE:
        from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import SpeculativePagedEngine
        pt.seed(23)
        dcfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32,
                           num_layers=1, num_heads=2, num_kv_heads=1,
                           max_seq_len=MAX_LEN)
        draft = LlamaForCausalLM(dcfg)
        # inflate one embedding row so the draft frequently DISAGREES
        # with the target: rejections are what give the rollback audit
        # (and the no-rollback control) something to catch — a draft
        # that always agrees never over-allocates
        w = draft.model.embed_tokens.weight.numpy().copy()
        w[VOCAB - 1] += 5.0
        draft.model.embed_tokens.weight.set_value(w)
        engine = SpeculativePagedEngine(
            get_model(), draft, spec_k=3,
            num_slots=SLOTS, max_len=MAX_LEN, block_size=8,
            num_blocks=33, prefill_chunk_len=PREFILL_LEN)
        Scheduler(engine).generate([1, 2, 3], max_tokens=2)   # warm
        _CACHE["spec_engine"] = engine
    return _CACHE["spec_engine"]


def scenario_spec_rollback(engine, inject):
    """Speculative decoding under chaos: a DECODE_WAVE_NAN fault during
    a speculative wave retires ONLY the poisoned lane — its whole
    speculation (blocks allocated ahead for drafted tokens) rolled
    back, healthy lanes token-identical to the fault-free run — and the
    refcount audit holds after EVERY round: no lane ever retains blocks
    past its committed positions, and the drained pool returns to 0
    used (draft pools share the tables, so one audit covers both).
    --inject no-rollback disables the engine's spec-block rollback; the
    per-round block audit must catch the orphaned draft blocks."""
    v = []
    spec = get_spec_engine()
    for s in spec.active_slots():
        spec.retire_slot(s)
    spec.set_health_state("ok")
    prompts = _prompts()
    ref = _spec_reference(prompts)
    if inject == "no-rollback":
        real = spec._rollback_spec_blocks
        spec._rollback_spec_blocks = lambda wave_slots: None
    try:
        monkey = chaos.ChaosMonkey([chaos.Fault(
            chaos.DECODE_WAVE_NAN, action="payload", payload=1,
            times=(2,))])
        over_held = 0
        with chaos.active(monkey):
            sched = Scheduler(spec)
            reqs = [sched.submit(prompt=p, max_tokens=MAX_TOKENS)
                    for p in prompts]
            while sched.step():
                for s in range(spec.num_slots):
                    if spec.slot_active[s] and \
                            len(spec._slot_blocks[s]) > \
                            spec.slot_pos[s] // spec.block_size + 1:
                        over_held += 1
    finally:
        if inject == "no-rollback":
            spec._rollback_spec_blocks = real
    _check(v, monkey.fired, "nan injection never fired")
    _check(v, reqs[1].finish_reason == "error",
           f"poisoned lane finished {reqs[1].finish_reason!r}, "
           "expected 'error'")
    for i in (0, 2, 3):
        _check(v, reqs[i].output_tokens == ref[i],
               f"healthy lane {i} diverged from the fault-free "
               "speculative run")
    _check(v, over_held == 0,
           f"orphaned speculative blocks: {over_held} round(s) held "
           "blocks past the committed positions (rollback missing)")
    _check(v, spec.block_pool.used == 0,
           f"blocks {spec.block_pool.outstanding()} still referenced "
           "after the stream drained — speculative refcounts leaked")
    _check(v, sched.metrics.snapshot()["faults"].get("nonfinite", 0) >= 1,
           "serving_faults_total{kind=nonfinite} did not move")
    _check(v, spec.decode_compiles == 1 and spec.draft_compiles == 1
           and spec.prefill_compiles == 1,
           "speculative configuration exceeded its three compiled "
           "programs under fault load")
    return v


def _spec_reference(prompts):
    """Fault-free greedy outputs from the speculative engine (greedy
    speculative == greedy target trajectory, so this also equals the
    paged reference — asserted once here, cheaply, as a bonus)."""
    key = ("spec_ref", tuple(tuple(p) for p in prompts))
    if key not in _CACHE:
        spec = get_spec_engine()
        _, reqs = _run_stream(spec, prompts)
        _CACHE[key] = [r.output_tokens for r in reqs]
    return _CACHE[key]


def scenario_replica_failover(engine, inject):
    """THE fleet proof: a replica killed mid-stream has every accepted
    request finish on a surviving replica with output bitwise-equal to
    the no-fault run — in-flight work is resubmitted as prompt + tokens
    generated so far (the preemption-by-recompute discipline, across
    engines) — a digest-verified replacement joins the rotation, and
    each surviving replica's decode wave stays compiled once.
    --inject no-migration disables failover, so the killed replica's
    in-flight requests resolve 'error' and the token-identity check
    must fail."""
    from paddle_tpu.serving import fleet
    v = []
    prompts = _prompts(6)
    ref = _paged_reference(prompts)
    router = fleet.FleetRouter(_paged_factory, replicas=2,
                               migrate=(inject != "no-migration"))
    reqs = [router.submit(prompt=p, max_tokens=MAX_TOKENS)
            for p in prompts]
    # fleet-step invocation 2: requests are dispatched and the first
    # wave ran, so the victim holds live mid-stream work
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.REPLICA_KILL, action="payload", payload=0, times=(2,))])
    with chaos.active(monkey):
        router.run()
    snap = router.metrics.snapshot()
    _check(v, monkey.fired, "replica_kill injection never fired")
    _check(v, snap["replica_kills"] == 1, "kill not recorded")
    for i, r in enumerate(reqs):
        _check(v, r.finish_reason == "max_tokens",
               f"request {i} resolved {r.finish_reason!r} — a killed "
               "replica's accepted work must complete via migration")
        _check(v, r.output_tokens == ref[i],
               f"request {i} output diverged from the no-fault run "
               "after migration")
    _check(v, snap["migrations"] >= 1,
           "fleet_migrations_total did not move")
    _check(v, snap["replica_restarts"] == 1,
           f"expected 1 digest-verified replacement, got "
           f"{snap['replica_restarts']}")
    _check(v, router.health()["routable"] == 2,
           "replacement replica did not rejoin the rotation")
    for rep in router.replicas:
        _check(v, rep.engine.decode_compiles <= 1,
               f"replica {rep.replica_id} decode wave recompiled under "
               "failover")
    router.shutdown()
    return v


def scenario_router_dispatch(engine, inject):
    """A dispatch fault (crashed/unreachable replica at hand-off time)
    must reroute the request to the next candidate — accepted work is
    never lost to one bad hand-off — with outputs untouched."""
    from paddle_tpu.serving import fleet
    v = []
    prompts = _prompts(4)
    ref = _paged_reference(prompts)
    router = fleet.FleetRouter(_paged_factory, replicas=2)
    monkey = chaos.ChaosMonkey([chaos.Fault(chaos.ROUTER_DISPATCH,
                                            times=(1, 3))])
    with chaos.active(monkey):
        reqs = [router.submit(prompt=p, max_tokens=MAX_TOKENS)
                for p in prompts]
        router.run()
    snap = router.metrics.snapshot()
    _check(v, len(monkey.fired) == 2, "dispatch injection never fired")
    _check(v, snap["dispatch_retries"] >= 2,
           "fleet_dispatch_retries_total did not move")
    for i, r in enumerate(reqs):
        _check(v, r.output_tokens == ref[i],
               f"request {i} lost or diverged after a dispatch fault")
    router.shutdown()
    return v


def scenario_prefill_handoff_kill(engine, inject):
    """Disaggregated fleet under fire: the PREFILL replica is killed
    mid-chunk. Requests still mid-prefill migrate to the role-preserving
    replacement and every request finishes on the DECODE side via the
    block-level KV handoff, token-identical to the single-engine run —
    and the decode replica proves the bytes-not-recompute contract by
    never compiling a prefill-chunk program at all (prefill_compiles ==
    0), while the prefill side never compiles a decode wave.
    --inject corrupt-handoff flips one element of the first handoff
    payload's KV in flight: the digest check must REFUSE it, the
    request resolves 'error', and the token-identity invariant fails."""
    from paddle_tpu.serving import fleet
    v = []
    # two one-chunk prompts (hand off before the kill) + two two-chunk
    # prompts (mid-prefill when the kill lands)
    prompts = [np.random.RandomState(200 + i)
               .randint(0, VOCAB, (n,)).tolist()
               for i, n in enumerate((10, 12, PREFILL_LEN + 2,
                                      PREFILL_LEN + 4))]
    ref = _paged_reference(prompts)
    router = fleet.DisaggFleetRouter(_paged_factory, prefill_replicas=1,
                                     decode_replicas=1)
    faults = [chaos.Fault(chaos.HANDOFF_IMPORT, action="payload",
                          payload=True, times=(1,))] \
        if inject == "corrupt-handoff" else \
        [chaos.Fault(chaos.REPLICA_KILL, action="payload", payload=0,
                     times=(2,))]
    monkey = chaos.ChaosMonkey(faults)
    with chaos.active(monkey):
        reqs = [router.submit(prompt=p, max_tokens=MAX_TOKENS)
                for p in prompts]
        router.run()
    snap = router.metrics.snapshot()
    _check(v, monkey.fired, "injection never fired")
    for i, r in enumerate(reqs):
        _check(v, r.finish_reason == "max_tokens",
               f"request {i} resolved {r.finish_reason!r} — a killed "
               "prefill replica's work must finish via handoff")
        _check(v, r.output_tokens == ref[i],
               f"request {i} output diverged from the single-engine run "
               "across the prefill->decode handoff")
    _check(v, snap["handoffs"] >= len(prompts),
           f"expected >= {len(prompts)} block-level handoffs, got "
           f"{snap['handoffs']}")
    _check(v, snap["handoff_blocks"] > 0 and snap["handoff_bytes"] > 0,
           "fleet_handoff_{blocks,bytes}_total did not move")
    _check(v, snap["replica_restarts"] == 1,
           f"expected 1 role-preserving replacement, got "
           f"{snap['replica_restarts']}")
    roles = router.health()["roles"]
    _check(v, roles.get("prefill") == 1 and roles.get("decode") == 1,
           f"role mix not preserved across the kill: {roles}")
    for rep in router.replicas:
        if rep.role == "decode":
            _check(v, rep.engine.prefill_compiles == 0,
                   f"decode replica {rep.replica_id} compiled a prefill "
                   "program — handoff replayed by recompute")
            _check(v, rep.engine.decode_compiles <= 1,
                   f"decode replica {rep.replica_id} decode wave "
                   "recompiled under handoff load")
        if rep.role == "prefill":
            _check(v, rep.engine.decode_compiles == 0,
                   f"prefill replica {rep.replica_id} compiled a decode "
                   "wave — role specialization leaked")
    router.shutdown()
    return v


def scenario_noisy_tenant(engine, inject):
    """Multi-tenant QoS: a tenant saturating the fleet cannot push a
    premium tenant out of SLO attainment. Six bulk requests flood a
    2-slot replica before two premium requests arrive; weighted-fair
    admission under pool pressure admits the premium cohort as soon as
    slots free instead of behind the whole bulk backlog, premium output
    stays token-identical, and the premium SLO window reads attainment
    1.0. --inject no-qos runs the same load with the QoS manager
    removed: strict FCFS finishes premium dead last and the
    admitted-ahead invariant must fail."""
    from paddle_tpu.serving import PagedServingEngine, SLOPolicy, fleet
    from paddle_tpu.serving.fleet import QoSManager, Tenant
    v = []

    def tiny_factory():
        # 2 slots + a 4-block pool; prompt(4) + 3 new tokens fit ONE
        # block, so admission — not mid-decode growth — is the only
        # pressure point and the run is deterministic
        return PagedServingEngine(get_model(), num_slots=2,
                                  max_len=MAX_LEN, block_size=8,
                                  num_blocks=5,
                                  prefill_chunk_len=PREFILL_LEN)

    bulk_p = [np.random.RandomState(300 + i)
              .randint(0, VOCAB, (4,)).tolist() for i in range(6)]
    prem_p = [np.random.RandomState(400 + i)
              .randint(0, VOCAB, (4,)).tolist() for i in range(2)]
    ref = {tuple(p): Scheduler(tiny_factory()).generate(p, max_tokens=3)
           for p in bulk_p + prem_p}
    qos = None if inject == "no-qos" else QoSManager(
        tenants=[Tenant("premium", weight=8.0, priority=10,
                        slo=SLOPolicy(error_rate=0.01)),
                 Tenant("bulk", weight=1.0, priority=0)],
        # one staged 1-block lane out of 4 usable blocks already counts
        # as pressure at this tiny scale, so the weighted-fair pick is
        # exercised on every admission after the first
        pressure_threshold=0.25)
    router = fleet.DisaggFleetRouter(tiny_factory, prefill_replicas=0,
                                     decode_replicas=0,
                                     unified_replicas=1, qos=qos)
    reqs = [(tenant, router.submit(prompt=p, max_tokens=3, tenant=tenant))
            for tenant, p in ([("bulk", p) for p in bulk_p]
                              + [("premium", p) for p in prem_p])]
    order = []                   # tenant names in completion order
    pending = list(reqs)
    while router.step():
        done = [(t, r) for t, r in pending if r.done]
        pending = [(t, r) for t, r in pending if not r.done]
        order.extend(t for t, _ in done)
    order.extend(t for t, r in pending if r.done)
    for tenant, r in reqs:
        _check(v, r.finish_reason == "max_tokens",
               f"{tenant} request resolved {r.finish_reason!r} — QoS "
               "must starve nobody, premium or bulk")
        _check(v, r.output_tokens == ref[tuple(r.prompt)],
               f"{tenant} output diverged under tenant contention")
    last_prem = max(i for i, t in enumerate(order) if t == "premium") \
        if "premium" in order else len(order)
    bulk_after = sum(1 for t in order[last_prem + 1:] if t == "bulk")
    _check(v, bulk_after >= 2,
           f"premium admitted behind the bulk backlog (only {bulk_after} "
           "bulk completions after the last premium; weighted-fair "
           "admission should have moved premium ahead)")
    if qos is not None:
        prem = qos.summary()["premium"]
        _check(v, prem["requests"] == 2,
               f"premium window saw {prem['requests']} requests, "
               "expected 2")
        _check(v, prem["attainment"] == 1.0 and not prem["breached"],
               f"premium pushed out of SLO attainment: {prem}")
    router.shutdown()
    return v


def _model_meta():
    """Replayable model-construction metadata for black-box `run_start`
    harnesses (scripts/replay_incident.py rebuilds get_model() from
    exactly this)."""
    return {"arch": "llama", "vocab_size": VOCAB, "hidden_size": HIDDEN,
            "num_layers": LAYERS, "num_heads": HEADS,
            "num_kv_heads": KV_HEADS, "max_seq_len": MAX_LEN,
            "init_seed": 7}


def scenario_blackbox_replay(engine, inject):
    """The black-box recorder's end-to-end proof: a 2-replica fleet
    serving mixed greedy + seeded-sampling requests has a replica
    KILLED mid-stream while the black box journals every decision; the
    journal then replays on a freshly built fleet
    (scripts/replay_incident.py) — re-forcing the recorded kill at the
    same round boundary — and every request's regenerated output
    digest must equal the recorded one, sampled requests included
    (identical engine seeds -> identical PRNG chains).  --inject
    no_journal runs the same stream with the recorder detached: the
    journal never exists, replay must refuse, and the checker exits 1."""
    from paddle_tpu.serving import blackbox, fleet
    from scripts import replay_incident
    v = []
    tmp = tempfile.mkdtemp(prefix="chaos_blackbox_")
    journal = os.path.join(tmp, "blackbox.jsonl")
    prompts = _prompts(6)
    router = fleet.FleetRouter(_paged_factory, replicas=2)
    harness = {"model": _model_meta(),
               "engine": router.replicas[0].engine.describe(),
               "fleet": {"kind": "fleet", "replicas": 2}}
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.REPLICA_KILL, action="payload", payload=0, times=(2,))])

    def drive():
        reqs = []
        for i, p in enumerate(prompts):
            kw = {"prompt": p, "max_tokens": MAX_TOKENS}
            if i % 2:
                kw.update(do_sample=True, temperature=0.9, top_k=8)
            reqs.append(router.submit(**kw))
        # fleet-step invocation 2: the victim holds mid-stream work
        with chaos.active(monkey):
            router.run()
        return reqs

    if inject == "no_journal":
        reqs = drive()               # recorder detached: no journal
    else:
        with blackbox.BlackBoxRecorder(path=journal) as bb:
            bb.run_start(harness=harness)
            reqs = drive()
    _check(v, monkey.fired, "replica_kill injection never fired")
    for i, r in enumerate(reqs):
        _check(v, r.finish_reason == "max_tokens",
               f"request {i} resolved {r.finish_reason!r} under the "
               "recorded kill")
    snap = router.metrics.snapshot()
    _check(v, snap["migrations"] >= 1,
           "the kill forced no migration — nothing worth replaying")
    router.shutdown()
    try:
        rep = replay_incident.replay(journal, model=get_model())
    except (replay_incident.UsageError, OSError) as e:
        _check(v, False, f"black-box journal not replayable: {e}")
        return v
    _check(v, rep["verified"] == len(reqs),
           f"replay verified {rep['verified']}/{len(reqs)} requests "
           "(journal lost completions)")
    _check(v, rep["ok"],
           "replayed outputs diverged from the recorded digests: "
           + "; ".join(f"request {r['request_id']} expect "
                       f"{r.get('expect_sha')} got {r['got_sha']}"
                       for r in rep["rows"] if r["ok"] is False))
    _check(v, any(r["sampled"] and r["ok"] for r in rep["rows"]),
           "no seeded-sampling request replayed token-exact")
    _check(v, any(r["ok"] and not r["sampled"] for r in rep["rows"]),
           "no greedy request replayed token-exact")
    return v


def scenario_latency_spike(engine, inject):
    """Anomaly-plane positive control: an injected decode-wave delay
    must fire the TTFT/TPOT anomaly alert (utils/anomaly.py) and then
    CLEAR once the detector's baseline absorbs the new level — slow is
    detected, and a one-time spike is a firing/cleared pair, not a
    latch.  Outputs stay token-exact (slow is not broken), and the
    sampled history serves in-process.  The black box rides along:
    the firing alert must snapshot an incident bundle whose journal
    round-trips through scripts/replay_incident.py token-exact on the
    same warmed engine.  --inject no_alerts evaluates with an EMPTY
    rule set while the invariants still expect the alert — the checker
    must fail."""
    from paddle_tpu.serving import blackbox
    from scripts import replay_incident
    v = []
    spike_rules = ("ttft_p99_anomaly", "tpot_p99_anomaly")
    prompts = _prompts()
    ref = _reference(engine, prompts)
    # fresh latency window: the preceding scenarios (slow_wave above
    # all) already banked big observations in the CUMULATIVE latency
    # histograms, which would bury the spike's p99 shift. Only these
    # two series reset — a registry-wide reset would zero the compile
    # counters the final compile-once invariant audits.
    for name in ("serving_ttft_seconds", "serving_tpot_seconds"):
        m = telemetry.REGISTRY.get(name)
        if m is not None:
            m._reset()
    sampler = timeseries.MetricsSampler(interval_s=0.0)
    rules = [] if inject == "no_alerts" else \
        anomaly.default_serving_rules(
            detector_kw={"warmup": 3, "z_fire": 3.0, "z_clear": 1.5,
                         "alpha": 0.3})
    am = anomaly.AlertManager(rules=rules)
    tmp = tempfile.mkdtemp(prefix="chaos_spike_bb_")
    bb = blackbox.BlackBoxRecorder(
        path=os.path.join(tmp, "blackbox.jsonl"),
        bundle_dir=os.path.join(tmp, "bundles"))
    with bb:
        bb.run_start(harness={"model": _model_meta(),
                              "engine": engine.describe()})
        sched = Scheduler(engine)
        sched.attach_timeseries(sampler, am)
        # fault-free stream first: seeds every detector's EWMA baseline
        for p in prompts:
            sched.submit(prompt=p, max_tokens=MAX_TOKENS)
        sched.run()
        monkey = chaos.ChaosMonkey([chaos.Fault(
            chaos.DECODE_WAVE, action="delay", delay_s=0.25,
            times=(1, 2, 3))])
        with chaos.active(monkey):
            reqs = [sched.submit(prompt=p, max_tokens=MAX_TOKENS)
                    for p in prompts]
            sched.run()
        _check(v, len(monkey.fired) == 3,
               "latency injection never fired")
        for i, r in enumerate(reqs):
            _check(v, r.output_tokens == ref[i],
                   f"request {i} output diverged under injected "
                   "latency")
        fired = {r for r in spike_rules
                 if am.summary()["rules"].get(r, {}).get("fired", 0)
                 >= 1}
        _check(v, fired,
               "no TTFT/TPOT anomaly alert fired under an injected "
               "0.25s decode-wave latency spike")
        # recovery: fault-free rounds until the EWMA absorbs the level
        for _ in range(8):
            if not set(am.active()) & set(spike_rules):
                break
            for p in prompts:
                sched.submit(prompt=p, max_tokens=MAX_TOKENS)
            sched.run()
        _check(v, not set(am.active()) & set(spike_rules),
               "latency alert latched forever — never cleared after "
               "the spike ended")
        _check(v, all(am.summary()["rules"][r]["cleared"] >= 1
                      for r in fired),
               "fired alert has no cleared transition")
    # the firing alert must have snapshotted a self-contained incident
    # bundle that round-trips through the replayer (on the SAME warmed
    # engine: a rebuilt one would violate the compile-once invariant)
    bundle = am.last_bundle
    _check(v, bundle is not None and os.path.isdir(bundle),
           "firing alert snapshotted no incident bundle")
    if bundle is not None and os.path.isdir(bundle):
        for fname in ("journal.jsonl", "history.json",
                      "manifest.json"):
            _check(v, os.path.isfile(os.path.join(bundle, fname)),
                   f"incident bundle missing {fname}")
        with open(os.path.join(bundle, "manifest.json"),
                  encoding="utf-8") as f:
            manifest = json.load(f)
        _check(v, manifest.get("rule") in spike_rules,
               f"bundle manifest names rule {manifest.get('rule')!r}, "
               "not the latency alert")
        rep = replay_incident.replay(bundle, engine=engine)
        _check(v, rep["verified"] >= 1 and rep["ok"],
               "incident bundle did not replay token-exact "
               f"({rep['diverged']}/{rep['verified']} diverged)")
    # the sampled plane serves in-process: history JSON + dashboard
    st, _, body = telemetry.http_get_inline("/metrics/history",
                                            sampler=sampler)
    hist = json.loads(body)
    _check(v, st == 200 and hist["samples"] > 0
           and "serving_tpot_seconds_p99" in hist["series"],
           "/metrics/history did not serve the sampled series")
    st, _, body = telemetry.http_get_inline("/dashboard",
                                            sampler=sampler)
    _check(v, st == 200 and b"serving_tpot_seconds_p99" in body,
           "/dashboard did not render the sampled series")
    return v


SCENARIOS = {
    "nan_slot": scenario_nan_slot,
    "wave_error": scenario_wave_error,
    "slow_wave": scenario_slow_wave,
    "prefill_error": scenario_prefill_error,
    "callback_error": scenario_callback_error,
    "overflow_shed": scenario_overflow_shed,
    "drain": scenario_drain,
    "cache_exhaustion": scenario_cache_exhaustion,
    "spec_rollback": scenario_spec_rollback,
    "replica_failover": scenario_replica_failover,
    "router_dispatch": scenario_router_dispatch,
    "prefill_handoff_kill": scenario_prefill_handoff_kill,
    "noisy_tenant": scenario_noisy_tenant,
    "ckpt_crash": scenario_ckpt_crash,
    "latency_spike": scenario_latency_spike,
    "blackbox_replay": scenario_blackbox_replay,
}

# positive controls: each disables one resilience property inside its
# scenario; the run MUST exit 1 (tests/test_chaos.py asserts it)
INJECTIONS = {"drop-isolation": "nan_slot", "no-retry": "wave_error",
              "alloc-crash": "cache_exhaustion",
              "no-migration": "replica_failover",
              "no-rollback": "spec_rollback",
              "corrupt-handoff": "prefill_handoff_kill",
              "no-qos": "noisy_tenant",
              "no_alerts": "latency_spike",
              "no_journal": "blackbox_replay"}


def run(argv=None):
    ap = argparse.ArgumentParser(
        prog="chaos_serving",
        description="chaos scenarios over the serving resilience layer")
    ap.add_argument("--scenarios", "--scenario", default=None,
                    help=f"comma-separated subset of "
                         f"{','.join(SCENARIOS)}")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 entry point: the full scenario set at "
                         "the canonical tiny scale (identical to the "
                         "default run; the flag names the contract)")
    ap.add_argument("--inject", default=None, choices=sorted(INJECTIONS),
                    help="positive control: violate one invariant and "
                         "prove this runner exits 1")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--journal", default=None,
                    help="write the chaos/fault flight-recorder journal "
                         "to this JSONL path")
    args = ap.parse_args(argv)

    if args.inject is not None:
        names = [INJECTIONS[args.inject]]
    elif args.scenarios:
        names = [s.strip() for s in args.scenarios.split(",") if s.strip()]
        unknown = set(names) - set(SCENARIOS)
        if unknown:
            print(f"chaos_serving: unknown scenario(s) {sorted(unknown)}",
                  file=sys.stderr)
            return 2
    else:
        names = list(SCENARIOS)

    engine = get_engine()
    rec = flight_recorder.FlightRecorder(args.journal)
    results = {}
    with flight_recorder.recording(rec):
        rec.run_start(mode="chaos_serving", scenarios=names,
                      inject=args.inject)
        for name in names:
            # scenario isolation on the shared engine: a failed scenario
            # must not leak active slots or health state into the next
            for s in engine.active_slots():
                engine.retire_slot(s)
            engine.set_health_state("ok")
            try:
                violations = SCENARIOS[name](engine, args.inject)
            except Exception as e:   # noqa: BLE001 — a fault ESCAPED
                violations = [f"fault escaped the resilience layer: "
                              f"{type(e).__name__}: {e}"]
            results[name] = violations
            if not args.as_json:
                mark = "ok" if not violations else "FAIL"
                print(f"== {name}: {mark} ==")
                for msg in violations:
                    print(f"   violated: {msg}")
        # the global invariant every fault path shares: the decode wave
        # is still ONE compiled program (and the live metric agrees)
        compile_ok = (engine.decode_compiles == 1
                      and telemetry.compile_count("serving_decode_wave")
                      == _CACHE["compiles_after_warm"])
        if not compile_ok:
            results["compile_once"] = [
                f"decode wave recompiled under fault load: "
                f"cache={engine.decode_compiles}, metric="
                f"{telemetry.compile_count('serving_decode_wave')}"]
        rec.run_end(status="ok" if not any(results.values()) else
                    "violations")
    rec.close()

    failed = {k: v for k, v in results.items() if v}
    if args.as_json:
        print(json.dumps({
            "version": 1,
            "status": "ok" if not failed else "violations",
            "inject": args.inject,
            "scenarios": results,
            "journal_counts": rec.counts(),
        }, indent=2))
    else:
        print(f"chaos_serving: {len(results) - len(failed)}/"
              f"{len(results)} scenarios clean"
              + (f" (inject={args.inject}: expected to FAIL)"
                 if args.inject else ""), file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(run())
