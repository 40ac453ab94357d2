"""Fleet observability plane (ISSUE 12 acceptance).

The contract under test:

  * **Cross-replica tracing** — a `replica_failover` chaos run exports
    ONE merged chrome trace in which the migrated request's spans are
    flow-linked across both replicas (same fleet trace id, a MIGRATE
    flow step joining the halves, each replica on its own named
    process row, per-chunk prefill instants).
  * **SLO engine** — deterministic burn-rate math over a sliding
    window; under injected latency (chaos delay action) the burn rate
    crosses threshold and the FLEET SCALES UP without dropping
    accepted work, while a no-SLO control keeps the old queue-depth
    behavior.
  * **Fleet /metrics** — one scrape of the router's exporter carries
    every replica's gauges with a `replica` label and counters that
    stay coherent across a kill/replace cycle.

Canonical tiny LLaMA scale (2 layers, hidden 64 — the shape every
serving suite compiles) so warm runs hit the persistent cache.
"""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (PagedServingEngine, Scheduler, SLOEngine,
                                SLOPolicy, fleet)
from paddle_tpu.utils import chaos, flight_recorder, telemetry
from paddle_tpu.utils import profiler as prof

VOCAB = 128
MAX_LEN = 64
BLOCK = 8
CHUNK = 16
MAX_NEW = 6


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def factory(model):
    def make():
        return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                  block_size=BLOCK, num_blocks=33,
                                  prefill_chunk_len=CHUNK)
    return make


@pytest.fixture(scope="module")
def paged(factory):
    return factory()


def _prompts(n, seed=100):
    return [np.random.RandomState(seed + i)
            .randint(0, VOCAB, (4 + i % 3,)).tolist() for i in range(n)]


def test_tpot_histogram_and_per_request_tpot(paged):
    before = telemetry.value("serving_tpot_seconds", default=0)
    sched = Scheduler(paged)
    req = sched.submit(prompt=[5, 6, 7], max_tokens=5)
    sched.run()
    assert req.done and len(req.output_tokens) == 5
    assert req.tpot is not None and req.tpot > 0
    # 5 tokens = 4 inter-token gaps; TTFT is deliberately NOT a sample
    after = telemetry.value("serving_tpot_seconds", default=0)
    assert after - before == 4
    snap = sched.metrics.snapshot()
    assert snap["tpot_p50_s"] is not None
    assert snap["tpot_p50_s"] <= snap["tpot_p99_s"]


# ---------------------------------------------------------------------------
# cross-replica tracing
# ---------------------------------------------------------------------------

def test_failover_exports_one_flow_linked_trace(factory, tmp_path):
    """THE tracing proof (acceptance criterion): a replica_failover
    chaos run yields one merged chrome trace where the migrated
    request's spans sit on BOTH replicas' process rows, joined by a
    MIGRATE flow step under one trace id."""
    prof.start_profiler()
    try:
        router = fleet.FleetRouter(factory, replicas=2)
        reqs = [router.submit(prompt=p, max_tokens=MAX_NEW)
                for p in _prompts(6, seed=60)]
        monkey = chaos.ChaosMonkey([chaos.Fault(
            chaos.REPLICA_KILL, action="payload", payload=0, times=(2,))])
        with chaos.active(monkey):
            router.run()
        assert monkey.fired
    finally:
        prof.stop_profiler()
    path = str(tmp_path / "fleet_trace.json")
    router.export_trace(path)
    events = json.load(open(path))["traceEvents"]
    migrated = [r for r in reqs if r.migrations]
    assert migrated, "the kill stranded no mid-stream work"
    # every spawned replica's process row is named in the ONE trace
    names = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert names[0] == "fleet-router"
    assert {f"replica-{i}" for i in range(router.supervisor.spawned)} \
        <= set(names.values())
    for fr in migrated:
        evs = sorted((e for e in events
                      if e.get("cat") == "serving.request"
                      and e.get("id") == fr.trace_id),
                     key=lambda e: e["ts"])
        assert evs, f"no trace events for fleet request {fr.request_id}"
        # spans landed on at least two distinct REPLICA rows (pid > 0)
        span_pids = {e["pid"] for e in evs if e["ph"] in "be"}
        assert len(span_pids) >= 2, span_pids
        flows = [e for e in evs if e["ph"] in "stf"]
        states = [e["args"]["state"] for e in flows]
        # one flow start + one finish per hop (the dead hop resolves
        # "error", the resumed hop delivers), linked by the router's
        # MIGRATE step, DISPATCH naming each placement
        assert states.count("QUEUED") == fr.migrations + 1
        assert states.count("DISPATCH") >= fr.migrations + 1
        assert "MIGRATE" in states
        assert flows[0]["ph"] == "s"
        assert [e["ph"] for e in flows].count("f") == fr.migrations + 1
        assert flows[-1]["ph"] == "f"
        assert flows[-1]["args"]["state"] == "DONE"
        assert flows[-1]["args"]["finish_reason"] == "max_tokens"
        # chunked prefill progress is correlated to the same trace id
        assert any(str(e.get("name", "")).startswith("PREFILL_CHUNK")
                   for e in evs if e["ph"] == "i")
    router.shutdown()


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def test_slo_engine_burn_math_is_deterministic():
    pol = SLOPolicy(ttft_p99_s=0.1, error_rate=0.5, objective=0.9,
                    window_s=10.0, fast_burn=2.0)
    eng = SLOEngine(pol)
    for i in range(10):
        eng.observe(ttft=(0.2 if i < 4 else 0.05), error=False, t=float(i))
    v = eng.evaluate(now=9.5, publish=False)
    # 4/10 over target against a 10% budget -> burn 4.0, worst ttft
    assert v["burn_rate"] == pytest.approx(4.0)
    assert v["attainment"] == pytest.approx(0.6)
    assert v["worst"] == "ttft_p99" and v["breached"]
    assert v["targets"]["error_rate"]["burn_rate"] == 0.0
    # the window slides: everything expires -> clean slate, not sticky
    v2 = eng.evaluate(now=25.0, publish=False)
    assert v2["burn_rate"] == 0.0 and v2["attainment"] == 1.0
    assert not v2["breached"]
    # the peak survives the window sliding clean (it is the lifetime
    # worst, what the bench rows report), and reset() clears it
    assert eng.summary()["burn_rate_peak"] == pytest.approx(4.0)
    eng.reset()
    assert eng.summary()["burn_rate_peak"] == 0.0
    with pytest.raises(ValueError):
        SLOPolicy()                                # no target at all
    with pytest.raises(ValueError):
        SLOPolicy(ttft_p99_s=1.0, fast_burn=0.5, slow_burn=0.5)


def test_slo_transitions_journal_and_gauges():
    pol = SLOPolicy(ttft_p99_s=0.1, objective=0.5, window_s=30.0,
                    fast_burn=1.5)
    eng = SLOEngine(pol)
    rec = flight_recorder.FlightRecorder(None)
    with flight_recorder.recording(rec):
        for i in range(4):
            eng.observe(ttft=0.5, t=float(i))
        eng.evaluate(now=4.0)              # breach -> burn_alert
        eng.evaluate(now=4.5)              # still breached: NO new line
        eng.evaluate(now=40.0)             # window empty -> burn_clear
    slo_events = [e for e in rec.events() if e["ev"] == "slo"]
    assert [e["action"] for e in slo_events] == ["burn_alert",
                                                 "burn_clear"]
    assert slo_events[0]["burn_rate"] == pytest.approx(2.0)
    assert slo_events[0]["slo"] == "ttft_p99"
    assert telemetry.value("slo_burn_rate", {"slo": "overall"}) == 0.0
    assert telemetry.value("slo_attainment", {"slo": "ttft_p99"}) == 1.0
    assert eng.summary()["burn_rate_peak"] == pytest.approx(2.0)


def test_slo_burn_scales_fleet_up_without_dropping_work(factory):
    """The acceptance scenario: injected wave latency (chaos delay)
    pushes TPOT past target, burn crosses fast_burn, the fleet scales
    up, and every accepted request still completes."""
    pol = SLOPolicy(tpot_p99_s=0.05, objective=0.5, window_s=60.0,
                    fast_burn=1.5, cooldown_rounds=2)
    router = fleet.FleetRouter(factory, replicas=1, max_replicas=2,
                               slo=pol)
    rec = flight_recorder.FlightRecorder(None)
    with flight_recorder.recording(rec):
        reqs = [router.submit(prompt=p, max_tokens=4)
                for p in _prompts(8, seed=80)]
        monkey = chaos.ChaosMonkey([chaos.Fault(
            chaos.DECODE_WAVE, action="delay", delay_s=0.12, every=1)])
        with chaos.active(monkey):
            router.run()
        assert monkey.fired
    snap = router.metrics.snapshot()
    assert snap["scale_ups"] >= 1, "burn never drove a scale-up"
    assert len(router.replicas) == 2
    # nothing dropped: every accepted request completed cleanly
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    # burn state is journaled and served on the health endpoint
    actions = [e["action"] for e in rec.events() if e["ev"] == "slo"]
    assert "burn_alert" in actions and "scale_up" in actions
    h = router.health()
    assert h["slo"]["burn_rate"] >= pol.fast_burn
    assert h["slo"]["breached"]
    router.shutdown()


@pytest.mark.slow
def test_no_slo_control_keeps_queue_depth_behavior(factory):
    """The control: same injected latency, no SLO policy — the
    autoscaler stays on the queue-depth heuristic (which sees no
    pressure here) and the rotation never moves."""
    router = fleet.FleetRouter(factory, replicas=1, max_replicas=2,
                               scale_up_queue_depth=50)
    reqs = [router.submit(prompt=p, max_tokens=4)
            for p in _prompts(8, seed=90)]
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.DECODE_WAVE, action="delay", delay_s=0.12, every=1)])
    with chaos.active(monkey):
        router.run()
    assert router.metrics.snapshot()["rebalances"] == 0
    assert len(router.replicas) == 1
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    router.shutdown()


def test_scheduler_level_slo_rides_healthz(paged):
    sched = Scheduler(paged, slo=SLOPolicy(ttft_p99_s=30.0))
    sched.submit(prompt=[9, 8, 7], max_tokens=2)
    sched.run()
    payload = paged._health()
    assert payload["slo"]["window_requests"] == 1
    assert payload["slo"]["burn_rate"] == 0.0
    assert not payload["slo"]["breached"]
    # and over the actual exporter handler, like an LB would read it
    status, _, body = telemetry.http_get_inline("/healthz",
                                                health_fn=paged._health)
    assert status == 200
    assert json.loads(body)["slo"]["targets"]["ttft_p99_s"] == 30.0


# ---------------------------------------------------------------------------
# fleet-wide /metrics aggregation
# ---------------------------------------------------------------------------

def test_one_scrape_covers_every_replica_after_kill_replace(factory):
    router = fleet.FleetRouter(factory, replicas=2)
    reqs = [router.submit(prompt=p, max_tokens=4)
            for p in _prompts(4, seed=70)]
    router.run()
    victim = router.replicas[0]
    router.kill_replica(victim)          # idle kill: replacement joins
    more = [router.submit(prompt=p, max_tokens=4)
            for p in _prompts(2, seed=75)]
    router.run()
    assert victim not in router.replicas
    freg = fleet.FleetRegistry(router)
    status, headers, body = telemetry.http_get_inline("/metrics",
                                                      registry=freg)
    assert status == 200
    text = body.decode()
    # every LIVE replica's gauges, labeled — including the replacement
    live = [r.replica_id for r in router.replicas]
    assert len(live) == 2
    for rid in live:
        assert f'fleet_replica_queue_depth{{replica="{rid}"}} 0' in text
        assert f'fleet_replica_cache_blocks_total{{replica="{rid}"}} 32' \
            in text
        assert (f'fleet_replica_state{{replica="{rid}",state="ok"}} 1'
                in text)
    # the dead replica's series is GONE, not frozen
    assert f'fleet_replica_queue_depth{{replica="{victim.replica_id}"}}' \
        not in text
    # counters stay coherent across the kill/replace cycle: work done
    # on the dead replica is still in the fleet totals
    tokens = sum(len(r.output_tokens) for r in reqs + more)
    completed = len(reqs) + len(more)
    assert f"fleet_tokens_generated_total {tokens}" in text
    assert f"fleet_requests_completed_total {completed}" in text
    # the process-wide registry still rides along in the same scrape
    assert "serving_decode_waves_total" in text
    # and the JSON snapshot carries the same fleet view
    _, _, body = telemetry.http_get_inline("/metrics.json", registry=freg)
    snap = json.loads(body)
    assert "fleet_replica_queue_depth" in snap["metrics"]
    assert snap["metrics"]["fleet_tokens_generated_total"][
        "series"][0]["value"] == tokens
    # the real socket server wires the same registry + fleet health
    srv = router.start_metrics_server(port=0)
    try:
        import urllib.request
        data = urllib.request.urlopen(srv.url + "/healthz",
                                      timeout=10).read()
        payload = json.loads(data)
        assert payload["routable"] == 2 and payload["status"] == "ok"
    finally:
        router.shutdown()                # also stops the fleet exporter
    assert router._metrics_server is None
