"""Serving x telemetry acceptance (ISSUE 3): a 12-request, 3-wave run
exports ONE chrome trace with per-request flow events for all four
lifecycle states; the compile-event metric reads exactly 1 for the
batched decode function; and the Prometheus exposition (exercised
in-process against the /metrics handler) shows the serving counters and
a TTFT histogram whose buckets sum to the request count.

Reuses the EXACT engine shape of tests/test_serving.py (2-layer /
hidden-64 llama, 4 slots) so warm runs hit the persistent compile
cache. The registry is reset (values only — registrations survive) at
the start of the big test so counts are exact, not >=.
"""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Scheduler, ServingEngine
from paddle_tpu.utils import profiler as prof
from paddle_tpu.utils import telemetry

VOCAB = 128
LIFECYCLE = {"QUEUED", "PREFILL", "DECODE", "DONE"}


@pytest.fixture(scope="module")
def engine():
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64)
    model = LlamaForCausalLM(cfg)
    return ServingEngine(model, num_slots=4, max_len=64, prefill_len=16)


def test_three_wave_run_trace_compiles_and_prometheus(engine, tmp_path):
    telemetry.REGISTRY.reset()
    prof.start_profiler()
    sched = Scheduler(engine)
    rng = np.random.RandomState(3)
    reqs = [sched.submit(
        prompt=rng.randint(0, VOCAB, (int(rng.randint(2, 12)),)).tolist(),
        max_tokens=int(rng.randint(2, 6))) for _ in range(12)]
    sched.run()
    assert all(r.done for r in reqs)

    # ---- one chrome trace, per-request flows for all four states
    path = str(tmp_path / "serving_trace.json")
    prof.stop_profiler(profile_path=path)
    events = json.load(open(path))["traceEvents"]
    flows = [e for e in events if e.get("cat") == "serving.request"
             and e["ph"] in "stf"]
    states = {}
    for e in flows:
        assert e["id"] == e["args"]["request_id"]     # valid id binding
        states.setdefault(e["args"]["request_id"], set()).add(
            e["args"]["state"])
    assert set(states) == {r.trace_id for r in reqs}
    for rid, seen in states.items():
        assert seen == LIFECYCLE, (rid, seen)
    # every flow step/finish references an id a flow start opened
    started = {e["id"] for e in flows if e["ph"] == "s"}
    assert all(e["id"] in started for e in flows if e["ph"] in "tf")
    # request spans and decode-wave slices share the timeline
    assert any(e["ph"] == "b" and e["name"] == "DECODE" for e in events)
    assert any(e.get("ph") == "X" and e["name"] == "serving/decode_wave"
               for e in events)
    assert any(e.get("ph") == "C" and e["name"] == "serving/slots"
               for e in events)

    # ---- compile-once as a live metric: exactly 1 for the decode wave
    assert telemetry.compile_count("serving_decode_wave") == 1
    assert telemetry.compile_count("serving_prefill") == 1
    assert engine.decode_compiles == 1            # agrees with _cache_size

    # ---- Prometheus exposition through the in-process /metrics handler
    status, headers, body = telemetry.http_get_inline("/metrics")
    assert status == 200
    assert headers["content-type"].startswith("text/plain")
    text = body.decode()
    assert 'serving_requests_total{state="submitted"} 12' in text
    assert 'serving_requests_total{state="completed"} 12' in text
    assert "serving_prefills_total 12" in text
    assert 'xla_compiles_total{function="serving_decode_wave"} 1' in text
    # TTFT histogram: buckets (cumulative, so +Inf) sum to request count
    assert 'serving_ttft_seconds_bucket{le="+Inf"} 12' in text
    assert "serving_ttft_seconds_count 12" in text
    tokens = sum(len(r.output_tokens) for r in reqs)
    assert f"serving_tokens_generated_total {tokens}" in text


def test_snapshot_keys_byte_compatible(engine):
    """ServingMetrics.snapshot() keeps the PR-1 key set (the bench
    script serializes it) now that percentiles come from bounded
    histograms instead of raw sample lists; the resilience PR appended
    its fault/shed/retry tallies after them."""
    sched = Scheduler(engine)
    req = sched.submit(prompt=[1, 2, 3], max_tokens=3)
    sched.run()
    assert req.done
    snap = sched.metrics.snapshot()
    assert list(snap) == [
        "requests_completed", "tokens_generated", "tokens_per_s",
        "ttft_p50_s", "ttft_p99_s", "latency_p50_s", "latency_p99_s",
        "slot_occupancy", "queue_depth_peak",
        "faults", "rejected", "wave_retries",
        "block_utilization", "prefix_hits", "prefix_misses",
        "prefix_hit_rate", "prefix_evictions",
        # fleet PR appended the raw span endpoints (rollups across
        # replicas need min(first)/max(last), not per-engine spans)
        "first_token_time", "last_token_time",
        # observability PR appended TPOT percentiles and the per-round
        # phase split
        "tpot_p50_s", "tpot_p99_s", "phase_seconds",
        # speculative-decoding PR appended the draft economics (0/None
        # on engines without a draft model)
        "spec_tokens_proposed", "spec_tokens_accepted",
        "spec_acceptance_rate", "spec_accepted_per_wave",
        # the paged core's page counters (0 / 0 on a dense engine)
        "paged_pages_visited", "paged_pages_spanned",
        # the grid steps its kernel ran, and those that scored pages
        "paged_steps_run", "paged_steps_visited",
        # what a model with slot state, experts or a latent cache was
        # staged (0 for any other)
        "state_resets", "moe_picks", "mla_rows_attended",
        "mla_rows_expanded", "prefill_tokens", "prefill_chunks",
        "ssm_records_stepped", "ssm_lanes_stepped",
        # token rows put through the maps of a multi-stream residual
        # path (0 with one stream)
        "mhc_rows_mixed",
        # keys the chunks' tokens attend in a full layer (position + 1)
        "prefill_keys",
        # a window pool's pages given back behind the window, and its
        # pages and lanes holding any, summed a round (0 without one)
        "window_pages_released", "window_pages_held", "window_lanes_held",
        # bias rows and matrices sent to the device (0 while no request
        # brings a bias)
        "bias_uploads",
        # the collector's pauses and collections (process totals)
        "gc_pause_seconds", "gc_collections", "gc_gen2_collections",
        # waves put on the device's queue, and those that went out
        # before the wave before them was read
        "waves_dispatched", "waves_dispatched_ahead"]
    assert snap["bias_uploads"] == 0
    assert snap["waves_dispatched"] == 2          # 3 tokens: 1 + 2 waves
    assert snap["waves_dispatched_ahead"] == 1
    # a 3-token request has 2 inter-token gaps — TPOT is real, and the
    # phase split saw every phase of a working round
    assert snap["tpot_p50_s"] is not None
    assert snap["phase_seconds"]["decode_wave"] > 0
    assert set(snap["phase_seconds"]) >= {"admission", "prefill_chunk",
                                          "decode_wave",
                                          "host_dispatch"}
    # dense engine: the paged-pool keys are present but empty
    assert snap["block_utilization"] is None
    assert snap["prefix_hits"] == 0 and snap["prefix_hit_rate"] is None
    assert snap["prefix_evictions"] == 0
    assert snap["paged_pages_visited"] == snap["paged_pages_spanned"] == 0
    assert snap["paged_steps_run"] == snap["paged_steps_visited"] == 0
    assert snap["requests_completed"] == 1
    assert snap["ttft_p50_s"] is not None
    assert snap["ttft_p50_s"] <= snap["latency_p50_s"]
    assert snap["faults"] == {} and snap["rejected"] == 0
    assert json.dumps(snap)                       # still serializable


@pytest.mark.parametrize("family", ["granite-hybrid", "nemotron-h",
                                    "llama"])
def test_slot_state_waves_count_their_records_and_lanes(family):
    """A wave of a model with slot state reads and writes every slot's
    record (`ssm_records_stepped`: the slots, each wave) whichever
    lanes decode in it (`ssm_lanes_stepped`); chunks and their prompt
    tokens are counted for it too. A model without slot state counts
    no records."""
    from paddle_tpu.nlp import (GraniteHybridConfig,
                                GraniteHybridForCausalLM, NemotronHConfig,
                                NemotronHForCausalLM)
    from paddle_tpu.serving import PagedServingEngine
    pt.seed(3)
    if family == "granite-hybrid":
        model = GraniteHybridForCausalLM(GraniteHybridConfig(
            vocab_size=VOCAB, hidden_size=64,
            layer_types=("mamba", "attention"), num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=96,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8))
    elif family == "nemotron-h":
        model = NemotronHForCausalLM(NemotronHConfig(
            vocab_size=VOCAB, hidden_size=64, hybrid_override_pattern="M*",
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
            ssm_state_size=16, chunk_size=8))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=64))
    eng = PagedServingEngine(model.eval(), num_slots=3, max_len=64,
                             block_size=8, prefill_chunk_len=16)
    sched = Scheduler(eng)
    # 20 prompt tokens in two chunks, then 4 waves of one lane of three
    out = sched.generate(list(range(1, 21)), max_tokens=5)
    snap = sched.metrics.snapshot()
    assert len(out) == 5
    if family == "llama":
        assert snap["ssm_records_stepped"] == snap["ssm_lanes_stepped"] == 0
        return
    assert snap["ssm_records_stepped"] == 4 * 3
    assert snap["ssm_lanes_stepped"] == 4
    assert snap["prefill_chunks"] == 2 and snap["prefill_tokens"] == 20
    assert snap["state_resets"] == 1
    # two requests at once: both lanes decode in the waves they share
    reqs = [sched.submit(prompt=list(range(1, 6)), max_tokens=4)
            for _ in range(2)]
    sched.run()
    assert all(len(r.output_tokens) == 4 for r in reqs)
    after = sched.metrics.snapshot()
    waves = (after["ssm_records_stepped"] - snap["ssm_records_stepped"]) // 3
    assert 3 <= waves <= 4
    assert after["ssm_lanes_stepped"] - snap["ssm_lanes_stepped"] == 6


def test_engine_metrics_server_and_healthz(engine):
    """ServingEngine exposes the exporter directly; /healthz reports
    slot/compile state."""
    srv = engine.start_metrics_server(port=0)
    try:
        assert engine.start_metrics_server() is srv       # idempotent
        assert engine.start_metrics_server(port=srv.port) is srv
        with pytest.raises(RuntimeError, match="already running"):
            engine.start_metrics_server(port=srv.port + 1)   # no silent
        with pytest.raises(RuntimeError, match="already running"):      #
            engine.start_metrics_server(host="0.0.0.0")      # rebinding
        status, _, body = telemetry.http_get_inline(
            "/healthz", health_fn=engine._health)
        payload = json.loads(body)
        assert status == 200 and payload["status"] == "ok"
        assert payload["num_slots"] == 4
        assert payload["decode_compiles"] == 1
        # load state rides the SAME endpoint (fleet router / LB
        # contract): queue depth from the last attached scheduler
        assert payload["queue_depth"] == 0
        sched = Scheduler(engine)
        for i in range(6):              # 4 slots + 2 queued
            sched.submit(prompt=[1 + i, 2, 3], max_tokens=2)
        _, _, body = telemetry.http_get_inline(
            "/healthz", health_fn=engine._health)
        assert json.loads(body)["queue_depth"] == sched.queue_depth() >= 1
        sched.run()
        _, _, body = telemetry.http_get_inline(
            "/healthz", health_fn=engine._health)
        assert json.loads(body)["queue_depth"] == 0
        import urllib.request
        data = urllib.request.urlopen(srv.url + "/healthz",
                                      timeout=10).read()
        assert json.loads(data)["num_slots"] == 4
    finally:
        engine.stop_metrics_server()
    assert engine._metrics_server is None


def test_config_front_door_starts_exporter(engine):
    """inference.Config.enable_metrics_exporter reaches the engine via
    create_llm_predictor; close() tears the server down."""
    from paddle_tpu import inference
    cfg = inference.Config()
    cfg.enable_llm_engine(num_slots=2, max_len=32, prefill_len=8)
    cfg.enable_metrics_exporter(port=0)
    assert cfg.metrics_exporter_enabled()
    pred = inference.create_llm_predictor(cfg, model=engine.model)
    try:
        assert pred.metrics_server is not None
        assert pred.metrics_server.port > 0
    finally:
        pred.close()
    assert pred.metrics_server is None
