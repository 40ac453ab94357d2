"""utils.telemetry: typed metric registry (Counter/Gauge/Histogram with
labels), Prometheus/JSON exporters, monitor-stat bridge, in-process
/metrics handler, XLA compile tracking, and the hapi TelemetryCallback.

Tests use PRIVATE Registry instances wherever possible so they don't
disturb the process-wide default registry other suites accumulate into.
"""
import json
import time

import pytest

from paddle_tpu.utils import monitor, telemetry
from paddle_tpu.utils.telemetry import (Counter, Gauge, Histogram,
                                        Registry, exponential_buckets)


# ---------------------------------------------------------------- registry
def test_counter_gauge_basics():
    reg = Registry()
    c = reg.counter("reqs_total", "requests", labelnames=("state",))
    c.labels(state="ok").inc()
    c.labels("ok").inc(2)          # positional == keyword
    c.labels(state="err").inc()
    assert c.labels(state="ok").value() == 3
    assert c.labels(state="err").value() == 1
    with pytest.raises(ValueError, match="only go up"):
        c.labels(state="ok").inc(-1)

    g = reg.gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4
    g.set_max(10)
    g.set_max(7)                   # running max keeps 10
    assert g.value() == 10


def test_get_or_create_and_conflicts():
    reg = Registry()
    a = reg.counter("dup_total", labelnames=("k",))
    b = reg.counter("dup_total", labelnames=("k",))
    assert a is b                  # modules re-declare at import safely
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("dup_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("dup_total", labelnames=("other",))


def test_histogram_bucket_mismatch_raises():
    """Silently handing caller B metric A's buckets would collapse B's
    observations into +Inf; mismatched buckets must raise like any other
    re-registration conflict."""
    reg = Registry()
    a = reg.histogram("op_seconds")
    assert reg.histogram("op_seconds") is a          # same buckets: fine
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("op_seconds", buckets=exponential_buckets(1, 2, 10))


def test_name_and_label_validation():
    reg = Registry()
    for bad in ("CamelCase", "9starts_with_digit", "has-dash", "", None):
        with pytest.raises(ValueError, match="snake_case"):
            reg.counter(bad)
    c = reg.counter("ok_total", labelnames=("a", "b"))
    with pytest.raises(ValueError, match="unexpected"):
        c.labels(a="1", z="2")
    with pytest.raises(ValueError, match="takes labels"):
        c.labels("only-one")
    with pytest.raises(ValueError, match="has labels"):
        c.inc()                    # labeled metric needs .labels()


def test_histogram_buckets_and_percentiles():
    h = Histogram("lat_seconds", buckets=exponential_buckets(0.001, 2, 10))
    for v in (0.0005, 0.0015, 0.003, 0.003, 0.02, 5.0):
        h.observe(v)
    assert h.count() == 6
    assert h.sum() == pytest.approx(5.028)
    buckets = h.bucket_counts()
    assert buckets[-1] == (None, 6)          # +Inf cumulative == count
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)              # cumulative is monotone
    # percentiles are bucket-interpolated, clamped to observed [min,max]
    assert 0.0005 <= h.percentile(0) <= 0.0015
    assert 0.001 <= h.percentile(50) <= 0.004
    assert h.percentile(100) == pytest.approx(5.0)
    assert Histogram("empty_seconds").percentile(50) is None
    with pytest.raises(ValueError, match="distinct and increasing"):
        Histogram("bad_seconds", buckets=(2.0, 1.0))


def test_bounded_memory_under_many_observations():
    """The whole point of the rebase off raw sample lists: observation
    count must not grow per-sample state."""
    h = Histogram("flood_seconds", buckets=exponential_buckets(0.001, 2, 4))
    child = h.labels()
    for i in range(10_000):
        h.observe((i % 100) / 1000.0)
    assert h.count() == 10_000
    assert len(child._counts) == 5           # 4 bounds + overflow, still


def test_prometheus_render_format():
    reg = Registry()
    c = reg.counter("hits_total", "hits by kind", labelnames=("kind",))
    c.labels(kind='we"ird\nname').inc(3)
    reg.histogram("t_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.render_prometheus(include_monitor=False)
    assert "# HELP hits_total hits by kind" in text
    assert "# TYPE hits_total counter" in text
    assert 'hits_total{kind="we\\"ird\\nname"} 3' in text
    assert 't_seconds_bucket{le="0.1"} 0' in text
    assert 't_seconds_bucket{le="1"} 1' in text
    assert 't_seconds_bucket{le="+Inf"} 1' in text
    assert "t_seconds_sum 0.5" in text
    assert "t_seconds_count 1" in text


def test_snapshot_is_json_and_monitor_bridge():
    reg = Registry()
    reg.counter("x_total").inc(2)
    monitor.stat_add("bridge_stat_demo", 9)
    try:
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["metrics"]["x_total"]["series"][0]["value"] == 2
        assert snap["monitor"]["bridge_stat_demo"] == 9
        text = reg.render_prometheus()
        assert "# TYPE bridge_stat_demo untyped" in text
        assert "bridge_stat_demo 9" in text
        # typed metrics shadow same-name monitor stats (no dup families)
        monitor.stat_set("x_total", 777)
        assert reg.render_prometheus().count("# TYPE x_total") == 1
    finally:
        monitor.stat_reset("bridge_stat_demo")
        monitor.stat_reset("x_total")


def test_reset_keeps_registrations_and_child_handles():
    reg = Registry()
    c = reg.counter("r_total")
    child = c.labels()
    child.inc(5)
    h = reg.histogram("r_seconds")
    h.observe(1.0)
    reg.reset()
    assert c.value() == 0 and h.count() == 0
    child.inc()                    # cached handle still live after reset
    assert c.value() == 1


def test_non_finite_values_render_instead_of_crashing():
    """A diverged train_loss (NaN/Inf gauge) must not take down /metrics
    or make /metrics.json unparseable."""
    reg = Registry()
    reg.gauge("diverged_loss").set(float("nan"))
    reg.gauge("exploded_loss").set(float("inf"))
    text = reg.render_prometheus(include_monitor=False)
    assert "diverged_loss NaN" in text
    assert "exploded_loss +Inf" in text
    snap = json.loads(json.dumps(reg.snapshot(), allow_nan=False))
    vals = {n: m["series"][0]["value"] for n, m in snap["metrics"].items()}
    assert vals == {"diverged_loss": "NaN", "exploded_loss": "+Inf"}
    # histograms drop non-finite samples rather than poison sum/min/max
    h = reg.histogram("h_seconds")
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe(0.5)
    assert h.count() == 1 and h.sum() == 0.5
    json.dumps(reg.snapshot(), allow_nan=False)


def test_value_read_does_not_create_series():
    reg_metric = telemetry.counter("peek_demo_total", labelnames=("k",))
    reg_metric.labels(k="real").inc()
    assert telemetry.value("peek_demo_total", {"k": "real"}) == 1
    # a typo'd / premature read returns default and mints NO series
    assert telemetry.value("peek_demo_total", {"k": "typo"}, 0) == 0
    assert reg_metric.peek(k="typo") is None
    text = telemetry.render_prometheus(include_monitor=False)
    assert 'peek_demo_total{k="typo"}' not in text
    assert telemetry.value("missing_metric_total", default=7) == 7
    telemetry.REGISTRY.unregister("peek_demo_total")


# ------------------------------------------------------- /metrics handler
def test_http_handler_inline_metrics_and_healthz():
    reg = Registry()
    reg.counter("served_total").inc(4)
    status, headers, body = telemetry.http_get_inline("/metrics",
                                                      registry=reg)
    assert status == 200
    assert headers["content-type"].startswith("text/plain")
    assert int(headers["content-length"]) == len(body)
    assert b"served_total 4" in body

    status, _, body = telemetry.http_get_inline(
        "/healthz", registry=reg, health_fn=lambda: {"slots": 2})
    payload = json.loads(body)
    assert status == 200 and payload["status"] == "ok"
    assert payload["slots"] == 2

    status, _, body = telemetry.http_get_inline("/metrics.json",
                                                registry=reg)
    assert status == 200
    assert json.loads(body)["metrics"]["served_total"]["kind"] == "counter"

    assert telemetry.http_get_inline("/nope", registry=reg)[0] == 404


def test_healthz_degrades_on_broken_health_fn():
    def boom():
        raise RuntimeError("engine wedged")

    status, _, body = telemetry.http_get_inline(
        "/healthz", registry=Registry(), health_fn=boom)
    payload = json.loads(body)
    assert status == 503           # status-code probes must fail too
    assert payload["status"] == "degraded"
    assert "engine wedged" in payload["error"]


def test_healthz_non_ok_state_is_503():
    """A health_fn reporting degraded/draining fails the probe at the
    HTTP layer — load balancers that only check the status code stop
    routing without parsing the body."""
    for state in ("degraded", "draining"):
        status, _, body = telemetry.http_get_inline(
            "/healthz", registry=Registry(),
            health_fn=lambda s=state: {"status": s})
        assert status == 503
        assert json.loads(body)["status"] == state


def test_metrics_server_real_socket():
    """Background ThreadingHTTPServer on a free port, exercised over a
    real loopback socket."""
    import urllib.request
    reg = Registry()
    reg.gauge("live_gauge").set(1)
    srv = telemetry.MetricsServer(registry=reg, port=0)
    try:
        srv.start()
        assert srv.port > 0
        body = urllib.request.urlopen(srv.url + "/metrics",
                                      timeout=10).read()
        assert b"live_gauge 1" in body
    finally:
        srv.stop()


# ----------------------------------------------------- compile tracking
def test_track_compiles_attributes_jit_compilation():
    import jax
    import jax.numpy as jnp

    before = telemetry.compile_count("telemetry_test_fn")
    fn = telemetry.instrument_jit(jax.jit(lambda x: x * 3 + 1),
                                  "telemetry_test_fn")
    out = fn(jnp.arange(4.0))
    fn(jnp.arange(4.0))            # cached call: no new compile
    assert float(out[1]) == 4.0
    assert fn._cache_size() == 1   # proxy passes jit internals through
    assert telemetry.compile_count("telemetry_test_fn") == before + 1
    # new dtype -> second executable -> counter follows _cache_size
    fn(jnp.arange(4, dtype=jnp.int32))
    assert telemetry.compile_count("telemetry_test_fn") == before + 2
    assert fn._cache_size() == 2


def test_track_compiles_context_manager_scopes_attribution():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(3.0)            # built OUTSIDE the scope: its own tiny
    before = telemetry.compile_count("telemetry_scoped")   # compile stays
    with telemetry.track_compiles("telemetry_scoped"):     # unattributed
        jax.jit(lambda x: x - 7)(x)
    assert telemetry.compile_count("telemetry_scoped") == before + 1
    with pytest.raises(ValueError, match="snake_case"):
        with telemetry.track_compiles("Bad-Label"):
            pass


# ----------------------------------------------------- request tracing
def test_trace_request_no_dangling_events_across_profiler_restart():
    """A request straddling stop_profiler()/start_profiler() must not
    emit span-ends or flow-finishes whose partners died with the old
    trace buffer (trace-generation guard)."""
    from paddle_tpu.utils import profiler as prof

    class R:
        request_id = trace_id = 77

    r = R()
    prof.start_profiler()
    telemetry.trace_request(r, "QUEUED")
    telemetry.trace_request(r, "PREFILL")
    prof.stop_profiler()             # first trace (with 's' flow) discarded
    prof.start_profiler()            # fresh buffer, new generation
    telemetry.trace_request(r, "DECODE")
    telemetry.trace_request(r, "DONE", reason="eos")
    events = [e for e in prof._raw_events if e.get("id") == 77]
    prof.stop_profiler()
    phases = [e["ph"] for e in events]
    assert phases == ["b", "e"]      # DECODE span opens AND closes here
    assert all(e["name"] == "DECODE" for e in events)
    # no flow 't'/'f' referencing the 's' that lives in the dead trace
    assert not [e for e in events if e["ph"] in "stf"]


# -------------------------------------------------- training callback
def test_telemetry_callback_records_step_loss_and_memory():
    from paddle_tpu.hapi.callbacks import TelemetryCallback

    cb = TelemetryCallback(memory_freq=1)
    steps0 = telemetry.value("train_steps_total", default=0)
    n0 = telemetry.value("train_step_seconds", default=0)
    for step, loss in enumerate([0.5, [0.25], 0.125]):
        cb.on_train_batch_begin(step)
        cb.on_train_batch_end(step, {"loss": loss})
    assert telemetry.value("train_steps_total") == steps0 + 3
    assert telemetry.value("train_step_seconds") == n0 + 3
    assert telemetry.value("train_loss") == pytest.approx(0.125)
    cb.on_train_end()              # device-memory poll must not raise
    # CPU jax has no PJRT memory stats: the gauge is SKIPPED (None), not
    # published as a misleading zero; on accelerators it's >= 0
    mem = telemetry.value("device_bytes_in_use")
    assert mem is None or mem >= 0


# ----------------------------------------------------- the process journal
_JOURNAL_PROBE = """
import json, jax, jax.numpy as jnp
from paddle_tpu.utils import compile_cache, telemetry
compile_cache.enable()                    # installs the listener
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
x = jnp.arange(8.0)
fn = telemetry.instrument_jit(jax.jit(lambda x: (x * 3 + 1).sum()),
                              "journal_probe")
out = {}
for phase in ("cold", "warm"):
    telemetry.clear_process_journal()
    fn(x)
    out[phase] = telemetry.process_summary()["kinds"]
    jax.clear_caches()                    # the directory is kept
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def journal_probe(tmp_path_factory):
    """One process, an empty cache directory: the labelled function's
    first call, then (executables dropped, directory kept) its second."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))
    out = subprocess.run([sys.executable, "-c", _JOURNAL_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("phase,journaled,absent", [
    ("cold", ("trace", "lower", "compile", "cache_miss"),
     ("cache_load", "cache_hit")),
    ("warm", ("trace", "lower", "cache_load", "cache_hit"),
     ("compile", "cache_miss")),
])
def test_compile_stages_are_journaled_by_program_and_a_load_is_no_compile(
        journal_probe, phase, journaled, absent):
    kinds = journal_probe[phase]
    for kind in journaled:
        assert kinds[kind]["labels"]["journal_probe"]["count"] == 1, kind
    for kind in absent:
        assert "journal_probe" not in kinds.get(kind, {"labels": {}})[
            "labels"], kind
    timed = [k for k in journaled if not k.startswith("cache_")
             or k == "cache_load"]
    assert all(kinds[k]["labels"]["journal_probe"]["seconds"] > 0
               for k in timed)


@pytest.fixture
def journal():
    """An empty journal, and no collection while the test runs: a hook an
    earlier test's scheduler installed would journal a long pause."""
    import gc
    gc.disable()
    telemetry.clear_process_journal()
    yield telemetry
    telemetry.clear_process_journal()
    gc.enable()


def test_process_events_cut_at_since_and_until(journal):
    for t in (10.0, 11.0, 12.0):
        journal.record_process_event("gc", 0, 0.002, t_end=t)
    assert [e[0] for e in journal.process_events()] == [10.0, 11.0, 12.0]
    assert [e[0] for e in journal.process_events(until=11.0)] == [10.0, 11.0]
    assert [e[0] for e in journal.process_events(since=11.5)] == [12.0]
    assert journal.process_events(since=10.5, until=11.5) == [
        (11.0, "gc", "0", 0.002)]
    assert journal.process_summary(until=10.5)["kinds"]["gc"]["count"] == 1


def test_the_journal_is_bounded_and_counts_what_it_dropped(journal):
    n = journal.JOURNAL_MAX + 7
    for i in range(n):
        journal.record_process_event("cache_hit", "f", t_end=float(i))
    events = journal.process_events()
    assert len(events) == journal.JOURNAL_MAX
    assert events[0][0] == 7.0 and events[-1][0] == float(n - 1)
    assert journal.process_summary()["dropped"] == 7


def test_a_nested_startup_span_and_what_it_compiled_count_once(journal):
    # engine [0, 10] holds pool [2, 5], which holds a 1 s compile; and a
    # program's trace [6, 9] that traced a helper [7, 8] on its way
    journal.record_process_event("compile", "zeros", 1.0, t_end=4.0)
    journal.record_process_event("startup", "pool", 3.0, t_end=5.0)
    journal.record_process_event("trace", "helper", 1.0, t_end=8.0)
    journal.record_process_event("trace", "wave", 3.0, t_end=9.0)
    journal.record_process_event("startup", "engine", 10.0, t_end=10.0)
    kinds = journal.process_summary()["kinds"]
    startup = kinds["startup"]["labels"]
    assert startup["pool"]["seconds"] == pytest.approx(2.0)
    assert startup["engine"]["seconds"] == pytest.approx(10.0 - 3.0 - 3.0)
    assert kinds["compile"]["seconds"] == pytest.approx(1.0)
    # the helper's second stays the program's, in one entry
    assert kinds["trace"]["labels"] == {"wave": {"seconds": 3.0, "count": 1}}
    total = sum(k["seconds"] for k in kinds.values())
    assert total == pytest.approx(10.0)      # wall time, nothing twice


def test_startup_span_journals_its_self_time_and_counts_it(journal):
    before = telemetry.value("startup_seconds_total",
                             {"phase": "probe_outer"}, 0.0)
    with journal.startup_span("probe_outer", slots=2) as outer:
        with journal.startup_span("probe_inner"):
            time.sleep(0.01)
    labels = journal.process_summary()["kinds"]["startup"]["labels"]
    inner = labels["probe_inner"]["seconds"]
    assert inner >= 0.01 and outer.name == "startup/probe_outer"
    assert labels["probe_outer"]["seconds"] == pytest.approx(
        outer.elapsed - inner)
    assert telemetry.value("startup_seconds_total",
                           {"phase": "probe_outer"}) == pytest.approx(
        before + outer.elapsed - inner)


def test_a_model_constructor_journals_its_initialisers_once(journal):
    from paddle_tpu import nn

    class Two(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a, self.b = nn.Linear(16, 32), nn.Linear(32, 8)

    net = Two()
    net.to(dtype="bfloat16")
    labels = journal.process_summary()["kinds"]["startup"]["labels"]
    assert labels["param_init"]["count"] == 1      # four parameters, one entry
    assert labels["param_init"]["seconds"] > 0
    assert labels["cast"]["count"] == 1
    assert str(net.a.weight.dtype).endswith("bfloat16")


def test_metrics_json_serves_the_process_summary(journal):
    journal.record_process_event("startup", "engine", 2.5, t_end=3.0)
    code, _, body = telemetry.http_get_inline("/metrics.json")
    process = json.loads(body)["process"]
    assert code == 200 and process["dropped"] == 0
    assert process["kinds"]["startup"]["labels"]["engine"] == {
        "seconds": 2.5, "count": 1}


def test_a_large_trace_does_not_push_the_start_up_out_of_the_journal(journal):
    """More stages under one label than the journal holds, before the
    enclosing stage arrives: the run is summed, nothing older is dropped,
    and the enclosing stage still takes it all in."""
    journal.record_process_event("startup", "param_init", 5.0, t_end=5.0)
    n = 3 * journal.JOURNAL_MAX
    for i in range(n):
        journal.record_process_event("trace", "big_step", 5e-4,
                                     t_end=10.0 + i * 1e-3)
    mid = journal.process_summary()
    assert mid["dropped"] == 0 and mid["entries"] <= journal.JOURNAL_MAX
    assert mid["kinds"]["trace"]["seconds"] == pytest.approx(n * 5e-4)
    journal.record_process_event("trace", "big_step", 20.0, t_end=25.0)
    kinds = journal.process_summary()["kinds"]
    assert kinds["trace"]["labels"]["big_step"] == {
        "seconds": pytest.approx(20.0), "count": 1}
    assert kinds["startup"]["labels"]["param_init"]["seconds"] == 5.0


def test_the_registry_counts_a_nested_stage_once(journal):
    """The journal folds a helper's trace into its program's entry; the
    counter by stage had the helper's second already and gets only the
    program's own."""
    def total():
        return sum(telemetry.value("xla_stage_seconds_total",
                                   {"stage": "trace", "function": f}, 0.0)
                   for f in ("fold_helper", "fold_program"))
    before = total()
    telemetry._stage("trace", "fold_helper", 1.0, t_end=8.0)
    telemetry._stage("trace", "fold_program", 3.0, t_end=9.0)
    assert total() - before == pytest.approx(3.0)
    assert journal.process_summary()["kinds"]["trace"]["labels"] == {
        "fold_program": {"seconds": 3.0, "count": 1}}
