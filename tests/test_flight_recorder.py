"""Training flight recorder: journal schema, ring-buffer crash flush,
MFU/cost accounting, the in-step non-finite sentinel, GradScaler skip
telemetry, collective byte counters, and the TelemetryCallback
device-memory regression (ISSUE 4 acceptance surface)."""
import json
import math

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, hapi
from paddle_tpu.hapi import callbacks as cbks
from paddle_tpu.io import DataLoader, TensorDataset
from paddle_tpu.jit import TrainStep
from paddle_tpu.utils import flight_recorder as fr
from paddle_tpu.utils import telemetry


def make_step(seed=0):
    pt.seed(seed)
    net = nn.Linear(4, 3)
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())

    def loss_fn(out, y):
        return nn.functional.mse_loss(out, y)

    return TrainStep(net, loss_fn, opt)


def batch(seed=0, nan_at=None):
    rng = np.random.RandomState(seed)
    x = rng.randn(8, 4).astype("f4")
    y = rng.randn(8, 3).astype("f4")
    if nan_at is not None:
        x[nan_at] = np.nan
    return x, y


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------

class TestRecorderCore:
    def test_journal_lines_are_strict_json(self, tmp_path):
        path = tmp_path / "run.jsonl"
        rec = fr.FlightRecorder(path)
        with rec:
            rec.step(step=1, data_s=0.1, host_s=0.2, device_s=0.3,
                     loss=float("nan"), mfu=0.5)
            rec.collective(op="all_reduce", nbytes=128, group="dp")
        lines = [ln for ln in path.read_text().splitlines() if ln]
        events = []
        for ln in lines:
            # strict JSON: the writer uses allow_nan=False, so a bare
            # NaN/Infinity token can never appear in the journal
            events.append(json.loads(ln, parse_constant=lambda c: pytest.fail(
                f"non-strict JSON constant {c} in journal line {ln!r}")))
        kinds = [e["ev"] for e in events]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        step_ev = next(e for e in events if e["ev"] == "step")
        assert step_ev["loss"] == "NaN"       # spelled, not bare NaN token

    def test_ring_flush_on_exception_preserves_last_steps(self, tmp_path):
        path = tmp_path / "crash.jsonl"
        rec = fr.FlightRecorder(path, ring_size=8, flush_every=10 ** 9)
        with pytest.raises(RuntimeError):
            with rec:
                for i in range(20):
                    rec.step(step=i, data_s=0, host_s=0, device_s=0)
                raise RuntimeError("boom")
        events = fr.read_journal(path)
        end = events[-1]
        assert end["ev"] == "run_end" and end["status"] == "crashed"
        assert "boom" in end["error"]
        steps = [e["step"] for e in events if e["ev"] == "step"]
        # ring_size=8, one slot went to run_end: the LAST steps survive
        assert steps == sorted(steps) and steps[-1] == 19
        assert len(steps) >= 7 and min(steps) >= 12
        assert end["dropped_events"] > 0

    def test_recorder_reuse_brackets_each_run(self, tmp_path):
        """One recorder across two runs: each gets its own
        run_start/run_end segment (a crashed first run must not make the
        retry invisible)."""
        path = tmp_path / "two.jsonl"
        rec = fr.FlightRecorder(path)
        with pytest.raises(RuntimeError):
            with rec:
                rec.step(step=1, data_s=0, host_s=0, device_s=0)
                raise RuntimeError("first run dies")
        with rec:
            rec.step(step=1, data_s=0, host_s=0, device_s=0)
        kinds = [e["ev"] for e in fr.read_journal(path)]
        assert kinds.count("run_start") == 2
        assert kinds.count("run_end") == 2
        statuses = [e["status"] for e in fr.read_journal(path)
                    if e["ev"] == "run_end"]
        assert statuses == ["crashed", "ok"]

    def test_current_recorder_stack(self):
        rec = fr.FlightRecorder()
        assert fr.get_recorder() is None
        with fr.recording(rec):
            assert fr.get_recorder() is rec
        assert fr.get_recorder() is None


# ---------------------------------------------------------------------------
# TrainStep instrumentation
# ---------------------------------------------------------------------------

V5E_PEAKS = (197e12, 819e9)


def test_device_peaks_come_from_the_table_or_not_at_all():
    """No default peak and no override: a device kind that is not in
    the table (the CPU here) has no peak, so no utilization."""
    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert fr.device_peaks(Dev("TPU v5 lite")) == V5E_PEAKS
    assert fr.device_peaks(Dev("TPU v5e")) == V5E_PEAKS
    assert fr.device_peaks(Dev("cpu")) is None
    assert fr.device_peaks(Dev("TPU v9 unheard-of")) is None
    assert fr.device_peaks() is None           # tests run on the CPU


class TestTrainStepInstrumentation:
    @pytest.mark.parametrize("peaks", [V5E_PEAKS, None],
                             ids=["device-in-table", "device-unknown"])
    def test_step_events_and_cost_accounting(self, tmp_path, monkeypatch,
                                             peaks):
        monkeypatch.setattr(fr, "device_peaks", lambda device=None: peaks)
        path = tmp_path / "run.jsonl"
        step = make_step()
        rec = fr.FlightRecorder(path)
        step.attach_flight_recorder(rec)
        x, y = batch()
        with rec:
            for _ in range(3):
                step.set_data_wait(0.002)
                step(x, y)
        events = fr.read_journal(path)
        steps = [e for e in events if e["ev"] == "step"]
        assert len(steps) == 3
        for e in steps:
            for key in ("data_s", "host_s", "device_s", "mfu", "loss",
                        "grad_norm", "nonfinite"):
                assert key in e, f"step event missing {key}"
            if peaks is None:
                assert e["mfu"] is None            # "not measured"
            else:
                assert e["mfu"] > 0 and math.isfinite(e["mfu"])
            assert e["data_s"] >= 0 and e["host_s"] > 0
        compiles = [e for e in events if e["ev"] == "compile"]
        assert len(compiles) == 1 and compiles[0]["count"] == 1
        assert compiles[0]["flops"] > 0
        assert compiles[0]["bytes_accessed"] > 0
        # gauges made it to the registry / exporter
        assert telemetry.value("train_step_flops") == compiles[0]["flops"]
        text = telemetry.render_prometheus()
        assert "train_step_flops" in text
        if peaks is not None:
            assert telemetry.value("train_mfu") == steps[-1]["mfu"]
            assert "train_mfu" in text

    def test_nonfinite_sentinel_and_counter(self, tmp_path):
        step = make_step()
        rec = fr.FlightRecorder(tmp_path / "nf.jsonl")
        step.attach_flight_recorder(rec)
        before = telemetry.value("train_nonfinite_total", default=0) or 0
        x, y = batch()
        with rec:
            step(x, y)
            assert step.last_nonfinite() is False
            step(*batch(nan_at=0))
            assert step.last_nonfinite() is True
        events = fr.read_journal(rec.path)
        nf = [e for e in events if e["ev"] == "nonfinite"]
        assert len(nf) == 1 and nf[0]["source"] == "train_step"
        assert nf[0]["step"] == 2
        after = telemetry.value("train_nonfinite_total", default=0)
        assert after == before + 1
        marked = [e for e in events if e["ev"] == "step" and e["nonfinite"]]
        assert len(marked) == 1

    def test_fail_fast_raises(self, tmp_path):
        step = make_step()
        rec = fr.FlightRecorder(tmp_path / "ff.jsonl", fail_fast=True)
        step.attach_flight_recorder(rec)
        with pytest.raises(fr.NonFiniteError):
            with rec:
                step(*batch(nan_at=1))
        # the journal still has the evidence
        events = fr.read_journal(rec.path)
        assert any(e["ev"] == "nonfinite" for e in events)
        assert events[-1]["status"] == "crashed"

    def test_uninstrumented_step_keeps_working(self):
        step = make_step()
        x, y = batch()
        loss = step(x, y)
        assert math.isfinite(float(loss.numpy()))
        assert step.last_nonfinite() is False     # sentinel still computed


# ---------------------------------------------------------------------------
# Model.fit end-to-end (acceptance scenario)
# ---------------------------------------------------------------------------

class TestFitJournal:
    def test_two_epoch_fit_journal(self, tmp_path, monkeypatch):
        monkeypatch.setattr(fr, "device_peaks",
                            lambda device=None: V5E_PEAKS)
        path = tmp_path / "fit.jsonl"
        pt.seed(7)
        net = nn.Linear(4, 3)
        model = hapi.Model(net)
        model.prepare(
            optimizer=pt.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters()),
            loss=lambda out, y: nn.functional.mse_loss(out, y))
        rng = np.random.RandomState(0)
        ds = TensorDataset([rng.randn(24, 4).astype("f4"),
                            rng.randn(24, 3).astype("f4")])
        loader = DataLoader(ds, batch_size=8)
        model.fit(loader, epochs=2, verbose=0, flight_recorder=str(path))
        events = fr.read_journal(path)
        assert events[0]["ev"] == "run_start"
        assert events[0]["epochs"] == 2
        end = events[-1]
        assert end["ev"] == "run_end" and end["status"] == "ok"
        steps = [e for e in events if e["ev"] == "step"]
        assert len(steps) == 6       # 24/8 * 2 epochs
        for e in steps:
            assert e["mfu"] > 0
            for key in ("data_s", "host_s", "device_s"):
                assert isinstance(e[key], float)
        # compile events exactly once per executable: ONE executable
        # serves both epochs (fixed shapes) -> exactly one event
        compiles = [e for e in events if e["ev"] == "compile"]
        assert len(compiles) == 1 and compiles[0]["count"] == 1
        # recorder detached after fit: later fits don't journal into it
        assert fr.get_recorder() is None
        assert model._train_step._recorder is None

    def test_unwritable_journal_path_does_not_leak_recorder(self, tmp_path):
        pt.seed(7)
        net = nn.Linear(4, 3)
        model = hapi.Model(net)
        model.prepare(
            optimizer=pt.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters()),
            loss=lambda out, y: nn.functional.mse_loss(out, y))
        ds = TensorDataset([np.zeros((8, 4), "f4"),
                            np.zeros((8, 3), "f4")])
        with pytest.raises(OSError):
            model.fit(DataLoader(ds, batch_size=8), epochs=1, verbose=0,
                      flight_recorder=str(tmp_path / "no/such/dir/r.jsonl"))
        # the broken recorder must NOT stay installed process-wide
        assert fr.get_recorder() is None
        assert model._flight_recorder is None

    def test_fit_checkpoint_event_and_crash_flush(self, tmp_path):
        path = tmp_path / "crash_fit.jsonl"
        pt.seed(7)
        net = nn.Linear(4, 3)
        model = hapi.Model(net)
        model.prepare(
            optimizer=pt.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters()),
            loss=lambda out, y: nn.functional.mse_loss(out, y))
        rng = np.random.RandomState(0)
        ds = TensorDataset([rng.randn(16, 4).astype("f4"),
                            rng.randn(16, 3).astype("f4")])

        class SaveThenBoom(cbks.Callback):
            def on_epoch_end(self, epoch, logs=None):
                self.model.save(str(tmp_path / "ckpt"))
                raise RuntimeError("mid-train crash")

        with pytest.raises(RuntimeError, match="mid-train crash"):
            model.fit(DataLoader(ds, batch_size=8), epochs=2, verbose=0,
                      callbacks=[SaveThenBoom()],
                      flight_recorder=str(path))
        events = fr.read_journal(path)
        assert events[-1]["status"] == "crashed"
        assert "mid-train crash" in events[-1]["error"]
        assert any(e["ev"] == "checkpoint" for e in events)
        assert any(e["ev"] == "step" for e in events)
        assert fr.get_recorder() is None


# ---------------------------------------------------------------------------
# satellites: GradScaler, collective counters, TelemetryCallback memory
# ---------------------------------------------------------------------------

class TestGradScalerTelemetry:
    def test_forced_inf_counts_skip_and_halves_scale(self):
        from paddle_tpu import amp
        pt.seed(0)
        net = nn.Linear(4, 2)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        scaler = amp.GradScaler(init_loss_scaling=1024.0,
                                decr_every_n_nan_or_inf=1)
        before = telemetry.value("amp_skipped_steps_total", default=0) or 0
        x = pt.to_tensor(np.full((4, 4), 1e38, "f4"))
        y = pt.to_tensor(np.zeros((4, 2), "f4"))
        w0 = net.weight.numpy().copy()
        loss = nn.functional.mse_loss(net(x), y)    # overflows in fp32
        scaler.minimize(opt, scaler.scale(loss))
        after = telemetry.value("amp_skipped_steps_total", default=0)
        assert after == before + 1
        assert scaler.get_init_loss_scaling() == 512.0      # halved
        assert telemetry.value("amp_loss_scale") == 512.0
        np.testing.assert_array_equal(net.weight.numpy(), w0)  # skipped

    def test_skip_journals_through_current_recorder(self, tmp_path):
        from paddle_tpu import amp
        pt.seed(0)
        net = nn.Linear(2, 1)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        scaler = amp.GradScaler(init_loss_scaling=4.0,
                                decr_every_n_nan_or_inf=1)
        rec = fr.FlightRecorder(tmp_path / "amp.jsonl")
        x = pt.to_tensor(np.full((2, 2), np.inf, "f4"))
        y = pt.to_tensor(np.zeros((2, 1), "f4"))
        with rec:
            loss = nn.functional.mse_loss(net(x), y)
            scaler.minimize(opt, scaler.scale(loss))
        nf = [e for e in fr.read_journal(rec.path)
              if e["ev"] == "nonfinite"]
        assert len(nf) == 1 and nf[0]["source"] == "amp_grad_scaler"


class TestCollectiveTelemetry:
    def test_eager_all_reduce_counts_bytes(self, tmp_path):
        from paddle_tpu import distributed as dist
        before_calls = telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "default"}, 0) or 0
        before_bytes = telemetry.value(
            "collective_bytes_total",
            {"op": "all_reduce", "group": "default"}, 0) or 0
        rec = fr.FlightRecorder(tmp_path / "coll.jsonl")
        t = pt.to_tensor(np.ones((8, 4), "f4"))
        with rec:
            dist.all_reduce(t)
        assert telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "default"}) == before_calls + 1
        assert telemetry.value(
            "collective_bytes_total",
            {"op": "all_reduce", "group": "default"}) \
            == before_bytes + 8 * 4 * 4
        ev = [e for e in fr.read_journal(rec.path)
              if e["ev"] == "collective"]
        assert ev and ev[0]["op"] == "all_reduce"
        assert ev[0]["bytes"] == 128 and ev[0]["traced"] is False

    def test_positional_and_int_group_resolve_axis_label(self):
        from paddle_tpu import distributed as dist
        from paddle_tpu.distributed import ReduceOp, mesh as mesh_mod
        mesh_mod.default_mesh()      # registers group 0 on the dp axis
        before = telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "dp"}, 0) or 0
        t = pt.to_tensor(np.ones((2,), "f4"))
        dist.all_reduce(t, ReduceOp.SUM, 0)      # positional int group id
        dist.all_reduce(t, group=0)              # keyword int group id
        assert telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "dp"}) == before + 2

    def test_kwarg_payload_still_counts_bytes(self):
        from paddle_tpu import distributed as dist
        before = telemetry.value(
            "collective_bytes_total",
            {"op": "all_gather", "group": "default"}, 0) or 0
        out = []
        dist.all_gather(tensor_list=out,
                        tensor=pt.to_tensor(np.ones((2, 2), "f4")))
        assert telemetry.value(
            "collective_bytes_total",
            {"op": "all_gather", "group": "default"}) == before + 16

    def test_traced_collective_counts_once_per_trace(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.distributed import collective, mesh as mesh_mod
        mesh = mesh_mod.default_mesh()
        before = telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "default"}, 0) or 0

        def body(x):
            return collective.all_reduce(x)._data

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                               out_specs=P("dp")))
        x = jnp.ones((8, 2), jnp.float32)
        fn(x)
        fn(x)     # second call: cached executable, no new trace
        after = telemetry.value(
            "collective_calls_total",
            {"op": "all_reduce", "group": "default"})
        assert after == before + 1      # once per trace, not per call


class TestTelemetryCallbackMemory:
    def test_memory_stats_none_skips_gauges(self):
        """CPU-only jax: device.memory_stats() is None — the callback
        must skip the gauges, not raise and not publish zeros."""
        from paddle_tpu.utils import monitor

        class FakeDev:
            def memory_stats(self):
                return None

        assert monitor.device_memory_stats(FakeDev()) is None
        cb = cbks.TelemetryCallback(memory_freq=1, device=FakeDev())
        cb._mem_in_use.set(123.0)      # pre-existing value must survive
        cb.on_train_batch_begin(0)
        cb.on_train_batch_end(0, {"loss": 1.0})   # polls at step 0
        assert cb._mem_in_use.value() == 123.0

    def test_memory_stats_raising_device_is_survived(self):
        class BadDev:
            def memory_stats(self):
                raise RuntimeError("no PJRT stats")

        cb = cbks.TelemetryCallback(memory_freq=1, device=BadDev())
        cb.on_train_batch_begin(0)
        cb.on_train_batch_end(0, {"loss": 1.0})    # must not raise

    def test_real_backend_poll_is_graceful(self):
        from paddle_tpu.utils import monitor
        stats = monitor.device_memory_stats()
        assert stats is None or stats["bytes_in_use"] >= 0


# ---------------------------------------------------------------------------
# rollup helper (bench surface)
# ---------------------------------------------------------------------------

def test_rollup():
    events = [
        {"ev": "compile", "count": 1},
        {"ev": "step", "mfu": 0.4},
        {"ev": "step", "mfu": 0.6},
        {"ev": "step", "mfu": None},
        {"ev": "nonfinite"},
    ]
    r = fr.rollup(events)
    assert r == {"steps": 3, "mean_mfu": 0.5, "recompiles": 1,
                 "nonfinite": 1}
    # no step carries an MFU (device off the peaks table): not measured
    assert fr.rollup([{"ev": "step", "mfu": None}])["mean_mfu"] is None
