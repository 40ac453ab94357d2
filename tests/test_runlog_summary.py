"""scripts/runlog_summary.py smoke: the CLI renders a real generated
journal (percentile table, MFU line, compiles, non-finite incidents) —
tier-1 so the tooling can't silently rot."""
import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.jit import TrainStep
from paddle_tpu.utils import flight_recorder as fr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "runlog_summary.py")


def _generate_journal(path):
    pt.seed(3)
    net = nn.Linear(4, 3)
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda o, y: nn.functional.mse_loss(o, y), opt)
    rec = fr.FlightRecorder(path)
    # the CPU has no entry in the peaks table, so a real step journals
    # no MFU; the CLI's MFU line is rendered from a stand-in v5e peak
    real_peaks = fr.device_peaks
    fr.device_peaks = lambda device=None: (197e12, 819e9)
    try:
        step.attach_flight_recorder(rec)
    finally:
        fr.device_peaks = real_peaks
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype("f4")
    y = rng.randn(8, 3).astype("f4")
    xnan = x.copy()
    xnan[0] = np.nan
    with rec:
        for _ in range(4):
            step.set_data_wait(0.001)
            step(x, y)
        step(xnan, y)
        rec.collective(op="all_reduce", nbytes=4096, group="dp")
        rec.checkpoint(path="ckpt/5", step=5)
        rec.xla_program("train_step", flops=1.2e9, bytes_accessed=3.4e8,
                        peak_memory_bytes=26743969, fusion_count=349)
        rec.jxaudit(findings=2, by_rule={"donation-missing": 2},
                    programs=6, degraded=0)
        rec.shaudit(findings=1, by_rule={"accidental-replication": 1},
                    programs=3, degraded=0,
                    wasted_replicated_bytes=3670016,
                    collective_breaches=0)
        # fleet events: the router's replica_* fault kinds + the SLO
        # engine's burn journal (serving/slo.py schema)
        rec.fault(kind="replica_killed", action="replace",
                  error="replica 0")
        rec.fault(kind="replica_migration", action="resubmitted",
                  request_id=3, error="replica 0 -> 1")
        rec.fault(kind="replica_migration", action="resubmitted",
                  request_id=4, error="replica 0 -> 1")
        rec.slo(burn_rate=2.5, action="burn_alert", attainment=0.4,
                slo="tpot_p99", window_requests=8)
        rec.slo(burn_rate=2.5, action="scale_up", attainment=0.4,
                slo="tpot_p99", window_requests=8, replicas=2)
        rec.slo(burn_rate=0.8, action="burn_clear", attainment=0.96,
                slo="tpot_p99", window_requests=8)
        # speculative decoding: the serving scheduler's per-wave events
        rec.spec(proposed=12, accepted=9, lanes=4, spec_depth=2.25)
        rec.spec(proposed=12, accepted=3, lanes=4, spec_depth=0.75)
    return path


def test_cli_end_to_end(tmp_path):
    journal = _generate_journal(str(tmp_path / "run.jsonl"))
    out = subprocess.run(
        [sys.executable, SCRIPT, journal],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    text = out.stdout
    assert "status=ok" in text and "steps=5" in text
    assert "p50" in text and "p99" in text          # percentile header
    for phase in ("data", "host", "device", "total"):
        assert phase in text
    assert "mfu: mean=" in text                     # MFU line renders
    assert "compiles: 1" in text
    assert "non-finite incidents: 1" in text
    assert "all_reduce[dp]" in text and "4.0 KB" in text
    assert "checkpoints: 1" in text
    # compiled-programs table merges the compile event (TrainStep's
    # label) with the journaled xla_program audit numbers
    assert "compiled programs:" in text
    assert "1.200e+09" in text and "25.5 MB" in text and "349" in text
    # semantic-audit verdict renders next to the programs table
    assert "semantic audit (jxaudit): 2 finding(s) (6 programs) — " \
           "donation-missing=2" in text
    # sharding-audit verdict with the mesh-specific severities
    assert "sharding audit (shaudit): 1 finding(s) (3 programs) — " \
           "accidental-replication=1" in text
    assert "wasted replicated bytes: 3.5 MB" in text
    # fleet table: replica events + the SLO burn journal
    assert "fleet:" in text
    assert "kills" in text and "migrations" in text
    assert "slo burn: peak=2.50 last=0.80" in text
    assert "burn_alert=1" in text and "scale_up=1" in text
    # speculative acceptance line folds the per-wave spec events
    assert "speculative decoding: 2 waves, 12/24 drafts accepted" in text
    assert "rate 0.500" in text and "6.00/wave" in text


def test_cli_json_mode(tmp_path):
    journal = _generate_journal(str(tmp_path / "run.jsonl"))
    out = subprocess.run(
        [sys.executable, SCRIPT, journal, "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout)
    assert summary["steps"] == 5
    assert summary["compiles"] == 1
    assert summary["mfu"]["mean"] > 0
    assert summary["nonfinite"]["count"] == 1
    assert summary["phases"]["device"]["count"] == 5
    assert summary["collectives"][0]["bytes"] == 4096
    prog = summary["programs"]["train_step"]
    assert prog["compiles"] == 1
    assert prog["fusion_count"] == 349
    assert prog["peak_memory_bytes"] == 26743969
    assert prog["flops"] == 1.2e9          # audit value wins over the
    #                                        compile event's estimate
    assert summary["jxaudit"] == {
        "runs": 1, "findings": 2, "by_rule": {"donation-missing": 2},
        "programs": 6, "degraded": 0}
    assert summary["shaudit"] == {
        "runs": 1, "findings": 1,
        "by_rule": {"accidental-replication": 1}, "programs": 3,
        "degraded": 0, "wasted_replicated_bytes": 3670016,
        "collective_breaches": 0}
    assert summary["spec"] == {
        "waves": 2, "proposed": 24, "accepted": 12,
        "acceptance_rate": 0.5, "accepted_per_wave": 6.0}
    assert summary["fleet"] == {
        "migrations": 2, "kills": 1, "degraded": 0, "spawn_failures": 0,
        "slo": {"events": 3,
                "actions": {"burn_alert": 1, "scale_up": 1,
                            "burn_clear": 1},
                "burn_rate_peak": 2.5, "last_burn_rate": 0.8}}


def test_fleet_section_absent_without_fleet_events(tmp_path):
    """A single-engine training journal renders NO fleet table."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import runlog_summary
    finally:
        sys.path.pop(0)
    events = [{"ev": "run_start", "ts": 0, "seq": 1},
              {"ev": "fault", "ts": 1, "seq": 2, "kind": "wave_error",
               "action": "retry"},
              {"ev": "run_end", "ts": 2, "seq": 3, "status": "ok"}]
    s = runlog_summary.summarize(events)
    assert s["fleet"] is None
    assert "fleet:" not in runlog_summary.render(s)


def test_summarize_importable_without_jax_side_effects(tmp_path):
    """The CLI module is stdlib-only: importable and usable on a bare
    journal without pulling in paddle_tpu/jax."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import runlog_summary
    finally:
        sys.path.pop(0)
    events = [{"ev": "run_start", "ts": 0, "seq": 1, "mode": "fit"},
              {"ev": "step", "ts": 1, "seq": 2, "step": 1, "data_s": 0.01,
               "host_s": 0.02, "device_s": 0.03, "loss": 1.0,
               "mfu": 0.5, "nonfinite": False},
              {"ev": "run_end", "ts": 2, "seq": 3, "status": "ok"}]
    s = runlog_summary.summarize(events)
    assert s["steps"] == 1 and s["status"] == "ok"
    assert abs(s["phases"]["total"]["p50_ms"] - 60.0) < 1e-6
    text = runlog_summary.render(s)
    assert "mfu: mean=0.5000" in text
