"""Training flight recorder: append-only JSONL run journal.

The serving path got full telemetry in the observability PR; this module
is the training-side counterpart — a crash-surviving record of what a
run actually did, step by step:

  * `FlightRecorder` writes one JSON object per line (`run_start`,
    `step`, `compile`, `nonfinite`, `collective`, `checkpoint`,
    `xla_program`, `jxaudit`, `run_end`). Events are ring-buffered
    (`ring_size`) between disk
    flushes, so a pathological run keeps bounded memory/IO and the LAST
    N events — the ones that explain the crash — always reach the
    journal: the context manager flushes on exception and appends a
    `run_end {status: "crashed"}` marker.
  * `jit.TrainStep.attach_flight_recorder` threads it through training:
    every step event carries the data-wait / host-dispatch / device-time
    split, loss, global grad norm, the non-finite sentinel, and MFU from
    the compiled executable's cost analysis (`cost_analysis` below —
    computed once per executable, cached by input signature).
  * `hapi.Model.fit(flight_recorder=...)` owns the run lifecycle
    (run_start/run_end, flush-on-exception) and measures data wait.
  * `amp.GradScaler`, `distributed.collective`, and `Model.save` emit
    `nonfinite` / `collective` / `checkpoint` events through the
    module-level *current recorder* (`set_recorder`/`get_recorder`) so
    deep layers need no plumbing.

`scripts/runlog_summary.py` renders a journal into a report;
`rollup()` is the compact version bench entrypoints attach to their
output. Journal schema is documented in docs/observability.md.
"""
import collections
import contextlib
import json
import os
import threading
import time


class NonFiniteError(RuntimeError):
    """Raised by fail-fast training when loss/grad-norm go non-finite."""


EVENT_KINDS = ("run_start", "step", "compile", "nonfinite", "collective",
               "checkpoint", "xla_program", "jxaudit", "shaudit", "chaos",
               "fault", "resume", "reshard", "hang", "slo", "alert",
               "spec", "run_end")

#: every `kind=` a `fault` event may carry.  The closed vocabulary is
#: what makes journals greppable and the runlog summarizer's fault
#: rollup stable; a NEW kind must be added here AND documented in
#: docs/observability.md — the `event-kind-documented` ptlint rule
#: enforces both at every literal call site.  The `replica_killed` /
#: `replica_degraded` pair is emitted dynamically by the fleet router
#: ("replica_" + retire reason), so the members are declared here even
#: though no literal call site spells them out.
FAULT_KINDS = ("nonfinite", "wave_error", "prefill_error",
               "callback_error", "token_mask_error", "cache_exhausted",
               "handoff_refused", "handoff_error", "degraded",
               "collective_error", "reshard_config_drift",
               "replica_killed", "replica_degraded", "replica_migration",
               "replica_handoff", "replica_spawn_failed")


def _json_safe(v):
    """JSON has no NaN/Inf literal; a diverged loss is exactly when the
    journal must stay parseable — spell non-finite floats as strings
    (same convention as telemetry's JSON snapshot)."""
    if isinstance(v, float) and (v != v or v in (float("inf"),
                                                 float("-inf"))):
        return "NaN" if v != v else ("+Inf" if v > 0 else "-Inf")
    if isinstance(v, dict):
        return {k: _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    return v


class FlightRecorder:
    """Ring-buffered JSONL journal writer.

        rec = FlightRecorder("runlog.jsonl")
        with rec:                      # run_start ... run_end bracketing
            rec.step(step=1, data_s=.001, host_s=.002, device_s=.03,
                     loss=2.3, mfu=0.41)

    `path=None` keeps events in memory only (bench rollups).
    `flush_every` defers disk writes; between flushes at most `ring_size`
    events are retained (oldest dropped, counted in `run_end`), so the
    last steps before a crash always survive — the flight-recorder
    contract. `fail_fast` is advisory state consumed by TrainStep: a
    non-finite step raises `NonFiniteError` instead of training on.
    """

    def __init__(self, path=None, ring_size=512, flush_every=1,
                 fail_fast=False, meta=None):
        self.path = os.fspath(path) if path is not None else None
        self.ring_size = max(1, int(ring_size))
        self.flush_every = max(1, int(flush_every))
        self.fail_fast = bool(fail_fast)
        self.meta = dict(meta or {})
        self._lock = threading.RLock()
        self._pending = collections.deque(maxlen=self.ring_size)
        self._recent = collections.deque(maxlen=self.ring_size)
        self._counts = {}
        self._dropped = 0
        self._seq = 0
        self._file = None
        self._started = False
        self._ended = False
        self.run_id = None

    # ---------------------------------------------------------------- core
    def record(self, event, **fields):
        """Append one event of kind `event`; returns the dict written
        (ts/seq added). The parameter is named `event`, not `kind`, so
        typed events (`fault`) may carry their own `kind` field."""
        with self._lock:
            self._seq += 1
            ev = {"ev": event, "ts": round(time.time(), 6),
                  "seq": self._seq}
            ev.update(_json_safe(fields))
            self._recent.append(ev)
            self._counts[event] = self._counts.get(event, 0) + 1
            if self.path is not None:
                if len(self._pending) == self._pending.maxlen:
                    self._dropped += 1    # ring full: oldest pending falls
                self._pending.append(ev)
                if len(self._pending) >= self.flush_every:
                    self.flush()
            return ev

    def flush(self):
        """Write buffered events to the journal file (no-op in-memory)."""
        if self.path is None:
            return
        with self._lock:
            if not self._pending:
                return
            if self._file is None:
                self._file = open(self.path, "a")
            while self._pending:
                self._file.write(
                    json.dumps(self._pending.popleft(), allow_nan=False)
                    + "\n")
            self._file.flush()

    def close(self):
        with self._lock:
            self.flush()
            if self._file is not None:
                self._file.close()
                self._file = None

    def events(self):
        """The last `ring_size` events, flushed or not (bench rollups)."""
        with self._lock:
            return list(self._recent)

    def counts(self):
        with self._lock:
            return dict(self._counts)

    @property
    def dropped_events(self):
        return self._dropped

    # ------------------------------------------------------------- typed
    def run_start(self, **meta):
        """Open a run. Idempotent while a run is open (fit and `with`
        both call it); after run_end it opens a NEW run segment in the
        same journal, so reusing one recorder across two fits brackets
        each run instead of silently recording neither."""
        import uuid
        with self._lock:
            if self._started and not self._ended:
                return None
            self._started, self._ended = True, False
            # a fresh id per run segment: checkpoints record it so a
            # resumed run's `resume` event names the run it continues
            self.run_id = uuid.uuid4().hex[:12]
        info = dict(self.meta)
        info.update(meta)
        return self.record("run_start", run_id=self.run_id, **info)

    def run_end(self, status="ok", error=None, **extra):
        """Close the run (idempotent) and force a flush — crashed runs
        keep their last `ring_size` events on disk."""
        with self._lock:
            if self._ended:
                return None
            self._ended = True
        fields = {"status": status, "counts": self.counts(),
                  "dropped_events": self._dropped}
        if error:
            fields["error"] = str(error)
        fields.update(extra)
        ev = self.record("run_end", **fields)
        self.flush()
        return ev

    def step(self, step, data_s, host_s, device_s, loss=None, grad_norm=None,
             mfu=None, nonfinite=False, **extra):
        return self.record(
            "step", step=int(step), data_s=round(float(data_s), 6),
            host_s=round(float(host_s), 6),
            device_s=round(float(device_s), 6),
            loss=None if loss is None else float(loss),
            grad_norm=None if grad_norm is None else float(grad_norm),
            mfu=None if mfu is None else float(mfu),
            nonfinite=bool(nonfinite), **extra)

    def compile_event(self, label, count=1, compile_s=None, flops=None,
                      bytes_accessed=None, **extra):
        fields = {"label": str(label), "count": int(count)}
        if compile_s is not None:
            fields["compile_s"] = round(float(compile_s), 6)
        if flops is not None:
            fields["flops"] = float(flops)
        if bytes_accessed is not None:
            fields["bytes_accessed"] = float(bytes_accessed)
        fields.update(extra)
        return self.record("compile", **fields)

    def nonfinite(self, step=None, loss=None, grad_norm=None,
                  source="train_step", **extra):
        fields = {"source": str(source)}
        if step is not None:
            fields["step"] = int(step)
        if loss is not None:
            fields["loss"] = float(loss)
        if grad_norm is not None:
            fields["grad_norm"] = float(grad_norm)
        fields.update(extra)
        return self.record("nonfinite", **fields)

    def collective(self, op, nbytes, group="default", traced=False, **extra):
        return self.record("collective", op=str(op), bytes=int(nbytes),
                           group=str(group), traced=bool(traced), **extra)

    def xla_program(self, program, flops=None, bytes_accessed=None,
                    peak_memory_bytes=None, fusion_count=None, **extra):
        """Compile-level audit result for one tracked program (the
        xprof observatory's journal hook — rides next to the `compile`
        events so one journal shows both when a program compiled and
        what the compiler made of it). None fields are journaled as
        null: 'analysis unavailable' is itself a recorded fact."""
        return self.record(
            "xla_program", program=str(program),
            flops=None if flops is None else float(flops),
            bytes_accessed=(None if bytes_accessed is None
                            else float(bytes_accessed)),
            peak_memory_bytes=(None if peak_memory_bytes is None
                               else float(peak_memory_bytes)),
            fusion_count=(None if fusion_count is None
                          else int(fusion_count)), **extra)

    def jxaudit(self, findings, by_rule=None, programs=None,
                degraded=None, **extra):
        """Semantic-audit verdict for the tracked programs (the jxaudit
        journal hook — rides next to compile / xla_program events so
        one journal shows what compiled, what it cost, and whether its
        semantics audit clean). `by_rule` maps rule id -> finding
        count; zero findings journals as a clean stamp, not silence."""
        fields = {"findings": int(findings),
                  "by_rule": {str(k): int(v)
                              for k, v in sorted((by_rule or {}).items())}}
        if programs is not None:
            fields["programs"] = int(programs)
        if degraded is not None:
            fields["degraded"] = int(degraded)
        fields.update(extra)
        return self.record("jxaudit", **fields)

    def shaudit(self, findings, by_rule=None, programs=None,
                degraded=None, wasted_replicated_bytes=None,
                collective_breaches=None, **extra):
        """Mesh-aware sharding-audit verdict for the pjit'd sharded
        programs (the shaudit journal hook). Beyond the jxaudit fields,
        `wasted_replicated_bytes` totals the accidental-replication
        waste across findings and `collective_breaches` counts
        collective-budget violations — zero findings journals as a
        clean stamp, not silence."""
        fields = {"findings": int(findings),
                  "by_rule": {str(k): int(v)
                              for k, v in sorted((by_rule or {}).items())}}
        if programs is not None:
            fields["programs"] = int(programs)
        if degraded is not None:
            fields["degraded"] = int(degraded)
        if wasted_replicated_bytes is not None:
            fields["wasted_replicated_bytes"] = int(wasted_replicated_bytes)
        if collective_breaches is not None:
            fields["collective_breaches"] = int(collective_breaches)
        fields.update(extra)
        return self.record("shaudit", **fields)

    def chaos(self, point, action, invocation=None, **extra):
        """An injected fault fired (utils.chaos) — journaled so a
        recovered run shows the injection next to the `fault` events
        the resilience layer wrote while handling it."""
        fields = {"point": str(point), "action": str(action)}
        if invocation is not None:
            fields["invocation"] = int(invocation)
        fields.update(extra)
        return self.record("chaos", **fields)

    def fault(self, kind, action=None, request_id=None, slot=None,
              error=None, **extra):
        """The resilience layer handled a fault: `kind` names the fault
        class (nonfinite / wave_error / prefill_error / callback_error /
        degraded), `action` what was done about it (retired / retry /
        degraded / shed)."""
        fields = {"kind": str(kind)}
        if action is not None:
            fields["action"] = str(action)
        if request_id is not None:
            fields["request_id"] = int(request_id)
        if slot is not None:
            fields["slot"] = int(slot)
        if error is not None:
            fields["error"] = str(error)
        fields.update(extra)
        return self.record("fault", **fields)

    def resume(self, prior_run_id=None, step=None, epoch=None, batch=None,
               **extra):
        """This run continues a checkpointed prior run: `prior_run_id`
        is the `run_start.run_id` of the run that wrote the checkpoint,
        `step` the global step being resumed from, epoch/batch the data
        cursor the fast-forward targets — journaled next to `run_start`
        so trajectory stitching is reconstructable from journals alone."""
        fields = {}
        if prior_run_id is not None:
            fields["prior_run_id"] = str(prior_run_id)
        if step is not None:
            fields["step"] = int(step)
        if epoch is not None:
            fields["epoch"] = int(epoch)
        if batch is not None:
            fields["batch"] = int(batch)
        fields.update(extra)
        return self.record("resume", **fields)

    def reshard(self, from_mesh=None, to_mesh=None, from_dp=None,
                to_dp=None, zero_stage=None, **extra):
        """This resume relaid sharded training state onto a DIFFERENT
        mesh than the checkpoint was written on (elastic reshard):
        from/to mesh shape dicts, the dp sizes on the checkpoint's dp
        axis, and the checkpoint's ZeRO stage — journaled right after
        the `resume` event so a trajectory stitched across a reshard
        names both layouts (utils/resume.maybe_record_reshard)."""
        fields = {}
        if from_mesh is not None:
            fields["from_mesh"] = {str(k): int(v)
                                   for k, v in dict(from_mesh).items()}
        if to_mesh is not None:
            fields["to_mesh"] = {str(k): int(v)
                                 for k, v in dict(to_mesh).items()}
        if from_dp is not None:
            fields["from_dp"] = int(from_dp)
        if to_dp is not None:
            fields["to_dp"] = int(to_dp)
        if zero_stage is not None:
            fields["zero_stage"] = int(zero_stage)
        fields.update(extra)
        return self.record("reshard", **fields)

    def hang(self, age_s, threshold_s=None, step=None, action="observe",
             stacks=None, **extra):
        """The training watchdog (utils/resume.TrainWatchdog) detected a
        stalled step: no step completed for `age_s` seconds against a
        rolling-step-time threshold. `stacks` carries the thread stack
        dumps captured at detection; `action` is "observe" or
        "interrupt" (deadline exceeded, KeyboardInterrupt raised into
        the main thread)."""
        fields = {"age_s": round(float(age_s), 3), "action": str(action)}
        if threshold_s is not None:
            fields["threshold_s"] = round(float(threshold_s), 3)
        if step is not None:
            fields["step"] = int(step)
        if stacks is not None:
            fields["stacks"] = stacks
        fields.update(extra)
        return self.record("hang", **fields)

    def slo(self, burn_rate, action, attainment=None, slo=None,
            window_requests=None, **extra):
        """The SLO engine's burn-rate state changed (serving/slo.py):
        `action` names the transition — "burn_alert" (burn rate crossed
        the fast-burn threshold), "burn_clear" (it came back under
        budget), "scale_up"/"scale_down" (the fleet autoscaler acted on
        it). `slo` names the worst target driving the verdict. Journaled
        on TRANSITIONS, not per evaluation, so a long breach is two
        lines, not a flood."""
        fields = {"burn_rate": round(float(burn_rate), 4),
                  "action": str(action)}
        if attainment is not None:
            fields["attainment"] = round(float(attainment), 6)
        if slo is not None:
            fields["slo"] = str(slo)
        if window_requests is not None:
            fields["window_requests"] = int(window_requests)
        fields.update(extra)
        return self.record("slo", **fields)

    def alert(self, rule, action, severity=None, **detail):
        """An AlertManager rule transitioned (utils/anomaly.py):
        `action` is "firing" (the detector tripped) or "cleared" (it
        recovered).  Journaled on TRANSITIONS only — the same
        discipline as the SLO engine's burn alerts, so a sustained
        anomaly is two lines, not a per-round flood.  `detail` carries
        the detector's evidence (value, z-score, the function that
        recompiled, the skew ratio, ...)."""
        fields = {"rule": str(rule), "action": str(action)}
        if severity is not None:
            fields["severity"] = str(severity)
        fields.update(detail)
        return self.record("alert", **fields)

    def spec(self, proposed, accepted, lanes=None, spec_depth=None,
             **extra):
        """One speculative decode wave's draft economics (the serving
        scheduler journals this next to its fault events): `proposed` =
        draft tokens offered to the verify program, `accepted` = how
        many the exact acceptance-rejection kept, `lanes` = slots the
        wave dispatched, `spec_depth` = accepted per dispatched lane.
        runlog_summary folds these into a per-run acceptance table."""
        fields = {"proposed": int(proposed), "accepted": int(accepted)}
        if lanes is not None:
            fields["lanes"] = int(lanes)
        if spec_depth is not None:
            fields["spec_depth"] = float(spec_depth)
        fields.update(extra)
        return self.record("spec", **fields)

    def checkpoint(self, path=None, step=None, **extra):
        fields = {}
        if path is not None:
            fields["path"] = str(path)
        if step is not None:
            fields["step"] = int(step)
        fields.update(extra)
        return self.record("checkpoint", **fields)

    # --------------------------------------------------------- lifecycle
    def __enter__(self):
        self._prev = set_recorder(self)
        self.run_start()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.run_end(status="crashed",
                         error=f"{exc_type.__name__}: {exc}")
        else:
            self.run_end(status="ok")
        set_recorder(getattr(self, "_prev", None))
        self.close()
        return False


# ---------------------------------------------------------------------------
# current recorder (so amp / collective / save need no plumbing)
# ---------------------------------------------------------------------------

_current_lock = threading.Lock()
_current = None


def set_recorder(recorder):
    """Install `recorder` as the process-wide current recorder; returns
    the previous one (restore it when done)."""
    global _current
    with _current_lock:
        prev = _current
        _current = recorder
        return prev


def get_recorder():
    return _current


@contextlib.contextmanager
def recording(recorder):
    prev = set_recorder(recorder)
    try:
        yield recorder
    finally:
        set_recorder(prev)


# ---------------------------------------------------------------------------
# journal readers / rollup
# ---------------------------------------------------------------------------

def read_journal(path):
    """Parse a JSONL journal -> list of event dicts (strict: a malformed
    line raises — the writer emits one valid object per line)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def rollup(events):
    """Compact summary for bench output: steps, mean MFU over steps that
    have one (None when none has: not measured), executable
    (re)compiles, and non-finite incidents."""
    steps = [e for e in events if e.get("ev") == "step"]
    mfus = [e["mfu"] for e in steps
            if isinstance(e.get("mfu"), (int, float)) and e["mfu"] > 0]
    return {
        "steps": len(steps),
        "mean_mfu": round(sum(mfus) / len(mfus), 4) if mfus else None,
        "recompiles": sum(int(e.get("count", 1)) for e in events
                          if e.get("ev") == "compile"),
        "nonfinite": sum(1 for e in events if e.get("ev") == "nonfinite"),
    }


# ---------------------------------------------------------------------------
# cost accounting (MFU)
# ---------------------------------------------------------------------------

# Published peaks by TPU device-kind substring (first match wins): bf16
# dense FLOP/s and HBM bytes/s, from Google Cloud's per-generation TPU
# documentation (v5e: 197 TFLOP/s, 819 GB/s). A device that is not in
# the table has no peak: `device_peaks` returns None and every
# utilization derived from it reads "not measured" (None) — a CPU run
# never yields an MFU or an HBM utilization.
_PEAKS_BY_KIND = (
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v5litepod", 197e12, 819e9),
    ("v6e", 918e12, 1640e9),
    ("trillium", 918e12, 1640e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)


def device_peaks(device=None):
    """(peak FLOP/s, peak HBM bytes/s) of `device` (default: the first
    local device), or None when its kind is not in the table."""
    if device is None:
        import jax
        device = jax.local_devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, flops, hbm_bw in _PEAKS_BY_KIND:
        if key in kind:
            return flops, hbm_bw
    return None


def mfu_text(flops_per_s, device=None):
    """`flops_per_s` over the device's published peak, for a log line;
    "not measured" on a device that has none."""
    peaks = device_peaks(device)
    return f"{flops_per_s / peaks[0]:.3f}" if peaks else "not measured"


def normalize_cost_analysis(ca):
    """Normalize a raw `cost_analysis()` result to one shape.

    Across jax versions/backends the call returns a dict, a
    list-of-dicts (one per device/partition — the first carries the
    program totals), or something unusable; keys use XLA's spaced
    spelling ("bytes accessed"). This is THE one place that shape
    knowledge lives — jit.TrainStep and the xprof audit consume this
    normalized form. Returns
    {"flops": float, "bytes_accessed": float, "transcendentals": float}
    (keys present when the analysis provides a numeric value, never
    NaN), or None when nothing usable came back."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    out = {}
    for key, spelled in (("flops", "flops"),
                         ("bytes_accessed", "bytes accessed"),
                         ("transcendentals", "transcendentals")):
        v = ca.get(spelled, ca.get(key))
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and v == v:
            out[key] = float(v)
    return out or None


def cost_analysis(jitted, *args, **kwargs):
    """FLOPs/bytes of the executable `jitted(*args)` would run, via the
    lowering's HLO cost analysis — no second backend compile, and safe
    to call with the concrete (not-yet-donated) call arguments. Returns
    the `normalize_cost_analysis` dict or None when the jax
    build/backend can't analyze."""
    try:
        lowered = jitted.lower(*args, **kwargs)
        ca = lowered.cost_analysis()
    except Exception:
        return None
    return normalize_cost_analysis(ca)
