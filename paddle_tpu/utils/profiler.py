"""Profiler: host event recorder + XLA/TPU device trace bridge.

TPU-native redesign of the reference profiler
(ref paddle/fluid/platform/profiler.h:127,210 RecordEvent /
EnableProfiler/DisableProfiler, device_tracer.cc CUPTI bridge,
tools/timeline.py chrome-trace writer): host-side RAII events aggregate into
the same kind of per-op summary table; the device side delegates to
`jax.profiler` (XPlane), whose traces open in TensorBoard/Perfetto — the
CUPTI-equivalent on TPU. `export_chrome_tracing` keeps the
chrome://tracing workflow of tools/timeline.py.
"""
import contextlib
import json
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# re-entrant: the collector's `<family>/gc` span (telemetry._on_gc) exits
# from whatever bytecode its thread was at, which may hold this lock
_lock = threading.RLock()
_enabled = False
_events = []          # (name, start_s, dur_s, thread_id, pid, ids)
_raw_events = []      # chrome-format dicts (async spans, flow, counters)
_trace_gen = 0        # bumped when _raw_events is cleared (new trace)
_active_trace_dir = None


def trace_generation():
    """Monotone id of the current trace buffer. Emitters holding
    open-span/flow state across traces (telemetry.trace_request) compare
    it so a request straddling a profiler restart doesn't emit
    span-ends/flow-finishes whose partners died with the old buffer."""
    return _trace_gen


def now_us():
    """Microsecond timestamp on the SAME clock the host events use —
    raw trace events must share it or spans drift off the timeline."""
    return time.perf_counter() * 1e6


def trace_enabled():
    return _enabled


def emit_trace_event(event):
    """Append one raw chrome-trace event (async 'b'/'n'/'e', flow
    's'/'t'/'f', counter 'C', instant 'i', ...) to the host trace.
    Fills ts/pid/tid defaults; dropped (returns False) when the profiler
    is not recording — callers can emit unconditionally."""
    if not _enabled:
        return False
    ev = dict(event)
    ev.setdefault("ts", now_us())
    ev.setdefault("pid", 0)
    ev.setdefault("tid", threading.get_ident() % 10000)
    with _lock:
        _raw_events.append(ev)
    return True


class RecordEvent:
    """RAII host span (ref platform/profiler.h:127): the program's ONE
    span primitive, with two sinks. Usable as context manager or
    decorator.

      * the profiler's clock: every span enters a
        `jax.profiler.TraceAnnotation(name, **ids)`, so it lands in the
        host plane of whatever profiler session is running (this
        module's `start_profiler(trace_dir=...)`, an operator's
        `jax.profiler.start_trace`, a benchmark's traced window) next
        to the device lines. With no session active the annotation is
        inert: name and ids are encoded only while one records.
      * the chrome buffer (`export_chrome_tracing`, the fleet trace
        merge): appended only after `start_profiler()`; the ids become
        the event's `args`.

    Nesting gives the parent (a span entered inside another lies inside
    it on the same thread); the ids (`round`, `request_id`, `slot`,
    `chunk`, `lanes`, `step`) tie one round's or one request's spans
    together. `step_num=` makes the annotation a
    `StepTraceAnnotation`, which the profiler's step view groups by.

    `pid` places the slice on a chrome-trace process row (the fleet
    router exports each replica's scheduler activity on its own row —
    pid = replica_id + 1, pid 0 is the router/host). `elapsed` (seconds)
    and `end` (`time.perf_counter()` at exit) are set after exit whether
    or not anything was recording, so callers can both trace AND meter
    one timed region (the scheduler's per-phase attribution)."""

    __slots__ = ("name", "pid", "ids", "elapsed", "end", "_t0", "_ann")

    def __init__(self, name, pid=0, **ids):
        self.name = name
        self.pid = int(pid)
        self.ids = ids
        self.elapsed = self.end = self._t0 = self._ann = None

    def __enter__(self):
        cls = StepTraceAnnotation if "step_num" in self.ids \
            else TraceAnnotation
        self._ann = cls(self.name, **self.ids)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            self.end = time.perf_counter()
            self.elapsed = self.end - self._t0
            self._ann.__exit__(*exc)
            if _enabled:
                with _lock:
                    _events.append((self.name, self._t0, self.elapsed,
                                    threading.get_ident(), self.pid,
                                    self.ids))
        return False

    def __call__(self, fn):
        def wrapped(*a, **k):
            with RecordEvent(self.name, pid=self.pid, **self.ids):
                return fn(*a, **k)
        return wrapped


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """ref EnableProfiler (profiler.h:210). When `trace_dir` is given, also
    start a jax.profiler device trace (XPlane -> TensorBoard)."""
    global _enabled, _active_trace_dir, _trace_gen
    with _lock:
        _events.clear()
        _raw_events.clear()
        _trace_gen += 1
        _enabled = True
    if trace_dir is not None:
        import jax
        jax.profiler.start_trace(trace_dir)
        with _lock:
            _active_trace_dir = trace_dir


def stop_profiler(sorted_key="total", profile_path=None):
    """ref DisableProfiler. Prints the aggregated per-event table; writes a
    chrome trace json when profile_path is given (tools/timeline.py analog)."""
    global _enabled, _active_trace_dir
    with _lock:
        _enabled = False
    if _active_trace_dir is not None:
        import jax
        jax.profiler.stop_trace()
        with _lock:
            _active_trace_dir = None
    stats = summary(sorted_key)
    if profile_path:
        export_chrome_tracing(profile_path)
    return stats


def summary(sorted_key="total"):
    """Aggregate events -> list of dicts (name, calls, total_ms, avg_ms,
    min_ms, max_ms), printed like the reference profiler table."""
    agg = {}
    with _lock:
        evs = list(_events)
    for name, _t0, dur, _tid, _pid, _ids in evs:
        a = agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
        a[0] += 1
        a[1] += dur
        a[2] = min(a[2], dur)
        a[3] = max(a[3], dur)
    rows = [{"name": n, "calls": c, "total_ms": t * 1e3,
             "avg_ms": t * 1e3 / c, "min_ms": lo * 1e3, "max_ms": hi * 1e3}
            for n, (c, t, lo, hi) in agg.items()]
    key = {"total": "total_ms", "calls": "calls", "max": "max_ms",
           "min": "min_ms", "ave": "avg_ms"}.get(sorted_key, "total_ms")
    rows.sort(key=lambda r: r[key], reverse=True)
    if rows:
        w = max(len(r["name"]) for r in rows)
        print(f"{'Event':<{w}}  Calls  Total(ms)  Avg(ms)  Min(ms)  Max(ms)")
        for r in rows:
            print(f"{r['name']:<{w}}  {r['calls']:>5}  {r['total_ms']:>9.3f}"
                  f"  {r['avg_ms']:>7.3f}  {r['min_ms']:>7.3f}"
                  f"  {r['max_ms']:>7.3f}")
    return rows


def export_chrome_tracing(path, extra_events=()):
    """Write host events as chrome://tracing json (tools/timeline.py).
    RecordEvent slices ('X') merge with the raw events other layers emit
    through emit_trace_event (serving request spans/flows, counters) so
    one trace shows host events, decode waves, and request lifecycles.
    `extra_events` are appended verbatim — the fleet router passes 'M'
    process_name metadata naming each replica's pid row when it merges
    the per-replica sinks into one trace."""
    with _lock:
        evs = list(_events)
        raw = [dict(e) for e in _raw_events]
    events = [
        {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": dur * 1e6,
         "pid": pid, "tid": tid % 10000, "cat": "host",
         **({"args": ids} if ids else {})}
        for name, t0, dur, tid, pid, ids in evs]
    trace = {"traceEvents": events + raw + [dict(e)
                                            for e in extra_events]}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir=None):
    """with profiler(): ... — start/stop convenience
    (ref python/paddle/fluid/profiler.py profiler ctx)."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# --------------------------------------------------------------------------
# paddle.profiler new-style API (ref python/paddle/profiler/profiler.py:
# Profiler(targets, scheduler, on_trace_ready) + make_scheduler)
# --------------------------------------------------------------------------

class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"          # accepted alias: the device side is the TPU trace
    TPU = "tpu"
    CUSTOM_DEVICE = "custom_device"


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """ref profiler.make_scheduler: step-state machine. Returns
    fn(step) -> 'closed'|'ready'|'record' (repeat=0 means cycle forever;
    a zero-length cycle — closed=ready=record=0 — never records)."""
    cycle = closed + ready + record

    def schedule(step):
        if step < skip_first or cycle == 0:
            return "closed"
        s = step - skip_first
        if repeat and s >= cycle * repeat:
            return "closed"
        pos = s % cycle
        if pos < closed:
            return "closed"
        if pos < closed + ready:
            return "ready"
        return "record"

    return schedule


class Profiler:
    """ref python/paddle/profiler/profiler.py Profiler: step-scheduled
    host + device tracing.

        p = profiler.Profiler(trace_dir="/tmp/trace",
                              scheduler=make_scheduler(closed=1, ready=1,
                                                       record=3))
        p.start()
        for batch in loader:
            train_step(batch)
            p.step()
        p.stop()                 # host table + XPlane dump for TensorBoard
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 trace_dir=None, timer_only=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self.scheduler = scheduler or (lambda step: "record")
        self.on_trace_ready = on_trace_ready
        self.trace_dir = trace_dir
        self.timer_only = timer_only
        self._step = 0
        self._recording = False
        self._device_active = False

    def start(self):
        self._apply_state(self.scheduler(self._step))
        return self

    def step(self):
        self._step += 1
        self._apply_state(self.scheduler(self._step))

    def _apply_state(self, st):
        global _enabled
        want_record = st == "record"
        if want_record and not self._recording:
            with _lock:
                _enabled = True
            self._recording = True
            # `a and b and c or d` bug fixed here: the un-parenthesized
            # form started a DEVICE trace whenever GPU was in targets,
            # even with timer_only=True or no trace_dir
            want_device = (self.trace_dir is not None
                           and not self.timer_only
                           and (ProfilerTarget.TPU in self.targets
                                or ProfilerTarget.GPU in self.targets))
            if want_device and not self._device_active:
                import jax
                jax.profiler.start_trace(self.trace_dir)
                self._device_active = True
        elif not want_record and self._recording:
            self._flush()

    def _flush(self):
        global _enabled
        with _lock:
            _enabled = False
        self._recording = False
        if self._device_active:
            import jax
            jax.profiler.stop_trace()
            self._device_active = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def stop(self):
        if self._recording:
            self._flush()

    def summary(self, sorted_by="total"):
        return summary(sorted_by)

    def export(self, path, format="json"):
        return export_chrome_tracing(path)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False
