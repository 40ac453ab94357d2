"""Unified telemetry: typed metric registry, Prometheus/JSON exporters,
request-trace emission, and XLA compile-event tracking.

This is the observability layer the reference stack spreads over
platform/monitor.h (StatRegistry), platform/profiler.h (RecordEvent) and
tools/timeline.py, rebuilt as one subsystem:

  * a typed metric REGISTRY — Counter / Gauge / Histogram with label
    sets and exponential latency buckets — that subsumes the flat
    `utils.monitor` int stats (they ride along in every snapshot and
    exposition) and renders both a JSON snapshot and the Prometheus
    text format;
  * an optional stdlib-`http.server` background thread (`MetricsServer`)
    exposing `/metrics` (Prometheus), `/metrics.json` (snapshot),
    `/healthz`, `/metrics/history` (the utils/timeseries ring-buffer
    history, `snapshot_history()`) and `/dashboard` (self-contained
    sparkline page);
  * XLA compile-event tracking: a `jax.monitoring` listener counts
    backend compilations (persistent-cache loads included — a new
    executable entered this process either way) and the seconds of
    every stage (trace, lower, compile, cache load), attributed to the
    function label on the `track_compiles` thread-local stack, so the
    serving engine's compile-once invariant is a live metric;
  * the process journal: a bounded, always-on record of what the
    process did outside its steady state — the compile stages by
    program, the `startup/<phase>` spans of the constructors that do
    start-up work, the collector's pauses — on `time.perf_counter()`,
    read by `process_events` / `process_summary` and served under
    `process` in `/metrics.json`;
  * `trace_request`: chrome-trace async spans + flow events for the
    serving Request lifecycle (QUEUED → PREFILL → DECODE → DONE) emitted
    into `utils.profiler`'s event sink, so one exported trace shows host
    RecordEvents, decode waves, and per-request lifecycles together.

Metric names and label conventions are cataloged in
docs/observability.md; the `metric-name` rule of scripts/ptlint.py
lints call sites against that catalog.
"""
import bisect
import functools
import gc
import http.server
import io
import json
import re
import threading
import time

from . import monitor, profiler

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def _check_name(name):
    if not isinstance(name, str) or not _NAME_RE.match(name):
        raise ValueError(
            f"metric name must be snake_case ([a-z][a-z0-9_]*), got {name!r}")
    return name


def exponential_buckets(start=0.001, factor=2.0, count=16):
    """Exponential bucket upper bounds: start, start*factor, ... — the
    default (1ms..~32.8s) covers TTFT/step-time latencies without keeping
    raw samples."""
    if start <= 0 or factor <= 1 or count < 1:
        raise ValueError("need start > 0, factor > 1, count >= 1")
    return tuple(start * factor ** i for i in range(count))


DEFAULT_LATENCY_BUCKETS = exponential_buckets()


# ---------------------------------------------------------------------------
# metric types
# ---------------------------------------------------------------------------

class _CounterChild:
    __slots__ = ("_lock", "_v")

    def __init__(self, lock):
        self._lock = lock
        self._v = 0.0

    def inc(self, amount=1.0):
        if amount < 0:
            raise ValueError(f"counters only go up, got {amount}")
        with self._lock:
            self._v += amount

    def value(self):
        with self._lock:
            return self._v

    def _reset(self):
        self._v = 0.0           # caller holds the lock


class _GaugeChild:
    __slots__ = ("_lock", "_v")

    def __init__(self, lock):
        self._lock = lock
        self._v = 0.0

    def set(self, value):
        with self._lock:
            self._v = float(value)

    def inc(self, amount=1.0):
        with self._lock:
            self._v += amount

    def dec(self, amount=1.0):
        self.inc(-amount)

    def set_max(self, value):
        """Atomic running max — the peak-gauge idiom monitor.stat_max has."""
        with self._lock:
            self._v = max(self._v, float(value))

    def value(self):
        with self._lock:
            return self._v

    def _reset(self):
        self._v = 0.0


class _HistogramChild:
    __slots__ = ("_lock", "_bounds", "_counts", "_sum", "_count",
                 "_min", "_max")

    def __init__(self, lock, bounds):
        self._lock = lock
        self._bounds = bounds
        self._counts = [0] * (len(bounds) + 1)     # +Inf overflow last
        self._sum = 0.0
        self._count = 0
        self._min = None
        self._max = None

    def observe(self, value):
        v = float(value)
        if v != v or v in (float("inf"), float("-inf")):
            return     # a non-finite sample would poison sum/min/max and
                       # every percentile forever; drop it at the door
        idx = bisect.bisect_left(self._bounds, v)  # le: v == bound stays in
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1
            self._min = v if self._min is None else min(self._min, v)
            self._max = v if self._max is None else max(self._max, v)

    def count(self):
        with self._lock:
            return self._count

    def sum(self):
        with self._lock:
            return self._sum

    def bucket_counts(self):
        """[(upper_bound, cumulative_count), ..., (None, total)] — the
        Prometheus cumulative view; None stands for +Inf."""
        with self._lock:
            counts = list(self._counts)
        out, cum = [], 0
        for ub, c in zip(self._bounds, counts):
            cum += c
            out.append((ub, cum))
        out.append((None, cum + counts[-1]))
        return out

    def percentile(self, q):
        """Estimate the q-th percentile from the buckets (linear
        interpolation within the bucket, clamped to the observed
        [min, max]); None when empty. The whole point of the rebase from
        raw sample lists: O(buckets) memory at any request count."""
        with self._lock:
            counts = list(self._counts)
            total, mn, mx = self._count, self._min, self._max
        if not total:
            return None
        target = (q / 100.0) * total
        cum, lower = 0.0, None
        for i, ub in enumerate(list(self._bounds) + [None]):
            c = counts[i]
            if c and cum + c >= target:
                lo = mn if lower is None else max(lower, mn)
                hi = mx if ub is None else min(ub, mx)
                if hi < lo:
                    hi = lo
                frac = (target - cum) / c
                return min(max(lo + frac * (hi - lo), mn), mx)
            cum += c
            lower = ub
        return mx

    def _reset(self):
        self._counts = [0] * (len(self._bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = None
        self._max = None


class _Metric:
    kind = "untyped"
    _child_args = ()

    def __init__(self, name, help="", labelnames=()):
        _check_name(name)
        self.name = name
        self.help = help
        self.labelnames = tuple(_check_name(n) for n in labelnames)
        # re-entrant: the collector's hook (`_on_gc`) counts into its
        # metrics from whatever bytecode the thread was at, which may
        # be a reader of those metrics holding this lock
        self._lock = threading.RLock()
        self._children = {}

    def _normalize(self, values, kv):
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by "
                                 "name, not both")
            extra = set(kv) - set(self.labelnames)
            if extra:
                raise ValueError(f"{self.name}: unexpected labels {extra}")
            values = tuple(str(kv[n]) for n in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(f"{self.name} takes labels {self.labelnames}, "
                             f"got {values!r}")
        return values

    def labels(self, *values, **kv):
        """Bind label values -> child handle (created on first use)."""
        values = self._normalize(values, kv)
        with self._lock:
            child = self._children.get(values)
            if child is None:
                child = self._new_child()
                self._children[values] = child
        return child

    def peek(self, *values, **kv):
        """Non-creating lookup: the child for these label values, or
        None if that series has never been recorded. Read paths use this
        so a dashboard probe cannot mint permanent zero-valued series."""
        values = self._normalize(values, kv)
        with self._lock:
            return self._children.get(values)

    def _new_child(self):
        raise NotImplementedError

    def _default(self):
        if self.labelnames:
            raise ValueError(f"{self.name} has labels {self.labelnames}; "
                             "use .labels(...)")
        return self.labels()

    def _series(self):
        with self._lock:
            return sorted(self._children.items())

    def _reset(self):
        with self._lock:
            for child in self._children.values():
                child._reset()


class Counter(_Metric):
    kind = "counter"

    def _new_child(self):
        return _CounterChild(self._lock)

    def inc(self, amount=1.0):
        self._default().inc(amount)

    def value(self):
        return self._default().value()


class Gauge(_Metric):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild(self._lock)

    def set(self, value):
        self._default().set(value)

    def inc(self, amount=1.0):
        self._default().inc(amount)

    def dec(self, amount=1.0):
        self._default().dec(amount)

    def set_max(self, value):
        self._default().set_max(value)

    def value(self):
        return self._default().value()


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets=DEFAULT_LATENCY_BUCKETS):
        super().__init__(name, help, labelnames)
        bounds = tuple(float(b) for b in buckets)
        if not bounds or list(bounds) != sorted(set(bounds)):
            raise ValueError(f"buckets must be distinct and increasing, "
                             f"got {buckets!r}")
        self.buckets = bounds

    def _new_child(self):
        return _HistogramChild(self._lock, self.buckets)

    def observe(self, value):
        self._default().observe(value)

    def count(self):
        return self._default().count()

    def sum(self):
        return self._default().sum()

    def percentile(self, q):
        return self._default().percentile(q)

    def bucket_counts(self):
        return self._default().bucket_counts()


# ---------------------------------------------------------------------------
# registry + exporters
# ---------------------------------------------------------------------------

def _fmt(v):
    # non-finite values are legal Prometheus samples (a diverged
    # train_loss gauge is NaN) — render them instead of crashing /metrics
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.10g}"


def _json_safe(v):
    """JSON has no NaN/Inf literal (json.dumps would emit invalid JSON);
    snapshot consumers get the string spelling instead."""
    if v != v or v in (float("inf"), float("-inf")):
        return _fmt(v)
    return v


def _esc_label(v):
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _sample_line(name, labelnames, values, value, suffix="", extra=()):
    pairs = [f'{n}="{_esc_label(v)}"' for n, v in zip(labelnames, values)]
    pairs += [f'{n}="{_esc_label(v)}"' for n, v in extra]
    lbl = "{" + ",".join(pairs) + "}" if pairs else ""
    return f"{name}{suffix}{lbl} {_fmt(value)}"


class Registry:
    """Named metric registry. `counter`/`gauge`/`histogram` get-or-create
    (re-registration with the same kind+labels returns the existing
    metric — modules can declare their metrics at import time without
    ordering hazards); mismatched re-registration raises."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _get_or_create(self, cls, name, help, labelnames, **kw):
        with self._lock:
            cur = self._metrics.get(name)
            if cur is not None:
                if (type(cur) is not cls
                        or cur.labelnames != tuple(labelnames)):
                    raise ValueError(
                        f"metric {name!r} already registered as {cur.kind} "
                        f"with labels {cur.labelnames}")
                want = kw.get("buckets")
                if want is not None and \
                        cur.buckets != tuple(float(b) for b in want):
                    raise ValueError(
                        f"histogram {name!r} already registered with "
                        f"buckets {cur.buckets}, requested {tuple(want)}")
                return cur
            m = cls(name, help, labelnames, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name, help="", labelnames=()):
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=DEFAULT_LATENCY_BUCKETS):
        return self._get_or_create(Histogram, name, help, labelnames,
                                   buckets=buckets)

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def names(self):
        with self._lock:
            return sorted(self._metrics)

    def unregister(self, name):
        with self._lock:
            self._metrics.pop(name, None)

    def reset(self):
        """Zero every series IN PLACE — registrations and any child
        handles modules cached stay live (tests isolate runs with this)."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m._reset()

    # ------------------------------------------------------------- exporters
    def snapshot(self, include_monitor=True):
        """JSON-able point-in-time dump of every metric (and, by default,
        the flat utils.monitor stats alongside)."""
        out = {"time_unix": time.time(), "metrics": {}}
        with self._lock:
            metrics = sorted(self._metrics.items())
        for name, m in metrics:
            series = []
            for values, child in m._series():
                entry = {"labels": dict(zip(m.labelnames, values))}
                if m.kind == "histogram":
                    entry.update(
                        count=child.count(), sum=_json_safe(child.sum()),
                        buckets=[[ub, c]
                                 for ub, c in child.bucket_counts()])
                    p50 = child.percentile(50)
                    if p50 is not None:
                        entry["p50"] = p50
                        entry["p99"] = child.percentile(99)
                else:
                    entry["value"] = _json_safe(child.value())
                series.append(entry)
            out["metrics"][name] = {"kind": m.kind, "help": m.help,
                                    "labelnames": list(m.labelnames),
                                    "series": series}
        if include_monitor:
            out["monitor"] = monitor.all_stats()
        return out

    def render_prometheus(self, include_monitor=True):
        """Prometheus text exposition (format 0.0.4). Histograms render
        cumulative `_bucket{le=...}` + `_sum` + `_count`; the flat
        monitor stats ride along as untyped samples (names sanitized,
        typed metrics win collisions)."""
        lines = []
        with self._lock:
            metrics = sorted(self._metrics.items())
        for name, m in metrics:
            if m.help:
                lines.append(f"# HELP {name} "
                             + m.help.replace("\\", "\\\\")
                                     .replace("\n", "\\n"))
            lines.append(f"# TYPE {name} {m.kind}")
            for values, child in m._series():
                if m.kind == "histogram":
                    for ub, cum in child.bucket_counts():
                        le = "+Inf" if ub is None else _fmt(ub)
                        lines.append(_sample_line(
                            name, m.labelnames, values, cum,
                            suffix="_bucket", extra=(("le", le),)))
                    lines.append(_sample_line(name, m.labelnames, values,
                                              child.sum(), suffix="_sum"))
                    lines.append(_sample_line(name, m.labelnames, values,
                                              child.count(),
                                              suffix="_count"))
                else:
                    lines.append(_sample_line(name, m.labelnames, values,
                                              child.value()))
        if include_monitor:
            taken = {n for n, _ in metrics}
            for key, v in sorted(monitor.all_stats().items()):
                name = re.sub(r"[^a-z0-9_]", "_", str(key).lower())
                if not _NAME_RE.match(name) or name in taken:
                    continue
                taken.add(name)
                lines.append(f"# TYPE {name} untyped")
                lines.append(f"{name} {_fmt(float(v))}")
        return "\n".join(lines) + "\n"


REGISTRY = Registry()


def counter(name, help="", labelnames=()):
    return REGISTRY.counter(name, help, labelnames)


def gauge(name, help="", labelnames=()):
    return REGISTRY.gauge(name, help, labelnames)


def histogram(name, help="", labelnames=(), buckets=DEFAULT_LATENCY_BUCKETS):
    return REGISTRY.histogram(name, help, labelnames, buckets=buckets)


def snapshot(include_monitor=True):
    return REGISTRY.snapshot(include_monitor)


def render_prometheus(include_monitor=True):
    return REGISTRY.render_prometheus(include_monitor)


def snapshot_history():
    """The utils/timeseries history payload of the process-wide sampler
    (what /metrics/history serves); an empty payload before any sampler
    is installed."""
    from . import timeseries
    s = timeseries.get_sampler()
    return s.history() if s is not None else timeseries.empty_history()


def value(name, labels=None, default=None):
    """Read one sample from the default registry: counter/gauge value, or
    histogram observation count. `default` when the metric or the label
    series is missing — reading never creates a series."""
    m = REGISTRY.get(name)
    if m is None:
        return default
    child = m.peek(**(labels or {}))
    if child is None:
        return default
    return child.count() if m.kind == "histogram" else child.value()


# ---------------------------------------------------------------------------
# the process journal
# ---------------------------------------------------------------------------

#: what an entry can be: a compile stage of a program (`trace`, `lower`,
#: `compile`, `cache_load`), a `startup/<phase>` span, a pause of the
#: collector (`gc`), or one of the two counts (seconds 0)
PROCESS_KINDS = ("trace", "lower", "compile", "cache_load", "startup", "gc",
                 "cache_hit", "cache_miss")
JOURNAL_MAX = 4096
_STAGES = PROCESS_KINDS[:4]

_tl = threading.local()               # label stack, pending cache events,
                                      # the thread's last `startup` row
_journal_lock = threading.RLock()     # re-entrant: `_on_gc` may interrupt
_journal = []                         # oldest first; rows end with a thread id
_journal_state = {"dropped": 0}

_STARTUP_SECONDS = counter(
    "startup_seconds_total",
    "Self seconds of the process's start-up phases (startup/<phase> spans)",
    labelnames=("phase",))


def _inside(start):
    # index of the first row that ended after `start`; caller holds the lock
    i = len(_journal)
    while i and _journal[i - 1][0] > start:
        i -= 1
    return i


def record_process_event(kind, label, seconds=0.0, t_end=None):
    """Add `(t_end, kind, label, seconds)` to the process journal and
    return the entry's own seconds (what a registry counter its children
    counted into before it gets). `t_end` is on `time.perf_counter()` (now if
    not given). For an entry that spans time, `seconds` is what the caller
    measured from its start to `t_end`; what is kept is its SELF time:
    less the seconds of every entry this thread journaled inside that
    interval (a child span, a compile stage, a pause of the collector).
    So the journal's seconds add up to wall time at most, and a sum by
    kind counts nothing twice. Two rules keep a model's start-up from
    filling the journal. A compile stage takes in the stages that ended
    inside it (the helpers a program's trace traces on its way, the
    kernels its lowering traces): they leave the journal and their
    seconds stay the program's (its entry holds them, the value returned
    does not). And a `startup` entry whose thread's last
    `startup` entry has the same label is added to that one (a
    constructor's parameters, one initialiser after another, are one
    entry; the replicas of a fleet are not, a `pool` lies between their
    `engine`s)."""
    t_end = time.perf_counter() if t_end is None else t_end
    label, tid = str(label), threading.get_ident()
    with _journal_lock:
        own = seconds
        if seconds > 0.0:
            i = _inside(t_end - seconds)
            inside = [row for row in _journal[i:] if row[4] == tid]
            own = max(0.0, seconds - sum(row[3] for row in inside))
            if kind in _STAGES and inside:
                inside = [row for row in inside if row[1] not in _STAGES]
                _journal[i:] = [row for row in _journal[i:] if row[4] != tid
                                or row[1] not in _STAGES]
                seconds = max(0.0, seconds - sum(row[3] for row in inside))
            else:
                seconds = own
        if kind == "startup":
            last = getattr(_tl, "startup_row", None)
            if last is not None and last[2] == label:
                last[0], last[3] = t_end, last[3] + seconds
                return own
        row = [t_end, kind, label, seconds, tid]
        if kind == "startup":
            _tl.startup_row = row
        _journal.append(row)
        if len(_journal) > JOURNAL_MAX and not _compact_tail(row):
            del _journal[0]
            _journal_state["dropped"] += 1
    return own


def _compact_tail(row):
    """Room in a full journal without dropping its oldest entry: a large
    program's trace journals thousands of stages under its label before
    the enclosing one arrives and takes them in, so this thread's run of
    such rows at the tail is summed into `row` (the enclosing stage's
    seconds cover them all the same). True if that made room."""
    kind, label, tid = row[1], row[2], row[4]
    if kind not in _STAGES:
        return False
    with _journal_lock:
        i = len(_journal) - 1
        while i and (_journal[i - 1][4] != tid or (
                _journal[i - 1][1] in _STAGES
                and _journal[i - 1][2] == label)):
            i -= 1
        run = [r for r in _journal[i:-1] if r[4] == tid]
        if not run:
            return False
        row[3] += sum(r[3] for r in run)
        _journal[i:] = [r for r in _journal[i:-1] if r[4] != tid] + [row]
    return True


def process_events(since=None, until=None):
    """The journal's entries `(t_end, kind, label, seconds)` with
    `since <= t_end <= until` (either may be None), oldest first.
    `seconds` is self time (`record_process_event`)."""
    with _journal_lock:
        rows = list(_journal)
    return [tuple(row[:4]) for row in rows
            if (since is None or row[0] >= since)
            and (until is None or row[0] <= until)]


def process_summary(until=None):
    """The journal summed: `{"entries", "dropped", "kinds": {kind:
    {"seconds", "count", "labels": {label: {"seconds", "count"}}}}}` over
    the entries that ended by `until`. Seconds are self time, so a span
    nested in another is counted once, under its own label. An operator
    reads a replica's time to ready by phase and its cache state here
    (`/metrics.json`, key `process`); a benchmark cuts at its window's
    start."""
    kinds = {}
    events = process_events(until=until)
    for _, kind, label, seconds in events:
        k = kinds.setdefault(kind, {"seconds": 0.0, "count": 0, "labels": {}})
        by = k["labels"].setdefault(label, {"seconds": 0.0, "count": 0})
        for acc in (k, by):
            acc["seconds"] += seconds
            acc["count"] += 1
    with _journal_lock:
        dropped = _journal_state["dropped"]
    return {"entries": len(events), "dropped": dropped, "kinds": kinds}


def clear_process_journal():
    """Empty the journal and its drop count (tests isolate themselves
    with this, as with `Registry.reset`)."""
    with _journal_lock:
        _journal.clear()
        _journal_state["dropped"] = 0
    _tl.startup_row = None


class startup_span(profiler.RecordEvent):
    """`RecordEvent("startup/<phase>", **ids)` whose self time is also
    journaled (kind `startup`, label `<phase>`) and counted in
    `startup_seconds_total{phase=}`: the constructors that do start-up
    work wrap it in one. Context manager or decorator."""

    __slots__ = ("phase",)

    def __init__(self, phase, **ids):
        super().__init__("startup/" + phase, **ids)
        self.phase = phase

    def __exit__(self, *exc):
        super().__exit__(*exc)
        if self.elapsed is not None:
            _STARTUP_SECONDS.labels(self.phase).inc(record_process_event(
                "startup", self.phase, self.elapsed, t_end=self.end))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with startup_span(self.phase, **self.ids):
                return fn(*a, **k)
        return wrapped


# ---------------------------------------------------------------------------
# XLA compile-event tracking
# ---------------------------------------------------------------------------

XLA_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
XLA_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
XLA_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
XLA_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
XLA_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_XLA_COMPILES = counter(
    "xla_compiles_total",
    "XLA backend compilations per attributed function (persistent-cache "
    "loads count too: a new executable entered the process either way)",
    labelnames=("function",))
_XLA_COMPILE_SECONDS = histogram(
    "xla_compile_seconds", "XLA backend compile/cache-load durations",
    buckets=exponential_buckets(0.01, 2.0, 12))
_XLA_CACHE_HITS = counter(
    "xla_persistent_cache_hits_total",
    "Compiled executables loaded from the persistent compilation cache")
_XLA_CACHE_MISSES = counter(
    "xla_persistent_cache_misses_total",
    "Executables compiled and written to the persistent compilation "
    "cache, per attributed function", labelnames=("function",))
_XLA_STAGE_SECONDS = counter(
    "xla_stage_seconds_total",
    "Self seconds of bringing programs into the process, by stage "
    "(trace, lower, compile, cache_load) and attributed function",
    labelnames=("stage", "function"))

_install_lock = threading.Lock()
_install_state = {"installed": False}
_known_labels = set()


def _tl_list(name):
    got = getattr(_tl, name, None)
    if got is None:
        got = []
        setattr(_tl, name, got)
    return got


def _compile_label(metadata_name=None):
    stack = getattr(_tl, "stack", None)
    if stack:
        return stack[-1]
    # a trace names its function `f`, its lowering and compile `jit(f)`
    name = metadata_name or "unattributed"
    return name[4:-1] if name.startswith("jit(") and name[-1] == ")" \
        else name


def _stage(kind, label, seconds, t_end=None):
    _XLA_STAGE_SECONDS.labels(kind, label).inc(
        record_process_event(kind, label, seconds, t_end))


def _on_duration(event, duration, **kw):
    """Every stage of bringing a program into the process, by program.

    A request for an executable is one `backend_compile_duration` event
    whether XLA compiled it or the persistent cache held it, so the two
    are told apart by what JAX reports from inside it, on the same
    thread, before it ends: a load reports `cache_hits`, a compile whose
    result is written reports `cache_misses`, a compile the cache was
    never asked for reports neither. Those inner events carry no name, so
    they wait (`_tl.pending`) for the enclosing event's `fun_name`. With
    a hit pending the request was a load, and all of it (the cache key,
    then what `cache_retrieval_time_sec` times) is journaled
    `cache_load` and nothing as `compile`; otherwise it is a `compile`."""
    if event == XLA_TRACE_EVENT:
        _stage("trace", _compile_label(kw.get("fun_name")), duration)
    elif event == XLA_LOWER_EVENT:
        _stage("lower", _compile_label(kw.get("fun_name")), duration)
    elif event == XLA_BACKEND_COMPILE_EVENT:
        label = _compile_label(kw.get("fun_name"))
        _XLA_COMPILES.labels(label).inc()
        _XLA_COMPILE_SECONDS.observe(duration)
        pending, _tl.pending = _tl_list("pending"), []
        for t_end, kind in pending:
            record_process_event(kind, label, t_end=t_end)
            if kind == "cache_miss":
                _XLA_CACHE_MISSES.labels(label).inc()
        hit = any(kind == "cache_hit" for _, kind in pending)
        _stage("cache_load" if hit else "compile", label, duration)


def _on_event(event, **kw):
    if event == XLA_CACHE_HIT_EVENT:
        _XLA_CACHE_HITS.inc()
        _tl_list("pending").append((time.perf_counter(), "cache_hit"))
    elif event == XLA_CACHE_MISS_EVENT:
        _tl_list("pending").append((time.perf_counter(), "cache_miss"))


def install_compile_tracking():
    """Register the jax.monitoring listeners, once a process
    (idempotent). `utils.compile_cache.enable()`, the
    serving front door and the train steps' constructors call it, so a
    process's first program is heard."""
    if not _install_state["installed"]:
        with _install_lock:
            if not _install_state["installed"]:
                import jax.monitoring as jmon
                jmon.register_event_duration_secs_listener(_on_duration)
                jmon.register_event_listener(_on_event)
                _install_state["installed"] = True


def _register_label(label):
    _check_name(label)
    install_compile_tracking()
    with _install_lock:
        _known_labels.add(label)
    return label


class track_compiles:
    """Attribute every XLA compile-stage event fired inside the block
    (from this thread) to `label`: `xla_compiles_total{function=label}`,
    `xla_stage_seconds_total{function=label}` and the journal's label.
    A label is checked, and the listener installed, the first time the
    label is seen; a later call pushes and pops a list."""

    __slots__ = ("label",)

    def __init__(self, label):
        if label not in _known_labels:
            _register_label(label)
        self.label = label

    def __enter__(self):
        _tl_list("stack").append(self.label)
        return self

    def __exit__(self, *exc):
        _tl.stack.pop()
        return False


class _InstrumentedJit:
    """Proxy over a jitted callable: calls run under its label as under
    `track_compiles(label)`. Attribute access (lower, _cache_size, ...)
    passes through."""

    def __init__(self, fn, label):
        self._fn = fn
        self.label = _register_label(label)

    def __call__(self, *args, **kw):
        stack = _tl_list("stack")
        stack.append(self.label)
        try:
            return self._fn(*args, **kw)
        finally:
            stack.pop()

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"instrument_jit({self._fn!r}, label={self.label!r})"


def instrument_jit(fn, label):
    """Wrap a jax.jit callable so its compilations show up as
    xla_compiles_total{function=label} (the serving engine labels its
    decode wave / prefill programs this way)."""
    return _InstrumentedJit(fn, label)


def compile_count(function):
    """Live compile count for an attributed function label."""
    return int(value("xla_compiles_total", {"function": function}, 0) or 0)


# ---------------------------------------------------------------------------
# the collector's pauses
# ---------------------------------------------------------------------------

GC_JOURNAL_MIN_S = 1e-3

_GC_PAUSE = counter(
    "gc_pause_seconds_total",
    "Seconds the interpreter's collector held the process")
_GC_COLLECTIONS = counter(
    "gc_collections_total", "Collections of the interpreter's collector",
    labelnames=("generation",))


class _GcWatch:
    """The one hook's state. Attributes, written by the hook alone: the
    collector does not run inside itself, so nothing needs a lock."""
    family = None       # span family of the pauses: `serving` or `train`
    span = None         # the open `<family>/gc` span, between the phases


_gc_watch = _GcWatch()


def _on_gc(phase, info):
    watch = _gc_watch
    if phase == "start":
        watch.span = profiler.RecordEvent(
            watch.family + "/gc", generation=info["generation"]).__enter__()
        return
    span, watch.span = watch.span, None
    if span is None:            # installed while a collection ran
        return
    span.__exit__(None, None, None)
    _GC_PAUSE.inc(span.elapsed)
    _GC_COLLECTIONS.labels(info["generation"]).inc()
    if span.elapsed >= GC_JOURNAL_MIN_S:
        record_process_event("gc", info["generation"], span.elapsed,
                             t_end=span.end)


def install_gc_tracking(family):
    """Hook `gc.callbacks`, once a process (idempotent): every collection
    is a `<family>/gc` span (ids: `generation`) and counts into
    `gc_pause_seconds_total` and `gc_collections_total{generation=}`; a
    pause of a millisecond or more is journaled (kind `gc`, label the
    generation). `family` is `serving` (`Scheduler`) or `train` (the
    train steps); the last caller's names the spans. Measures only: no
    threshold, freeze or disable."""
    with _install_lock:
        _gc_watch.family = family
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def gc_totals():
    """The process's collector totals, the three numbers
    `ServingMetrics.snapshot()` hands on."""
    by_generation = [int(value("gc_collections_total", {"generation": g}, 0))
                     for g in range(3)]
    return {"gc_pause_seconds": _GC_PAUSE.value(),
            "gc_collections": sum(by_generation),
            "gc_gen2_collections": by_generation[2]}


# ---------------------------------------------------------------------------
# request-correlated tracing (chrome async spans + flow events)
# ---------------------------------------------------------------------------

_SPAN_STATES = ("QUEUED", "PREFILL", "DECODE")


def trace_request(request, state, reason=None):
    """Emit the chrome-trace events for one Request lifecycle transition:
    close the previous async span, open the new one (QUEUED/PREFILL/
    DECODE), and add a flow event (`s` at QUEUED, `t` in between, `f` at
    DONE/REJECTED) binding the request's arrow across the timeline. All
    events share id=trace_id and cat "serving.request"; no-op unless the
    host profiler is recording."""
    if not profiler.trace_enabled():
        return
    gen = profiler.trace_generation()
    if getattr(request, "_trace_gen", None) != gen:
        # first emission into a NEW trace buffer: any open span / flow
        # start this request remembers died with the old buffer — reset
        # so we never emit an 'e'/'t'/'f' whose partner is gone
        request._trace_span = None
        request._trace_started = False
        request._trace_gen = gen
    rid = int(getattr(request, "trace_id", 0)
              or getattr(request, "request_id", 0))
    # pid 0 = single-engine/host; fleet replicas stamp their requests
    # with trace_pid = replica_id + 1 so one merged trace shows each
    # replica's lifecycle spans on its own process row
    base = {"cat": "serving.request", "id": rid,
            "pid": int(getattr(request, "trace_pid", 0)),
            "tid": threading.get_ident() % 10000, "ts": profiler.now_us()}
    open_span = getattr(request, "_trace_span", None)
    if open_span is not None and open_span != state:
        profiler.emit_trace_event({**base, "ph": "e", "name": open_span})
    if state in _SPAN_STATES:
        profiler.emit_trace_event({**base, "ph": "b", "name": state})
        request._trace_span = state
    else:
        request._trace_span = None
    ph = "s" if state == "QUEUED" else (
        "f" if state in ("DONE", "REJECTED") else "t")
    if ph != "s" and not getattr(request, "_trace_started", False):
        return    # e.g. rejected before admission: no dangling flow-finish
    request._trace_started = ph != "f"
    flow = {**base, "ph": ph, "name": "request",
            "args": {"state": state, "request_id": rid}}
    if ph == "f":
        flow["bp"] = "e"
    if reason:
        flow["args"]["finish_reason"] = reason
    profiler.emit_trace_event(flow)


def trace_flow_step(trace_id, state, pid=0, **args):
    """Mid-flow chrome step ('t') for a fleet-level transition the
    replica-local Request lifecycle cannot see: DISPATCH (the router
    handed the request to a replica) and MIGRATE (a dead replica's hop
    was resubmitted elsewhere). Shares cat/id/name with trace_request's
    flow events, so the request's arrow runs QUEUED → DISPATCH →
    PREFILL → DECODE → (MIGRATE → next replica's spans) → DONE across
    process rows in one merged trace. No-op unless recording."""
    if not profiler.trace_enabled():
        return
    profiler.emit_trace_event({
        "cat": "serving.request", "id": int(trace_id), "ph": "t",
        "name": "request", "pid": int(pid),
        "args": {"state": str(state), **args}})


def trace_instant(trace_id, name, pid=0, **args):
    """Request-correlated chrome instant event ('i', thread-scoped) —
    the paged engine marks each PREFILL_CHUNK[i] it runs this way, so a
    chunked admission's progress is visible inside the PREFILL span.
    No-op unless recording."""
    if not profiler.trace_enabled():
        return
    profiler.emit_trace_event({
        "cat": "serving.request", "id": int(trace_id), "ph": "i",
        "s": "t", "name": str(name), "pid": int(pid),
        "args": dict(args) if args else {}})


# ---------------------------------------------------------------------------
# /metrics exporter (stdlib http.server, background thread)
# ---------------------------------------------------------------------------

_debug_requests_provider = None


def set_debug_requests_provider(fn):
    """Install the `/debug/requests` payload provider. The serving
    black-box recorder (serving/blackbox.py) registers itself at import
    time — utils must not import serving, so the endpoint reaches the
    journal through this hook. `fn` takes no arguments and returns a
    JSON-safe dict; None detaches (the endpoint then serves an empty
    trace list)."""
    global _debug_requests_provider
    with _install_lock:
        _debug_requests_provider = fn


def _debug_requests_body():
    fn = _debug_requests_provider
    if fn is None:
        return {"recording": False, "requests": []}
    try:
        return fn()
    except Exception as e:   # noqa: BLE001 - report, not die
        return {"recording": False, "requests": [], "error": repr(e)}


def make_metrics_handler(registry=None, health_fn=None, sampler=None):
    reg = registry or REGISTRY

    def _history():
        # the handler-bound sampler wins; otherwise the process-wide
        # install (utils/timeseries) is resolved per request, so a
        # server started before the sampler still serves its history
        from . import timeseries
        s = sampler or timeseries.get_sampler()
        return s.history() if s is not None else timeseries.empty_history()

    class Handler(http.server.BaseHTTPRequestHandler):
        server_version = "paddle-tpu-telemetry/1.0"

        def do_GET(self):
            path = self.path.split("?", 1)[0]
            if path == "/metrics":
                body = reg.render_prometheus().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
                code = 200
            elif path == "/metrics.json":
                body = json.dumps({**reg.snapshot(),
                                   "process": process_summary()}).encode()
                ctype = "application/json"
                code = 200
            elif path == "/metrics/history":
                # sorted keys + no timestamps anywhere in the payload:
                # identical sampled values serve identical BYTES
                # (tests pin this determinism)
                body = json.dumps(_history(), sort_keys=True).encode()
                ctype = "application/json"
                code = 200
            elif path == "/dashboard":
                from . import timeseries
                body = timeseries.render_dashboard(_history()).encode()
                ctype = "text/html; charset=utf-8"
                code = 200
            elif path == "/debug/requests":
                # sorted keys, timestamp-free payload — same bytes
                # discipline as /metrics/history
                body = json.dumps(_debug_requests_body(),
                                  sort_keys=True).encode()
                ctype = "application/json"
                code = 200
            elif path == "/healthz":
                payload = {"status": "ok", "time_unix": time.time()}
                if health_fn is not None:
                    try:
                        payload.update(health_fn() or {})
                    except Exception as e:   # noqa: BLE001 - report, not die
                        payload["status"] = "degraded"
                        payload["error"] = repr(e)
                body = json.dumps(payload).encode()
                ctype = "application/json"
                # status-code-probing load balancers (the k8s httpGet
                # default) never parse the body — a degraded/draining
                # engine must fail the probe, not answer 200 with a
                # sad JSON inside
                code = 200 if payload.get("status") == "ok" else 503
            else:
                body = (b"not found; try /metrics /metrics.json "
                        b"/metrics/history /dashboard /debug/requests "
                        b"/healthz\n")
                ctype = "text/plain"
                code = 404
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):      # keep the serving loop's stdout
            pass

    return Handler


def http_get_inline(path="/metrics", registry=None, health_fn=None,
                    sampler=None):
    """Drive the metrics handler fully in-process (no socket): returns
    (status_code, headers_dict, body_bytes). Tests exercise the exporter
    exactly as an HTTP client would, without binding a port."""

    class _FakeSocket:
        """socketserver writes either via makefile('wb') or, for the
        unbuffered default, via sendall() — capture both into one
        buffer that survives close()."""

        def __init__(self):
            self._rd = io.BytesIO(f"GET {path} HTTP/1.0\r\n\r\n".encode())
            self.out = bytearray()
            outer = self

            class _Wr(io.RawIOBase):
                def writable(self):
                    return True

                def write(self, data):
                    outer.out += bytes(data)
                    return len(data)

            self._wr = io.BufferedWriter(_Wr())

        def makefile(self, mode, *a, **kw):
            return self._rd if "r" in mode else self._wr

        def sendall(self, data):
            self.out += bytes(data)

    sock = _FakeSocket()
    make_metrics_handler(registry, health_fn,
                         sampler=sampler)(sock, ("127.0.0.1", 0), None)
    raw = bytes(sock.out)
    head, _, body = raw.partition(b"\r\n\r\n")
    head_lines = head.decode("latin-1").split("\r\n")
    status = int(head_lines[0].split()[1])
    headers = {}
    for ln in head_lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, body


class MetricsServer:
    """Background /metrics exporter over stdlib http.server.

        srv = MetricsServer(port=9100).start()   # port=0 picks a free one
        ... srv.url, srv.port ...
        srv.stop()

    health_fn (optional) returns extra key/values merged into the
    /healthz payload (the serving engine reports slot state there)."""

    def __init__(self, registry=None, host="127.0.0.1", port=0,
                 health_fn=None, sampler=None):
        self.registry = registry or REGISTRY
        self.host = host
        self.port = int(port)
        self.health_fn = health_fn
        self.sampler = sampler
        self._httpd = None
        self._thread = None

    def start(self):
        if self._httpd is not None:
            return self
        handler = make_metrics_handler(self.registry, self.health_fn,
                                       sampler=self.sampler)
        self._httpd = http.server.ThreadingHTTPServer(
            (self.host, self.port), handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="pt-metrics-exporter",
            daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
            self._thread = None

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False
