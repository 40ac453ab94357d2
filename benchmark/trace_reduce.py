"""From a profiler trace (.xplane.pb) to numbers: device busy time, time
by kernel and by jitted program, collective time that no compute hides,
and the idle gaps named by what the host was doing.

What a TPU trace holds (read off a v5e trace by hand, tests/data):
  plane "/device:TPU:<n>", lines
    "XLA Modules"   one event per execution of a jitted program, named
                    "jit_<function>(<fingerprint>)"
    "XLA Ops"       one event per executed HLO instruction, named by the
                    instruction's whole text: "%fusion.3 = bf16[..] fusion(..)"
    "Async XLA Ops" the start-to-done span of asynchronous instructions
  plane "/host:CPU", one line per thread; `jax.profiler.TraceAnnotation`
    spans appear there under their own names. The benchmark's spans start
    with "bench/", the program's with "serving/", "train" or
    "collective/" (SPAN_PREFIXES); the runtime's own host events are not
    kept.
Host and device share one clock, aligned to about a millisecond (in the
recorded trace a program starts 0.65 ms before the host span that
launched it); gaps are attributed, not timed, by the host spans.

Times are nanoseconds on the trace's clock until `summarize` returns
seconds.
"""
import bisect
import dataclasses
import functools
import heapq
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIXES = ("bench/", "serving/", "train", "collective/")
WINDOW_SPAN = "bench/window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
# Instructions whose event encloses the events of their bodies, which sit
# on the same line: counted, they would count their bodies twice, and as
# "something else running" they would hide a collective issued inside a
# loop. (In the v5e trace of gpt2s-train a `while` of 70.5 ms holds 533
# events whose union is 70.5 ms.)
CONTAINERS = ("while", "conditional", "call")
_FLOATS = ("bf16", "f16", "f32")
_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<shape>.*?) (?P<opcode>[a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class DeviceLines:
    ops: list
    modules: list
    async_ops: list


@dataclasses.dataclass
class Trace:
    devices: dict          # chip number -> DeviceLines
    spans: list            # host events of the SPAN_PREFIXES families


def load(path):
    """Read an .xplane.pb with nothing but JAX."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {"XLA Ops": [], "XLA Modules": [], "Async XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
            devices[int(m.group(1))] = DeviceLines(
                lines["XLA Ops"], lines["XLA Modules"],
                lines["Async XLA Ops"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(devices, sorted(spans, key=lambda e: e.start))


# ------------------------------------------------------------- intervals
def clip(events, t0, t1):
    """[(start, end)] of the parts of `events` inside [t0, t1]."""
    out = []
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover):
    """The parts of merged `intervals` that merged `cover` leaves bare."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ----------------------------------------------------------------- naming
@functools.lru_cache(maxsize=None)
def parse_hlo(text):
    """(instruction name, opcode) of an "XLA Ops" event; the name without
    its ".<n>" suffix, so that instances of one fusion kind add up."""
    m = _HLO.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), ""
    return re.sub(r"\.\d+$", "", m.group("name")), m.group("opcode")


@functools.lru_cache(maxsize=None)
def kernel_class(text):
    """Which of the program's Pallas kernels an "XLA Ops" event is, or
    None. The program gives its kernels no names (the instruction is
    called after whatever scope traced it: "%jvp__.1", "%probe_scope.1"),
    so they are told apart by what only they take:
      paged attention   scalar-prefetch operands first: an s32 block table
      flash forward     (q, k, v) of one float type -> (o, f32 lse)
      flash backward    (q, k, v, do, f32 lse, f32 delta) -> (dk, dv), or
                        -> dq alone
    Any other Pallas call is "pallas_other": a new kernel is not taken
    for one of these because it has as many operands.
    """
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    m = _HLO.match(text)
    if not m:
        return "pallas_other"
    args = text[m.end():text.index("), custom_call_target")]
    ins = re.findall(r"([a-z0-9]+)\[[0-9,]*\][^%]*%", args)
    outs = re.findall(r"([a-z0-9]+)\[[0-9,]*\]", m.group("shape"))
    if ins and ins[0] == "s32":
        return "paged_attention"
    x = ins[0] if ins and ins[0] in _FLOATS else None
    if x and ins == [x] * 3 and outs == [x, "f32"]:
        return "flash_fwd"
    if x and ins == [x] * 4 + ["f32", "f32"]:
        if outs == [x, x]:
            return "flash_bwd_dkv"
        if outs == [x]:
            return "flash_bwd_dq"
    return "pallas_other"


def is_collective(opcode):
    return any(opcode == c or opcode == c + "-start" or opcode == c + "-done"
               for c in COLLECTIVES)


def module_name(text):
    """"jit_decode_wave(123)" -> "decode_wave"."""
    name = text.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


# -------------------------------------------------------------- reduction
def window_of(trace):
    """The traced window: the benchmark's "bench/window" span if it is
    there, else first to last device event."""
    for s in trace.spans:
        if s.name == WINDOW_SPAN:
            return s.start, s.end
    evs = [e for d in trace.devices.values() for e in d.ops + d.modules]
    if not evs:
        return 0.0, 0.0
    return min(e.start for e in evs), max(e.end for e in evs)


def idle_by_span(busy_iv, spans, t0, t1):
    """{host span name: ns of device idleness inside it}. Each instant of
    the window belongs to the innermost (shortest) span that covers it;
    what no span covers is "unattributed". One sweep over the spans'
    ends: a serving trace holds thousands of the program's spans."""
    clipped = [(max(s.start, t0), min(s.end, t1), s.dur, i, s.name)
               for i, s in enumerate(spans) if s.name != WINDOW_SPAN]
    clipped = sorted(c for c in clipped if c[1] > c[0])
    cuts = sorted({t0, t1} | {c[0] for c in clipped}
                  | {c[1] for c in clipped}) if t1 > t0 else []
    mine, active, k = {}, [], 0        # active: heap of (dur, i, end, name)
    for a, b in zip(cuts, cuts[1:]):
        while k < len(clipped) and clipped[k][0] <= a:
            _, end, dur, i, name = clipped[k]
            heapq.heappush(active, (dur, i, end, name))
            k += 1
        while active and active[0][2] <= a:
            heapq.heappop(active)
        mine.setdefault(active[0][3] if active else "unattributed",
                        []).append((a, b))
    out = {name: length(subtract(union(iv), busy_iv))
           for name, iv in mine.items()}
    return {k: v for k, v in out.items() if v > 0}


def summarize(trace, top=10):
    """Everything the readers ask of a trace, averaged over the chips
    that ran anything; seconds.

    busy_s            union of "XLA Ops" intervals inside the window
    window_s          length of the window
    op_s              {instruction or kernel class: seconds}; loops and
                      calls are left out, their bodies are counted
    kernel_s          {kernel class: seconds}
    kernel_by_module  {program: {kernel class: seconds}}
    module_s          {program: [seconds of each execution]} (chip 0)
    collective_s      union of collective spans (sync and async)
    exposed_collective_s   the part of it under which no other
                      instruction (a loop's body, not the loop) ran on
                      that chip
    idle_by_span      {host span: seconds of device idleness inside it}
    """
    t0, t1 = window_of(trace)
    chips = [d for d in trace.devices.values() if d.ops or d.modules]
    n = max(1, len(chips))
    busy = exposed = coll = 0.0
    op_s, kernel_s, kernel_by_module, idle = {}, {}, {}, {}
    for d in chips:
        busy_iv = union(clip(d.ops, t0, t1))
        busy += length(busy_iv)
        mods = sorted(d.modules, key=lambda e: e.start)
        mod_starts = [m.start for m in mods]
        coll_ev, other_ev = [], []
        for e in d.ops:
            a, b = max(e.start, t0), min(e.end, t1)
            if b <= a:
                continue
            name, opcode = parse_hlo(e.name)
            if opcode in CONTAINERS:
                continue
            kc = kernel_class(e.name)
            key = kc or name
            op_s[key] = op_s.get(key, 0.0) + (b - a)
            if kc:
                kernel_s[kc] = kernel_s.get(kc, 0.0) + (b - a)
                i = bisect.bisect_right(mod_starts, e.start) - 1
                mod = (module_name(mods[i].name)
                       if i >= 0 and e.start < mods[i].end else "?")
                km = kernel_by_module.setdefault(mod, {})
                km[kc] = km.get(kc, 0.0) + (b - a)
            (coll_ev if is_collective(opcode) else other_ev).append(e)
        coll_ev += [e for e in d.async_ops
                    if is_collective(parse_hlo(e.name)[1])]
        coll_iv = union(clip(coll_ev, t0, t1))
        coll += length(coll_iv)
        exposed += length(subtract(coll_iv, union(clip(other_ev, t0, t1))))
        for k, v in idle_by_span(busy_iv, trace.spans, t0, t1).items():
            idle[k] = idle.get(k, 0.0) + v
    module_s = {}
    if chips:
        for m in chips[0].modules:
            if t0 <= m.start and m.end <= t1:
                module_s.setdefault(module_name(m.name), []).append(
                    m.dur * 1e-9)
    ns = 1e-9 / n

    def ranked(d):
        return sorted(([k, v * ns] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {
        "chips": len(chips),
        "busy_s": busy * ns,
        "window_s": (t1 - t0) * 1e-9,
        "op_s": {k: v * ns for k, v in op_s.items()},
        "kernel_s": {k: v * ns for k, v in kernel_s.items()},
        "kernel_by_module": {m: {k: v * ns for k, v in d.items()}
                             for m, d in kernel_by_module.items()},
        "module_s": module_s,
        "collective_s": coll * ns,
        "exposed_collective_s": exposed * ns,
        "idle_by_span": {k: v * ns for k, v in idle.items()},
        "breakdown": {"device_ops": ranked(op_s), "idle_gaps": ranked(idle)},
    }
