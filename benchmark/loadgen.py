"""The one traffic generator: a data file of parameters in, a schedule of
requests out, and the two loops that offer it (open: on a clock, whether
or not earlier requests have finished; closed: each client sends its next
request when its last one completes).

The draws are stratified: every block of `block` requests holds each
1/block quantile of its distribution once, in shuffled order, so a
schedule offers about the same amount of work block after block.

Two things are drawn, and a traffic file says where each comes from:

  * the PATTERN (arrival gaps, prompt and output lengths, which requests
    share which system prompt, and their order) comes from the run's
    `--seed`, unless the file pins it with a `shape_seed` of its own;
  * the CONTENT (token ids of prompts and system prompts) always comes
    from `--seed`, as do the weights.

A cell pins its pattern when a run holds too few requests for its judged
metric to repeat across patterns: a 90th percentile over 42 requests is
set by which four long prompts met which burst (PERF.md section 2 has the
spread across patterns that was measured and simulated). Such a cell
replays one set of requests, and a claim is shown on another pattern by
a second traffic file with another `shape_seed`: data only.

Times are `time.perf_counter()` seconds throughout.
"""
import collections
import dataclasses
import math
import statistics
import threading
import time

import numpy as np


# ---------------------------------------------------------------- drawing
def _stratified_uniforms(n, block, rng):
    """n numbers in (0, 1): each run of `block` holds one jittered draw
    from every 1/block stratum, in shuffled order."""
    out = []
    while len(out) < n:
        u = (np.arange(block) + rng.uniform(0.05, 0.95, block)) / block
        rng.shuffle(u)
        out.extend(u.tolist())
    return np.asarray(out[:n])


def _quantile(spec, u):
    """Inverse CDF of the distribution a traffic file names."""
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in u])
        return float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    if dist == "exponential":
        return -np.log1p(-u) * float(spec["mean"])
    raise ValueError(f"unknown distribution {dist!r}")


def draw_lengths(spec, n, block, rng):
    x = _quantile(spec, _stratified_uniforms(n, block, rng))
    lo, hi = spec.get("min"), spec.get("max")
    if lo is not None or hi is not None:
        x = np.clip(x, lo, hi)
    return np.maximum(1, np.rint(x)).astype(int)


def arrival_times(arrivals, n, block, rng):
    """Due times in seconds from the schedule's start; None for a closed
    loop. `poisson` is exponential gaps at `rate_per_s`."""
    process = arrivals["process"]
    if process == "closed":
        return None
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    spec = {"dist": "exponential",
            "mean": 1.0 / float(arrivals["rate_per_s"])}
    return np.cumsum(_quantile(spec, _stratified_uniforms(n, block, rng)))


@dataclasses.dataclass
class Planned:
    index: int
    due_s: float            # from the schedule's start; None: closed loop
    prompt: list
    max_tokens: int
    prefix_id: int          # which system prompt it starts with, or None


def make_schedule(traffic, vocab, seed, n, stagger=0):
    """The first `n` requests of the cell's traffic for `seed`.

    traffic: the `traffic` group of a workload file. stagger: shorten the
    outputs of the first `stagger` requests to evenly spread fractions, so
    that a closed loop opens on requests at every stage of their life and
    not on `stagger` requests that all started together."""
    block = int(traffic.get("block", 32))
    shape = np.random.default_rng(
        int(traffic["shape_seed"]) if "shape_seed" in traffic
        else [int(seed), 0x5A9E])
    due = arrival_times(traffic["arrivals"], n, block, shape)
    p_len = draw_lengths(traffic["prompt_len"], n, block, shape)
    o_len = draw_lengths(traffic["output_len"], n, block, shape)
    sp = traffic.get("shared_prefix")
    prefix_of = [None] * n
    if sp:
        # every `block` holds its share of sharers, system prompts in turn
        per_block = int(round(float(sp["share"]) * block))
        k = 0
        for b0 in range(0, n, block):
            picks = shape.permutation(min(block, n - b0))[:per_block]
            for j in sorted(picks):
                prefix_of[b0 + j] = k % int(sp["count"])
                k += 1
    if stagger:
        frac = (shape.permutation(stagger) + 0.5) / stagger
        o_len[:stagger] = np.maximum(
            1, np.rint(o_len[:stagger] * frac[:len(o_len[:stagger])]))

    content = np.random.default_rng([int(seed), 0x70C5])
    systems = ([content.integers(0, vocab, int(sp["len"])).tolist()
                for _ in range(int(sp["count"]))] if sp else [])
    out = []
    for i in range(n):
        own = int(p_len[i])
        head = []
        if prefix_of[i] is not None:
            head = systems[prefix_of[i]]
            own = max(int(sp.get("min_own", 16)), own - len(head))
        out.append(Planned(
            i, None if due is None else float(due[i]),
            head + content.integers(0, vocab, own).tolist(),
            int(o_len[i]), prefix_of[i]))
    return out


# ------------------------------------------------------------- observation
@dataclasses.dataclass
class Record:
    """What the benchmark itself saw of one request."""
    planned: Planned
    due_t: float = None          # absolute; None in a closed loop
    submit_t: float = None
    token_t: list = dataclasses.field(default_factory=list)
    request: object = None       # the program's handle
    refused: str = None          # why submit() raised, if it did

    def on_token(self, _request, _token):
        self.token_t.append(time.perf_counter())

    @property
    def done(self):
        return self.refused is not None or (
            self.request is not None and self.request.done)

    @property
    def failed(self):
        return self.refused is not None or (
            self.request is not None
            and self.request.finish_reason in ("error", "rejected",
                                               "timeout"))


def submit(predictor, planned, due_t=None):
    rec = Record(planned, due_t=due_t)
    rec.submit_t = time.perf_counter()
    try:
        rec.request = predictor.submit(
            prompt=planned.prompt, max_tokens=planned.max_tokens,
            on_token=rec.on_token)
    except ValueError as e:                  # shed or refused at admission
        rec.refused = str(e)
    return rec


class OpenLoop(threading.Thread):
    """Sends each planned request at its due time from one thread of its
    own, whatever the server is doing; `records` is in due order."""

    def __init__(self, predictor, schedule, t0):
        super().__init__(name="bench-loadgen", daemon=True)
        self.predictor, self.schedule, self.t0 = predictor, schedule, t0
        self.records = []
        self._halt = threading.Event()

    def run(self):
        for p in self.schedule:
            due_t = self.t0 + p.due_s
            while True:
                wait = due_t - time.perf_counter()
                if wait <= 0 or self._halt.wait(min(wait, 0.5)):
                    break
            if self._halt.is_set():
                return
            self.records.append(submit(self.predictor, p, due_t))

    def stop(self):
        self._halt.set()
        self.join(timeout=10.0)
        if self.is_alive():
            raise RuntimeError("load generator did not stop")


class ClosedLoop:
    """`clients` callers, each with one request outstanding: `poll()`
    after every scheduler round sends a next request for each one that
    completed. Runs in the caller's thread."""

    def __init__(self, predictor, schedule, clients):
        self.predictor = predictor
        self.pending = collections.deque(schedule)
        self.records, self.live = [], []
        for _ in range(min(clients, len(self.pending))):
            self._send()

    def _send(self):
        rec = submit(self.predictor, self.pending.popleft())
        self.records.append(rec)
        if not rec.done:
            self.live.append(rec)

    def poll(self):
        still = [r for r in self.live if not r.done]
        finished = len(self.live) - len(still)
        self.live = still
        for _ in range(min(finished, len(self.pending))):
            self._send()
        return finished


# ------------------------------------------------------------- arithmetic
def percentile(values, p):
    """Nearest rank: the smallest value with at least p% of the sample at
    or below it. None on an empty sample."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def token_gaps(records, t0, t1):
    """Every gap between consecutive tokens of one request whose later
    token landed in [t0, t1]."""
    gaps = []
    for r in records:
        for a, b in zip(r.token_t, r.token_t[1:]):
            if t0 <= b <= t1:
                gaps.append(b - a)
    return gaps


def ttft_sample(records, t0, t1, tail_s):
    """(times to first token of the requests due in [t0, t1 - tail_s],
    how many of those had none by t1). A request without a first token by
    the window's end enters with the wait it had reached by then."""
    waits, missing = [], 0
    for r in records:
        if r.due_t is None or not (t0 <= r.due_t <= t1 - tail_s):
            continue
        first = r.token_t[0] if r.token_t else None
        if first is None or first > t1:
            missing += 1
            waits.append(t1 - r.due_t)
        else:
            waits.append(first - r.due_t)
    return waits, missing


def awaiting_first_token(records, t):
    """Requests due by `t` whose first token had not arrived by then:
    the backlog on the benchmark's own clock. A rate is sustained while
    it is no larger at a window's end than at its middle."""
    return sum(1 for r in records
               if r.due_t is not None and r.due_t <= t
               and not (r.token_t and r.token_t[0] <= t))


def tokens_in(records, t0, t1):
    return sum(1 for r in records for t in r.token_t if t0 <= t <= t1)
