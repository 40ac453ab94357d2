"""Scheduler: admission queue + continuous-batching loop on top of
ServingEngine.

FCFS admission: whenever a slot is free and the queue is non-empty, the
head request is assigned to it MID-STREAM (engine.begin_prefill) and its
prefill advances one engine step per scheduling round
(engine.prefill_step) — the dense engine completes in one round, the
paged engine runs one CHUNK per round, so a long prompt's admission is
folded between decode waves and never stalls the other lanes (same
compiled programs throughout). Retirement (EOS / max_tokens / cache
horizon / timeout) frees slots between waves and the freed slot is
refilled by the next round's admission.

A round is a ONE-DEEP PIPELINE of device programs: it puts its prefill
chunks and its wave on the device's queue first and reads the PREVIOUS
wave's tokens afterwards, so the host's whole share of a round (emit,
retire, journal, the tail, the next admission and staging) runs while
the device works. A token is therefore read one program after it is
made. Who rides wave k is decided without wave k-1's tokens: a lane
whose budget (`max_tokens`) or cache horizon is met by a token still in
flight is left out (the host counts), and a lane that ends on a token's
VALUE (eos, a stop sequence, non-finite logits) is found one wave late:
the step it ran too many wrote one K/V row into a block it still owned
and produced a token that is dropped. The pipeline is held empty (the
round reads each program before it dispatches the next: `_may_dispatch_
ahead`) where the next wave depends on the last one's tokens: a dynamic
token mask, the speculative engine, a prefill-role replica (it exports
what it just read), a draining server; and it is emptied before a
preemption and before a failed dispatch is retried.

Paged-engine capacity (serving/paged) is handled here too: an exhausted
block pool at admission queues the head request behind the blocks it is
waiting for (or sheds it when nothing in flight could free them), and a
lane starved mid-decode is PREEMPTED BY RECOMPUTE — blocks freed,
request requeued with prompt + generated tokens (prefix-cache hits make
the re-prefill cheap), bounded by `max_preemptions`.

Resilience (docs/robustness.md; every path below is proven
by injection in scripts/chaos_serving.py):

  * a failed prefill or a non-finite decode lane resolves ONLY that
    request (finish_reason "error") — the rest of the batch keeps
    decoding the same compiled program; a streak of
    `prefill_fail_limit` CONSECUTIVE prefill failures across distinct
    requests escalates to graceful degradation, so a persistently
    broken engine cannot hide behind per-request isolation with
    /healthz still reporting "ok";
  * a decode-wave exception is retried up to `wave_retries` times with
    bounded exponential backoff (`retry_backoff_s`, doubling); an
    exhausted budget degrades the engine gracefully — in-flight
    requests resolve with "error", queued and new work is shed with
    "rejected", /healthz reports "degraded" — instead of a stack trace
    out of the wave loop;
  * admission control: `max_queue` bounds the queue (overflow sheds
    with finish_reason "rejected"), `drain()` stops admissions while
    accepted work runs to completion (/healthz: "draining").

Observability (docs/observability.md "Spans"): every round is one
`serving/round` span holding the admission / prefill / token_masks /
decode_wave / host_dispatch / round_tail phase spans, and the engine
nests its own inside prefill and decode_wave (stage, dispatch, wait).
Every span is traced on the profiler's clock AND metered into
`phase_seconds`, folded in once a round (the sampling tail is fused
inside the wave program, so it deliberately has no host-side span).
An optional `slo=SLOPolicy` feeds completions into a burn-rate window
served on /healthz.

Thread-model: submit() is safe from any producer thread (the bench
script's Poisson arrival generator); the wave loop itself runs wherever
run()/step() is called — the engine's compiled programs are driven from
one thread at a time.
"""
import collections
import threading
import time

import numpy as np

from ..utils import flight_recorder, profiler, telemetry
from ..utils.profiler import RecordEvent
from . import blackbox
from .metrics import ServingMetrics
from .paged.block_pool import BlockPoolExhausted
from .request import Request, RequestState
from .slo import as_engine as _slo_as_engine


#: replica roles for the disaggregated fleet (serving/fleet/disagg.py):
#: a "prefill" scheduler runs ONLY chunked-prefill programs — each
#: completed prefill is exported as a block-level KV payload and parked
#: for the router (take_handoffs) instead of decoding; a "decode"
#: scheduler accepts ONLY handoff continuations (admission imports the
#: blocks, zero prefill-chunk programs run); "unified" is the classic
#: do-both replica.
ROLES = ("prefill", "decode", "unified")


#: A wave the scheduler dispatched and has not collected: the engine's
#: ticket, the request each of its lanes served at dispatch ({slot:
#: request}; None with no journal to name them in), and what the
#: journal's `wave` event says of the dispatch (its round, the lanes it
#: starved, a speculative wave's counts).
_Wave = collections.namedtuple(
    "_Wave", "ticket members round starved spec_proposed spec_accepted")


class Scheduler:
    def __init__(self, engine, max_queue=None, completed_log=1024,
                 wave_retries=3, retry_backoff_s=0.05,
                 prefill_fail_limit=None, max_preemptions=3, slo=None,
                 role="unified", qos=None):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        if role != "unified" and not hasattr(engine, "export_slot_kv"):
            raise ValueError(
                f"role {role!r} needs an engine with the block-level "
                "handoff surface (export_slot_kv / import_handoff — "
                "serving/paged)")
        self.role = role
        # the collector's pauses, wherever in a round they fall, are
        # `serving/gc` spans and the process's gc_* counters
        telemetry.install_gc_tracking("serving")
        # optional multi-tenant QoS manager (serving/fleet/qos.py),
        # duck-typed: under_pressure(pool) gates weighted-fair admission
        # (pick_admission over the queue) — with qos=None the queue is
        # strict FCFS, pre-QoS behavior exactly
        self.qos = qos
        # prefill-role staging area: (request, payload) pairs whose
        # prefill completed this round, waiting for the router to hand
        # them to a decode replica (payload None = export failed)
        self._handoff_ready = []
        self.engine = engine
        self.max_queue = max_queue
        # chrome-trace process row for this scheduler's (and its
        # engine's) spans/requests (0 = single-engine; a fleet Replica
        # sets replica_id + 1 so the router's merged trace shows each
        # replica on its own row)
        self.trace_pid = 0
        # this round's {phase: seconds}, folded into the metrics (with
        # the engine's own) when the round ends
        self._phases = {}
        self._round_worked = False
        # optional SLO tracking (serving/slo.py): completions feed the
        # sliding window, every round re-evaluates, and the burn-rate
        # verdict rides /healthz next to queue depth
        self.slo_engine = _slo_as_engine(slo)
        # optional observability plane (utils/timeseries + utils/anomaly,
        # attach_timeseries): the sampler banks every metric once per
        # working round, the alert manager runs its detector set.  The
        # engine's health-probe slot is NEWEST-WINS, so the SLO verdict
        # and the alert state must share ONE merged probe.
        self._sampler = None
        self._alerts = None
        if self.slo_engine is not None:
            engine.attach_health_probe(self._health_extras)
        self.wave_retries = max(0, int(wave_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        # paged engines: a request may be preempted by recompute (its KV
        # blocks reclaimed under pool pressure, the request requeued
        # with prompt + generated tokens) at most this many times before
        # it resolves "error" — preemption must converge, not livelock
        self.max_preemptions = max(0, int(max_preemptions))
        # consecutive DISTINCT-request prefill failures tolerated before
        # concluding the fault is the engine's, not the requests' (e.g. a
        # raise from inside the compiled prefill after the donated cache
        # was consumed fails every admission thereafter) — reaching it
        # degrades instead of failing requests one-by-one forever while
        # /healthz keeps saying "ok"
        self.prefill_fail_limit = (engine.num_slots + self.wave_retries
                                   if prefill_fail_limit is None
                                   else max(1, int(prefill_fail_limit)))
        self._prefill_fail_streak = 0
        self._queue = collections.deque()
        self._lock = threading.Lock()        # queue + lifecycle flags
        self._wave_lock = threading.Lock()   # one step() at a time
        self._slot_req = [None] * engine.num_slots
        # tokens the slot's request may still have made: its `max_tokens`
        # less those it had at admission, less one for every program
        # dispatched since that makes it one (its last chunk, a wave it
        # rides), read or not
        self._budget = np.zeros((engine.num_slots,), np.int64)
        # waves dispatched and not collected, oldest first: at most one
        # between rounds (`_Wave`)
        self._waves = collections.deque()
        self._draining = False
        self._degraded = False
        self.last_error = None
        self.metrics = ServingMetrics(engine.num_slots)
        # an engine whose model keeps slot state or routes to experts
        # counts that work; the others are asked nothing a round
        self._counts_model_work = getattr(engine, "counts_model_work",
                                          False)
        # /healthz carries this scheduler's queue depth (fleet routers
        # and LBs read load + pool pressure from one endpoint)
        engine.attach_queue_probe(self.queue_depth)
        pool = getattr(engine, "block_pool", None)
        if pool is not None:
            # seed the prefix-delta baseline with the pool's totals
            # BEFORE any round of ours — the snapshot then reports
            # exactly this scheduler's lookups, first round included
            self.metrics.on_prefix_totals(pool.prefix_hits,
                                          pool.prefix_misses,
                                          pool.evictions)
        # bounded: callers hold their own Request handles (submit returns
        # them); this ring is a debugging/inspection tail, and unbounded
        # growth would leak every prompt ever served on a long-running
        # server. completed_log=None keeps everything (tests/benches).
        self.completed = collections.deque(maxlen=completed_log)
        # black-box journal coordinates (serving/blackbox.py): the
        # scheduling-round counter stamps every journaled decision so
        # replay can re-submit and re-fault in the same round order;
        # the wave counter names waves in `wave` events
        self._round = 0
        self._wave_seq = 0
        # perf_counter() when the last round ended
        self._round_end = None

    @property
    def trace_pid(self):
        return self._trace_pid

    @trace_pid.setter
    def trace_pid(self, pid):
        self._trace_pid = self.engine.trace_pid = int(pid)

    def _phase(self, phase, ev):
        """Add a finished span's seconds to this round's `phase`."""
        if ev.elapsed is not None:
            self._phases[phase] = self._phases.get(phase, 0.0) + ev.elapsed

    def _replica_ord(self):
        """This scheduler's fleet replica id for journal events (the
        chrome-trace pid is replica_id + 1; None = single-engine)."""
        return self.trace_pid - 1 if self.trace_pid else None

    # ------------------------------------------------------ observability
    def attach_timeseries(self, sampler=None, alerts=None):
        """Attach the metrics-history sampler and/or an AlertManager
        (utils/timeseries, utils/anomaly): both run once per WORKING
        round at wave end, and the alert state rides /healthz next to
        the SLO verdict (one merged health probe — the engine's probe
        slot is newest-wins, so separate attaches would shadow each
        other).  Returns self for chaining."""
        if sampler is not None:
            self._sampler = sampler
        if alerts is not None:
            self._alerts = alerts
        self.engine.attach_health_probe(self._health_extras)
        return self

    def _health_extras(self):
        """The merged /healthz fragment: SLO verdict + alert state."""
        out = {}
        if self.slo_engine is not None:
            out.update(self.slo_engine.health() or {})
        if self._alerts is not None:
            out.update(self._alerts.health() or {})
        return out

    # ---------------------------------------------------------- admission
    def submit(self, request=None, **kw):
        """Enqueue a Request (or build one from kwargs: prompt,
        max_tokens, eos_token_id, timeout, on_token, do_sample,
        temperature). Oversized prompts are rejected CLEANLY here — the
        request is marked REJECTED, a ValueError raises to the caller,
        and the engine/queue state is untouched."""
        if request is None:
            request = Request(**kw)
        # role defense-in-depth: the fleet router filters candidates by
        # role before dispatch, so these raise only on a direct misuse —
        # without finalizing the request (the caller may route it to a
        # capable replica instead)
        if self.role == "decode" and request.handoff is None:
            raise ValueError(
                "decode-role replica accepts only block-level handoff "
                "continuations (this request still needs prefill)")
        if self.role == "prefill" and request.handoff is not None:
            raise ValueError(
                "prefill-role replica cannot import a handoff payload")
        # seed provenance: stamp the engine's PRNG-chain seed on the
        # request (greedy too — the chain is shared) so the journal
        # names the seed that replays it; an already-stamped seed (a
        # fleet hop's continuation) wins
        if request.seed is None:
            request.seed = getattr(self.engine, "seed", None)
        bb = blackbox.get_recorder()
        why = self.engine.validate_prompt(request.prompt)
        if why is not None:
            self.metrics.on_reject()
            if bb is not None:
                bb.admission(request.request_id, verdict="rejected",
                             reason="invalid_prompt",
                             tenant=request.tenant,
                             trace_id=request.trace_id,
                             round=self._round,
                             replica=self._replica_ord())
            request._reject(why)           # raises ValueError
        with self._lock:
            if self._degraded:
                shed = f"engine degraded ({self.last_error})"
            elif self._draining:
                shed = "engine draining (graceful shutdown)"
            elif self.max_queue is not None and len(self._queue) >= \
                    self.max_queue:
                shed = f"queue full (max_queue={self.max_queue})"
            else:
                shed = None
                request.trace_pid = self.trace_pid
                request._mark_submitted()
                self._queue.append(request)
                depth = len(self._queue)
        if shed is not None:
            self.metrics.on_reject()
            if bb is not None:
                bb.admission(request.request_id, verdict="shed",
                             reason=shed, tenant=request.tenant,
                             trace_id=request.trace_id,
                             round=self._round,
                             replica=self._replica_ord())
            request._reject(shed)          # raises ValueError
        if bb is not None:
            bb.submit(request, round=self._round,
                      replica=self._replica_ord())
        self.metrics.on_submit()
        self.metrics.on_queue_depth(depth)
        return request

    def queue_depth(self):
        with self._lock:
            return len(self._queue)

    def _pop_next(self):
        """Next request to admit: strict FCFS — except under block-pool
        pressure with a QoS manager attached, where the pick is
        weighted-fair across tenants (least weighted in-flight cost
        first, FCFS within a tenant) so one saturating tenant cannot
        monopolize every freed block while others queue behind it."""
        with self._lock:
            req, i = None, 0
            if self._queue:
                if self.qos is not None and len(self._queue) > 1 and \
                        self.qos.under_pressure(
                            getattr(self.engine, "block_pool", None)):
                    counts = {}
                    for r in self._slot_req:
                        if r is not None:
                            t = getattr(r, "tenant", "default")
                            counts[t] = counts.get(t, 0) + 1
                    i = self.qos.pick_admission(self._queue, counts)
                req = self._queue[i]
                del self._queue[i]
            depth = len(self._queue)
        self.metrics.on_queue_depth(depth)
        return req

    def _requeue_front(self, req):
        """Put a request back at the queue HEAD (capacity pressure:
        pool-exhausted admission, preemption) — it keeps its FCFS
        standing."""
        with self._lock:
            self._queue.appendleft(req)
            depth = len(self._queue)
        self.metrics.on_queue_depth(depth)

    def _continuation(self, req):
        """The token prefix a (re-)admission must prefill: the prompt
        plus anything already generated — a preempted request resumes by
        recompute, and its next prefill's frontier logits produce the
        NEXT token, not a repeat."""
        return req.prompt + req.output_tokens

    def _combined_bias(self, req):
        """The slot's effective [V] bias row: static logit_bias plus the
        request's token_mask evaluated against what it has emitted so
        far (bool masks normalize to 0/-1e9 in the engine)."""
        bias = self.engine._normalize_bias(req.logit_bias)
        if req.token_mask is not None:
            bias = bias + self.engine._normalize_bias(req.token_mask(req))
        return bias

    def _admission_bias(self, req):
        """Bias row handed to begin_prefill: the first token must obey
        the mask too. A raising token_mask lands inside the admission
        fault barrier — it fails ITS request, nothing else."""
        return (req.logit_bias if req.token_mask is None
                else self._combined_bias(req))

    def _refresh_token_masks(self):
        """Re-evaluate every dynamic token_mask against the tokens its
        request has emitted (constrained decoding advances per token)
        and upload the fresh bias rows before the wave. A raising mask
        callable fails only its own request — same isolation contract
        as on_token callbacks."""
        for slot, req in enumerate(self._slot_req):
            if req is None or req.token_mask is None or \
                    not self.engine.slot_active[slot]:
                continue
            try:
                self.engine.set_slot_bias(slot, self._combined_bias(req))
            except Exception as e:   # noqa: BLE001 — client code
                self.last_error = e
                self._free_slot(slot)
                self._fault("token_mask_error", action="request_failed",
                            request=req, slot=slot, error=e)
                req._fail(e)
                self._complete(req)

    def _admit(self):
        """Assign queued requests to free slots and stage their prefill
        (engine.begin_prefill — block allocation on a paged engine); the
        work itself runs in _advance_prefills, so a long chunked prefill
        folds between decode waves. A request whose timeout already
        expired in the queue is retired without spending a prefill on
        it; an exhausted block pool is CAPACITY, not a request fault —
        the head request waits for blocks to free (or is rejected when
        nothing in flight could ever free them)."""
        bb = blackbox.get_recorder()
        rep = self._replica_ord()
        while True:
            free = self.engine.free_slots()
            if not free:
                return
            req = self._pop_next()
            if req is None:
                return
            if req._timed_out():
                req._finish("timeout")
                self._complete(req)
                continue
            slot = free[0]
            handoff = getattr(req, "handoff", None)
            try:
                if handoff is not None:
                    # block-level handoff: import the prefill replica's
                    # populated KV blocks and arm the slot directly —
                    # ZERO prefill-chunk programs run here (the whole
                    # point: a handoff costs bytes, not recompute)
                    self.engine.import_handoff(
                        slot, self._continuation(req), handoff,
                        do_sample=req.do_sample,
                        temperature=req.temperature,
                        top_k=req.top_k, top_p=req.top_p,
                        logit_bias=self._admission_bias(req),
                        dynamic_mask=req.token_mask is not None)
                else:
                    self.engine.begin_prefill(
                        slot, self._continuation(req),
                        do_sample=req.do_sample,
                        temperature=req.temperature,
                        top_k=req.top_k, top_p=req.top_p,
                        logit_bias=self._admission_bias(req),
                        dynamic_mask=req.token_mask is not None)
            except BlockPoolExhausted as e:
                if self.engine.active_slots() or \
                        self.engine.prefilling_slots():
                    # in-flight work will free blocks: wait at the head.
                    # One fault per wait EPISODE — a long decode can
                    # hold the head here for hundreds of rounds, and
                    # per-round records would flood the counters/journal
                    if not req._cache_waiting:
                        req._cache_waiting = True
                        self._fault("cache_exhausted", action="requeued",
                                    request=req, error=e)
                        if bb is not None:
                            bb.admission(req.request_id,
                                         verdict="deferred",
                                         reason="cache_exhausted",
                                         tenant=req.tenant,
                                         trace_id=req.trace_id,
                                         round=self._round, replica=rep)
                    self._requeue_front(req)
                    return
                # nothing in flight to free blocks — shed cleanly
                self.metrics.on_reject()
                self._fault("cache_exhausted", action="rejected",
                            request=req, error=e)
                if bb is not None:
                    bb.admission(req.request_id, verdict="rejected",
                                 reason="cache_exhausted",
                                 tenant=req.tenant,
                                 trace_id=req.trace_id,
                                 round=self._round, replica=rep)
                req._reject(f"KV cache exhausted ({e})",
                            raise_error=False)
                self.completed.append(req)
                continue
            except Exception as e:   # noqa: BLE001 — fault barrier:
                # isolate the failing admission to ITS request; staging
                # mutates no device state, so the slot stays free and
                # every other lane is untouched
                self.last_error = e
                if handoff is not None:
                    # a refused handoff (digest/geometry mismatch) is a
                    # REQUEST fault — the payload is unusable, so fail
                    # only this request; it never feeds the engine's
                    # prefill-fail streak (the engine is healthy)
                    self._fault("handoff_refused",
                                action="request_failed", request=req,
                                slot=slot, error=e)
                    req.handoff = None
                    req._fail(e)
                    self._complete(req)
                    continue
                if self._prefill_fault(req, slot):
                    return
                continue
            if bb is not None:
                bb.admission(req.request_id, verdict="admitted",
                             slot=slot, tenant=req.tenant,
                             basis=("handoff" if handoff is not None
                                    else "prefill"),
                             trace_id=req.trace_id,
                             round=self._round, replica=rep)
            # handoff consumed one-shot: a LATER re-admission of this
            # request (preemption, migration) replays from the prefix
            # cache like any other continuation
            req.handoff = None
            req._cache_waiting = False         # wait episode (if any) over
            req._start_prefill(slot)
            # engine-internal progress (per-chunk prefill) correlates
            # to the request's chrome flow through the slot
            self.engine.set_slot_trace(slot, req.trace_id,
                                       self.trace_pid)
            self._slot_req[slot] = req
            self._budget[slot] = req.max_tokens - len(req.output_tokens)

    def _free_slot(self, slot):
        """The slot's request is leaving it (finished, failed, evicted):
        free the engine's slot, with its blocks. Whatever is still in
        flight for it is dropped when it is read."""
        self.engine.retire_slot(slot)
        self._slot_req[slot] = None

    def _prefill_fault(self, req, slot):
        """Shared admission/chunk fault barrier: fail ONLY this request,
        free the slot, and escalate to degradation after
        `prefill_fail_limit` consecutive distinct-request failures.
        Returns True when the engine degraded (stop the round)."""
        self._free_slot(slot)              # frees pending state + blocks
        self._prefill_fail_streak += 1
        escalate = self._prefill_fail_streak >= self.prefill_fail_limit
        self._fault("prefill_error",
                    action=("degrade" if escalate else "request_failed"),
                    request=req, slot=slot, error=self.last_error)
        req._fail(self.last_error)
        self._complete(req)
        if escalate:
            self._degrade()
            return True
        return False

    def _advance_prefills(self):
        """Dispatch one prefill step per mid-admission slot (ONE chunk
        on a paged engine; the whole bucket on the dense engine), back to
        back, reading nothing. Slots whose prefill completed are armed
        and ride this round's decode wave; their first tokens are read by
        `_emit_first_tokens`. Returns True when a fault escalated to
        degradation."""
        for slot in self.engine.prefilling_slots():
            req = self._slot_req[slot]
            if req._timed_out():
                # chunked prefill can span many rounds — don't keep
                # burning chunk programs (and finally emit a token) on a
                # request that already expired; same semantics as the
                # queue-pop timeout check
                self._free_slot(slot)
                req._finish("timeout")
                self._complete(req)
                continue
            ev = RecordEvent(
                "serving/prefill", pid=self.trace_pid,
                request_id=req.request_id, slot=slot,
                chunk=self.engine.prefill_chunk_index(slot))
            try:
                with ev:
                    done = self.engine.prefill_step(slot)
            except Exception as e:   # noqa: BLE001 — fault barrier
                self.last_error = e
                if self._prefill_fault(req, slot):
                    return True
                continue
            finally:
                self._phase("prefill_chunk", ev)
            self._prefill_fail_streak = 0
            if done:                 # else mid-prefill: decode waves go on
                self._budget[slot] -= 1
        return False

    def _emit_first_tokens(self):
        """Read the first tokens of the prompts whose last chunk was
        dispatched (one blocking read: with a wave queued behind the
        chunks the device works on through it) and stream them. Each was
        made one program or more ago; a request that ends on it (eos,
        `max_tokens` 1) retires here, and was left out of the wave
        dispatched meanwhile only if the host could count that."""
        if not self.engine.first_tokens_pending:
            return
        self._round_worked = True
        with RecordEvent("serving/prefill", pid=self.trace_pid,
                         first_tokens=self.engine.first_tokens_pending
                         ) as ev:
            firsts = self.engine.collect_first_tokens()
        self._phase("prefill_chunk", ev)
        now = time.monotonic()
        for slot, first in firsts.items():
            req = self._slot_req[slot]
            self.metrics.on_prefill()
            # prev_t is non-None only for a preempted-then-resumed
            # request: its re-prefill token IS an inter-token gap (the
            # preemption stall is real TPOT the client observed)
            prev_t = req.last_token_time
            req._emit(first)
            self.metrics.on_token(now, prev_t=prev_t)
            # a prompt leaves room to decode (validate_prompt): the
            # horizon is never met by a first token
            self._maybe_retire(slot, first, full=False)
            if self.role == "prefill" and self._slot_req[slot] is not None:
                # prefill-role epilogue: this replica never decodes —
                # package the populated KV blocks for a decode replica
                self._export_handoff(slot)

    def _export_handoff(self, slot):
        """Export the slot's populated KV blocks (the prefill just
        completed and emitted its first token) and park (request,
        payload) for the router to hand to a decode replica; the slot
        retires either way — freed blocks keep their prefix hashes, so
        a failed export's fallback (migration-by-recompute, payload
        None) still re-prefills mostly from cache."""
        req = self._slot_req[slot]
        payload = None
        try:
            payload = self.engine.export_slot_kv(slot)
        except Exception as e:   # noqa: BLE001 — fault barrier: the
            # router falls back to recompute, bounded by its budget
            self.last_error = e
            self._fault("handoff_error", action="export_failed",
                        request=req, slot=slot, error=e)
        self._free_slot(slot)
        with self._lock:
            self._handoff_ready.append((req, payload))

    def take_handoffs(self):
        """Drain the prefill-role staging area: [(request, payload)]
        pairs whose prefill completed (payload None = export failed;
        the caller migrates by recompute instead)."""
        with self._lock:
            out = self._handoff_ready
            self._handoff_ready = []
        return out

    # ---------------------------------------------------------- wave loop
    def _maybe_retire(self, slot, last_token, full):
        """Retire the slot if its request just finished: EOS (even on the
        very first prefill-produced token), a stop sequence, token
        budget, cache horizon, or wall-clock timeout. `full`: whether
        this token is the one that filled the slot to the horizon, by
        the wave that made it (`WaveTicket.full`: the engine's mirror may
        have counted a later wave since). False for the NON-final tokens
        of a speculative batch: the position is already advanced for the
        whole batch, and only its last token is the one written at the
        horizon — retiring on an earlier one would drop tokens the plain
        engine delivers."""
        req = self._slot_req[slot]
        reason = None
        if req.eos_token_id is not None and last_token == req.eos_token_id:
            reason = "eos"
        elif req.stop_sequences and req._hit_stop():
            reason = "stop"
        elif len(req.output_tokens) >= req.max_tokens:
            reason = "max_tokens"
        elif full:
            reason = "length"
        elif req._timed_out():
            reason = "timeout"
        if reason is not None:
            self._free_slot(slot)
            req._finish(reason)
            self._complete(req)

    def _complete(self, req):
        self.completed.append(req)
        bb = blackbox.get_recorder()
        if bb is not None:
            bb.complete(req, round=self._round,
                        replica=self._replica_ord())
        self.metrics.on_complete(req)
        if self.slo_engine is not None:
            self.slo_engine.observe_request(req)

    def _fault(self, kind, action=None, request=None, slot=None,
               error=None):
        """One fault handled: count it (serving_faults_total{kind}) and
        journal it through the current flight recorder."""
        self.metrics.on_fault(kind)
        rec = flight_recorder.get_recorder()
        if rec is not None:
            rec.fault(kind=kind, action=action,
                      request_id=None if request is None
                      else request.request_id,
                      slot=slot,
                      error=None if error is None else repr(error))

    def _spent_lanes(self):
        """The active slots with no token left to make: the programs
        dispatched for the request so far meet its `max_tokens`, or fill
        its slot to the horizon. The host knows both by counting, without
        the tokens' values. Empty when nothing is in flight: such a slot
        has been retired."""
        eng = self.engine
        return np.flatnonzero(eng.slot_active & (
            (self._budget <= 0) | (eng.slot_pos >= eng.max_len)))

    def _dispatch_wave_with_retry(self):
        """The decode wave's dispatch behind a bounded-exponential-
        backoff retry. Returns None after degrading (budget exhausted),
        else the lanes the wave starved of a block (the wave, if any
        lane was left to decode, is the newest of `_waves`; no program
        goes out when every active lane's last token is in flight). The
        engine raises BEFORE consuming its key or the donated cache, so
        a retried dispatch replays exactly, and it goes out with nothing
        in flight: what was is read first. An error from inside the
        compiled call may have invalidated the donated cache, in which
        case the retry fails too and the budget runs out — degradation,
        not an infinite loop."""
        delay = self.retry_backoff_s
        for attempt in range(self.wave_retries + 1):
            spent = self._spent_lanes()
            lanes = len(self.engine.active_slots()) - len(spent)
            if not lanes:
                return []
            try:
                with RecordEvent("serving/decode_wave",
                                 pid=self.trace_pid, round=self._round,
                                 lanes=lanes) as ev:
                    ticket = self.engine.dispatch_wave(spent)
                self._phase("decode_wave", ev)
            except Exception as e:   # noqa: BLE001 — fault barrier
                self.last_error = e
                self._fault("wave_error",
                            action=("retry" if attempt < self.wave_retries
                                    else "degrade"),
                            error=e)
                if attempt >= self.wave_retries:
                    break
                self.metrics.on_wave_retry()
                if not self._collect_waves():
                    return None
                self._emit_first_tokens()
                time.sleep(delay)
                delay *= 2
                continue
            if ticket is not None:
                self._wave_dispatched(ticket)
            return self.engine.last_starved_slots
        self._degrade()
        return None

    def _wave_dispatched(self, ticket):
        """Count a wave that went out and keep what its collect needs:
        the request each lane serves now, not then."""
        waved = len(ticket.lanes)
        self.metrics.on_wave(waved, ahead=bool(self._waves))
        self._record_spec_wave(waved)
        self._budget[ticket.lanes] -= 1
        # the journal names requests: taken now, while the slots hold them
        members = (None if blackbox.get_recorder() is None else
                   {s: self._slot_req[s] for s in ticket.lanes.tolist()})
        self._waves.append(_Wave(
            ticket, members, self._round,
            sorted(self.engine.last_starved_slots) or None,
            getattr(self.engine, "last_spec_proposed", None),
            getattr(self.engine, "last_spec_accepted", None)))

    def _collect_waves(self, keep=0):
        """Read the dispatched waves, oldest first, down to the newest
        `keep`, and stream their tokens. False when a read failed and
        the engine degraded."""
        while len(self._waves) > keep:
            if not self._collect_wave(self._waves.popleft()):
                return False
        return True

    def _collect_wave(self, wave):
        """One wave's tokens, read (`serving/wave/wait`) and handed out:
        the journal's `wave` event with the membership taken at
        dispatch, the poisoned lanes retired, every other lane's token
        emitted and its request retired if that token ended it. A lane
        whose slot was retired (or re-admitted) since the dispatch has no
        entry: its request ended on an earlier token."""
        self._round_worked = True
        try:
            with RecordEvent("serving/decode_wave", pid=self.trace_pid,
                             round=wave.round, collect=True) as ev:
                toks = self.engine.collect_wave(wave.ticket)
        except Exception as e:   # noqa: BLE001 — fault barrier: the
            # program consumed its donated inputs, so nothing can be
            # dispatched again
            self.last_error = e
            self._fault("wave_error", action="degrade", error=e)
            self._degrade()
            return False
        self._phase("decode_wave", ev)
        bb = blackbox.get_recorder()
        if bb is not None and toks and wave.members is not None:
            # membership BEFORE the dispatch loop below retires finished
            # slots (after it, the slot->request map may be cleared)
            self._wave_seq += 1
            bb.wave(
                self._wave_seq,
                members=[{"slot": s,
                          "request_id": wave.members[s].request_id,
                          "tokens": (len(t) if isinstance(t, list)
                                     else 1)}
                         for s, t in sorted(toks.items())],
                starved=wave.starved,
                nonfinite=sorted(self.engine.last_nonfinite_slots)
                or None,
                spec_proposed=wave.spec_proposed,
                spec_accepted=wave.spec_accepted,
                round=wave.round, replica=self._replica_ord())
        # fused-sentinel fallout: retire ONLY the poisoned lanes —
        # their requests resolve with "error", healthy neighbours
        # stream on token-identically (proven in chaos_serving)
        for slot in self.engine.last_nonfinite_slots:
            req = self._slot_req[slot]
            self._free_slot(slot)
            self._fault("nonfinite", action="slot_retired",
                        request=req, slot=slot)
            req._fail("non-finite logits in decode wave")
            self._complete(req)
        now = time.monotonic()
        with RecordEvent("serving/host_dispatch",
                         pid=self.trace_pid) as ev:
            for slot, emitted in toks.items():
                req = self._slot_req[slot]
                full = slot in wave.ticket.full
                # a speculative wave emits a BATCH per lane; stream
                # it in order and stop at the first retirement
                # (eos/stop/budget/horizon) — the batch's rejected
                # tail past that point is dropped, exactly what the
                # non-speculative wave would never have generated
                if not isinstance(emitted, list):
                    emitted = [emitted]
                for j, tok in enumerate(emitted):
                    prev_t = req.last_token_time
                    req._emit(tok)
                    self.metrics.on_token(now, prev_t=prev_t)
                    self._maybe_retire(
                        slot, tok, full and j == len(emitted) - 1)
                    if self._slot_req[slot] is None:
                        break
        self._phase("host_dispatch", ev)
        return True

    def _degrade(self):
        """Graceful degradation: the wave loop cannot make progress, so
        resolve everything cleanly — in-flight requests finish with
        "error", queued requests shed with "rejected", new submits are
        rejected, and /healthz reports "degraded" — instead of leaking
        a stack trace through step()."""
        with self._lock:
            # flag + health transition under ONE lock: a concurrent
            # drain() cannot interleave and overwrite "degraded" with
            # "draining" on an engine that can no longer make progress
            self._degraded = True
            self.engine.set_health_state("degraded")
        self._fault("degraded", action="drain_and_reject",
                    error=self.last_error)
        # what is in flight is never read: every request it served
        # resolves here
        self._waves.clear()
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            self._free_slot(slot)
            req._fail(f"engine degraded: {self.last_error!r}")
            self._complete(req)
        with self._lock:
            parked = [req for req, _ in self._handoff_ready]
            self._handoff_ready = []
        for req in parked:
            req._fail(f"engine degraded: {self.last_error!r}")
            self._complete(req)
        while True:
            req = self._pop_next()
            if req is None:
                break
            self.metrics.on_reject()
            req._reject(f"engine degraded ({self.last_error!r})",
                        raise_error=False)
            # shed, not completed: on_complete would double-count the
            # request and pollute the latency histogram with a
            # queue-wait-only sample — the inspection ring still gets it
            self.completed.append(req)

    def evacuate(self):
        """Pull every accepted-but-unresolved request out of this
        scheduler WITHOUT resolving it, and stop accepting work. The
        fleet failover path calls this on a replica presumed DEAD, so
        no engine call is made here (a wave in flight is never read: its
        tokens were handed to no one, and the migrated request makes them
        again). The router migrates from its OWN
        live-request registry (serving/fleet/router.py scans _live —
        it must not trust a dead replica's bookkeeping); the returned
        list (in-slot first, then queued) is informational: operators
        and tests can see exactly what a kill stranded."""
        with self._wave_lock:          # never mid-round: whole rounds
            with self._lock:           # interleave with the evacuation
                self._degraded = True  # step() idles; submit() sheds
                if self.last_error is None:
                    self.last_error = "replica evacuated"
                queued = list(self._queue)
                self._queue.clear()
                # handoffs parked but never picked up (the payload dies
                # with the replica; the request migrates by recompute)
                parked = [req for req, _ in self._handoff_ready]
                self._handoff_ready = []
            out = [req for req in self._slot_req if req is not None]
            self._slot_req = [None] * self.engine.num_slots
            self._waves.clear()
            out.extend(parked)
            out.extend(queued)
        self.metrics.on_queue_depth(0)
        return out

    def step(self):
        """One scheduling round: refill free slots from the queue,
        dispatch the prefill chunks and one batched decode wave, THEN
        read the previous round's wave, stream its tokens and retire the
        slots they finished (the module comment: a token is read one
        program after it is made, so a slot freed by a token's value is
        refilled one round later than the token was made, and the round
        after the last wave only reads). Returns the number of requests
        still in flight or queued.

        Serialized by `_wave_lock`, so concurrent drivers (a run() loop
        in one thread, shutdown() in another) interleave whole rounds
        instead of racing the engine's donated caches."""
        with self._wave_lock:
            return self._step_locked()

    def _record_spec_wave(self, waved):
        """Speculative-wave accounting: proposed/accepted counters +
        acceptance-rate gauge (serving_spec_* — docs/observability.md),
        a `spec` journal event, and a per-wave trace instant carrying
        the wave's spec_depth (accepted tokens per dispatched lane)."""
        proposed = getattr(self.engine, "last_spec_proposed", None)
        if proposed is None:
            return                      # not a speculative engine
        accepted = self.engine.last_spec_accepted
        self.metrics.on_spec(proposed, accepted)
        depth = accepted / waved if waved else 0.0
        rec = flight_recorder.get_recorder()
        if rec is not None:
            rec.spec(proposed=proposed, accepted=accepted,
                     lanes=waved, spec_depth=round(depth, 4))
        if profiler.trace_enabled():
            telemetry.trace_instant(
                0, "SPEC_WAVE", pid=self.trace_pid,
                spec_depth=round(depth, 4), proposed=proposed,
                accepted=accepted)

    def _preemption_victim(self, starved_slot):
        """Priority preemption: choose which lane recompute evicts to
        unblock a starved one. Among the OTHER active lanes, pick the
        lowest-priority one STRICTLY below the starved request's
        priority (ties: latest-submitted goes, preserving FCFS within a
        class). None when no lane ranks below — then the starved lane
        itself is evicted, which at uniform priority (the default 0
        everywhere) reproduces pre-QoS behavior exactly."""
        starved_pri = getattr(self._slot_req[starved_slot], "priority", 0)
        victim = None
        for slot, req in enumerate(self._slot_req):
            if req is None or slot == starved_slot or \
                    not self.engine.slot_active[slot]:
                continue
            pri = getattr(req, "priority", 0)
            if pri >= starved_pri:
                continue
            if victim is None:
                victim = slot
                continue
            vreq = self._slot_req[victim]
            vpri = getattr(vreq, "priority", 0)
            if pri < vpri or (pri == vpri and (req.submit_time or 0) >
                              (vreq.submit_time or 0)):
                victim = slot
        return victim

    def _evict_for_recompute(self, slot, victim_for=None):
        """Preemption-by-recompute of one lane: free the slot's blocks,
        requeue the request with prompt + generated tokens (the freed
        blocks' prefix hashes make the re-prefill mostly cache hits). A
        request past its preemption budget, or one whose continuation
        could never fit the pool, resolves "error" instead of
        livelocking. `victim_for` names the starved request this
        eviction unblocks (priority preemption) for the journal."""
        req = self._slot_req[slot]
        bb = blackbox.get_recorder()
        self._free_slot(slot)                  # frees the blocks
        req.preemptions += 1
        cont = self._continuation(req)
        why = self.engine.validate_prompt(cont)
        if req.preemptions > self.max_preemptions or why is not None:
            self._fault("cache_exhausted", action="request_failed",
                        request=req, slot=slot)
            if bb is not None:
                bb.preempt(req.request_id, slot=slot,
                           reason="budget_spent", victim_for=victim_for,
                           preemptions=req.preemptions,
                           round=self._round,
                           replica=self._replica_ord())
            req._fail(why or "KV cache exhausted: preemption budget "
                             f"spent ({req.preemptions}x)")
            self._complete(req)
            return
        self._fault("cache_exhausted", action="preempted",
                    request=req, slot=slot)
        if bb is not None:
            bb.preempt(req.request_id, slot=slot, reason="pool_pressure",
                       victim_for=victim_for,
                       preemptions=req.preemptions, round=self._round,
                       replica=self._replica_ord())
        self._requeue_front(req)

    def _preempt_starved(self, starved):
        """Pool-exhausted lanes (the wave excluded them): evict a lane
        by recompute so blocks free up. Which lane is a QoS decision —
        a lower-priority lane below the starved request goes first
        (_preemption_victim); otherwise the starved lane evicts itself
        (and the victim path leaves it armed to retry allocation at the
        next wave against the freed blocks)."""
        for slot in starved:
            if self._slot_req[slot] is None:
                continue     # already evicted as another lane's victim
                             # (or finished during this round's dispatch)
            victim = self._preemption_victim(slot)
            if victim is None:
                self._evict_for_recompute(slot)
            else:
                self._evict_for_recompute(
                    victim,
                    victim_for=self._slot_req[slot].request_id)

    def _step_locked(self):
        if self._degraded:
            return 0
        # round stamp for every decision journaled below: replay
        # re-submits and re-faults in the same round order, so the
        # counter must tick before ANY of this round's decisions
        self._round += 1
        eng = self.engine
        # the device may have run dry since the last round ended, while
        # this scheduler's caller had the thread
        eng.poll_unfed(self._round_end)
        self._phases = {}
        self._round_worked = False
        pending = 0
        ev = RecordEvent("serving/round", pid=self.trace_pid,
                         round=self._round, lanes=int(eng.slot_active.sum()),
                         prefilling=len(eng.prefilling_slots()))
        try:
            with ev:
                pending = self._run_round()
        finally:
            # one fold a round: the scheduler's phases, the engine's
            # (wave.*, prefill.*, unfed), and `round`, the total of a
            # round that had work
            phases = self._phases
            for k, v in eng.take_phase_seconds().items():
                phases[k] = phases.get(k, 0.0) + v
            if self._round_worked and ev.elapsed is not None:
                phases["round"] = ev.elapsed
            self.metrics.on_phases(phases)
            if hasattr(eng, "take_page_counts"):
                self.metrics.on_pages(*eng.take_page_counts())
            if self._counts_model_work:
                self.metrics.on_model_counts(eng.take_model_counts())
            uploads = eng.take_bias_uploads()
            if uploads:
                self.metrics.on_bias_uploads(uploads)
            if not (self._round_worked and pending):
                # an empty server is not a slow host: what passes until
                # the next dispatch is not the host's doing
                eng.drop_unfed()
            else:
                eng.poll_unfed()
            self._round_end = time.perf_counter()
        return pending

    def _may_dispatch_ahead(self):
        """Whether this round may put its programs on the device's queue
        before it reads the last wave's tokens. Not where the next wave
        is a function of those tokens: a dynamic token mask on any
        request in a slot (the next mask is computed from the token not
        read yet), an engine that has to read each wave before the next
        (speculative: the accepted lengths roll blocks back); not on a
        prefill-role replica (it exports the slot it just read); not
        while the server drains. The round then reads every program
        before it dispatches the next: the same code, nothing in
        flight."""
        if not self.engine.pipelined or self._draining or \
                self.role == "prefill":
            return False
        return not any(r is not None and r.token_mask is not None
                       for r in self._slot_req)

    def _run_round(self):
        with RecordEvent("serving/admission", pid=self.trace_pid) as ev:
            self._admit()
        self._phase("admission", ev)
        # captured BEFORE the advance: a prefill that admits, emits its
        # first token, and retires within one round still counts as a
        # working round for the pool sample below
        self._round_worked = bool(self.engine.prefilling_slots())
        ahead = self._may_dispatch_ahead()
        if not ahead and not self._collect_waves():
            return 0                         # degraded on a read
        if self._advance_prefills():
            return 0                         # degraded mid-advance
        if not ahead:
            self._emit_first_tokens()
        with RecordEvent("serving/token_masks", pid=self.trace_pid) as ev:
            self._refresh_token_masks()
        self._phase("token_masks", ev)
        waves_before = len(self._waves)
        starved = []
        if self.engine.active_slots():
            self._round_worked = True
            starved = self._dispatch_wave_with_retry()
            if starved is None:
                return 0          # degraded: everything is resolved
        # this round's wave stays in flight while the host does its
        # share; the one before it is read now. With no wave dispatched
        # (no lane left to decode) whatever is in flight is read.
        keep = int(ahead and len(self._waves) > waves_before)
        if not self._collect_waves(keep):
            return 0
        self._emit_first_tokens()
        if starved:
            # an eviction requeues a request with the tokens it was
            # handed: everything in flight is read first. AFTER the
            # dispatch loop: a priority victim was in this wave —
            # evicting it first would drop the token it just produced
            # (starved lanes were never in it, so they don't care about
            # the ordering)
            if not self._collect_waves():
                return 0
            self._preempt_starved(starved)
        with RecordEvent("serving/round_tail", pid=self.trace_pid) as ev:
            pending = self._round_tail()
        self._phase("round_tail", ev)
        return pending

    def _round_tail(self):
        """What a round does after its tokens are out: the pool sample,
        the SLO verdict, the history sampler, the alert rules, the
        chrome counter track. Returns the requests still in flight or
        queued."""
        worked = self._round_worked
        pool = getattr(self.engine, "block_pool", None)
        if pool is not None and worked:
            # pool sample per WORKING round (idle spins don't dilute the
            # integral — same cadence discipline as on_wave's slot
            # occupancy): utilization + prefix tallies ride the snapshot
            self.metrics.on_blocks(pool.used, pool.usable)
            self.metrics.on_prefix_totals(pool.prefix_hits,
                                          pool.prefix_misses,
                                          pool.evictions)
        if self.slo_engine is not None and worked:
            # re-evaluate once per WORKING round: gauges track live,
            # transitions journal, /healthz serves the cached verdict
            self.slo_engine.evaluate()
        if worked:
            # the history sampler and the anomaly detectors run on the
            # same working-round cadence (idle spins sample nothing:
            # they would flood the ladders with flat lines and dilute
            # every EWMA baseline toward the idle value)
            if self._sampler is not None:
                self._sampler.maybe_sample()
            if self._alerts is not None:
                self._alerts.evaluate()
        # chrome-trace counter track: occupancy/queue depth over time,
        # on the same timeline as the decode-wave slices
        if profiler.trace_enabled():
            profiler.emit_trace_event({
                "ph": "C", "name": "serving/slots", "cat": "serving",
                "pid": self.trace_pid,
                "args": {"active": self.in_flight(),
                         "queued": self.queue_depth()}})
        return self.in_flight() + self.queue_depth()

    def in_flight(self):
        return sum(1 for r in self._slot_req if r is not None)

    @property
    def draining(self):
        return self._draining

    @property
    def degraded(self):
        return self._degraded

    # ------------------------------------------------------- graceful stop
    def drain(self):
        """Stop admitting new work: requests already accepted (queued or
        in a slot) run to completion; new submit()s are shed with
        finish_reason "rejected". /healthz reports "draining". Keep
        driving step()/run() until it returns 0 to finish the accepted
        work."""
        with self._lock:
            self._draining = True
            if not self._degraded:     # degraded is sticky: see _degrade
                self.engine.set_health_state("draining")

    def shutdown(self, max_waves=None):
        """Graceful shutdown: drain(), drive the wave loop until every
        accepted request resolves, then stop the engine's metrics
        exporter. Returns the number of waves run. Safe alongside a
        concurrent run()/step() driver — rounds serialize on
        `_wave_lock`, so the two loops cooperate on draining rather
        than racing the engine."""
        self.drain()
        waves = self.run(max_waves=max_waves)
        self.engine.stop_metrics_server()
        return waves

    def run(self, drain=True, max_waves=None):
        """Drive step() until the queue and all slots drain (or max_waves
        hit). Producer threads may keep submit()ing while this runs."""
        waves = 0
        while self.step():
            waves += 1
            if max_waves is not None and waves >= max_waves:
                break
        return waves

    # ---------------------------------------------------------- conveniences
    def generate(self, prompt, **kw):
        """Blocking single-request convenience (the create_llm_predictor
        surface): submit, drain, return the generated token list."""
        req = self.submit(prompt=prompt, **kw)
        while not req.done:
            self.step()
        return req.output_tokens
