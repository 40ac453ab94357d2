"""Serving metrics: TTFT distribution, token throughput, queue depth and
slot occupancy.

Two sinks, one recording path:

  * the typed telemetry registry (utils/telemetry.py) — labeled
    counters/gauges plus BOUNDED exponential-bucket histograms for
    TTFT/latency, rendered on /metrics and in JSON snapshots. The
    histograms replaced the raw per-request sample lists, so a
    long-running engine's memory no longer grows with request count;
    p50/p99 come from bucket interpolation.
  * the legacy flat `utils.monitor` stat registry (`serving_*` keys),
    kept so `monitor.all_stats()` callers see the same counters.

`ServingMetrics.snapshot()` keys are byte-compatible with the PR-1
shape (`benchmark/kinds/_serving.py` hands every number of it to
the benchmark's readers).
"""
import threading

from ..utils import flight_recorder, monitor, telemetry

#: the four scheduler-round phases every round is attributed to —
#: admission (queue pop + block alloc + staging), prefill_chunk (one
#: prefill program per mid-admission slot), decode_wave (the batched
#: wave INCLUDING its fused in-program sampling tail), host_dispatch
#: (token emit + callbacks + retirement). Keys of snapshot()'s
#: `phase_seconds`, next to the finer ones the scheduler and the engine
#: add (docs/observability.md "Spans"): `round` is the total of every
#: round that had work; a dotted key nests inside its parent
#: (`wave.blocks` / `wave.stage` / `wave.dispatch` / `wave.wait` inside
#: decode_wave, `prefill.stage` / `prefill.dispatch` /
#: `prefill.first_token` inside prefill_chunk); `token_masks` and
#: `round_tail` are scheduler work outside the four; `state.reset` is
#: the zeroing of a slot's recurrent record inside admission (models
#: with slot state only); `unfed` (seconds from the host's seeing the
#: device with nothing queued, at a read or a round's end that finds the
#: newest program finished, to the next program dispatch) overlaps the
#: others. A
#: round reads the wave BEFORE the one it dispatched, so `wave.wait` and
#: `prefill.first_token` are seconds the host waited with work queued
#: behind what it read, except in a round that holds the pipeline empty.
PHASES = ("admission", "prefill_chunk", "decode_wave", "host_dispatch")

# legacy stat-registry keys (monitor.stat_get / all_stats)
REQUESTS_SUBMITTED = "serving_requests_submitted"
REQUESTS_COMPLETED = "serving_requests_completed"
REQUESTS_REJECTED = "serving_requests_rejected"
TOKENS_GENERATED = "serving_tokens_generated"
PREFILLS = "serving_prefills"
DECODE_WAVES = "serving_decode_waves"
QUEUE_DEPTH_PEAK = "serving_queue_depth_peak"
# NOTE: `serving_queue_depth` / `serving_slots_active` are TYPED gauges
# only — the monitor keys of the same name used to ride along in every
# exposition just to be shadowed by the typed series (the documented
# legacy-monitor wart); ServingMetrics.snapshot() keys are unchanged.

# typed registry metrics (docs/observability.md catalogs these)
_REQUESTS = telemetry.counter(
    "serving_requests_total", "Requests by lifecycle event",
    labelnames=("state",))
_TOKENS = telemetry.counter(
    "serving_tokens_generated_total", "Generated tokens streamed to hosts")
_PREFILLS = telemetry.counter(
    "serving_prefills_total", "Prefill program invocations (admissions)")
_WAVES = telemetry.counter(
    "serving_decode_waves_total", "Batched decode waves executed")
_QUEUE_DEPTH = telemetry.gauge(
    "serving_queue_depth", "Requests waiting for a slot")
_SLOTS_ACTIVE = telemetry.gauge(
    "serving_slots_active", "Slots decoding in the latest wave")
_TTFT = telemetry.histogram(
    "serving_ttft_seconds", "Time from submit to first token",
    buckets=telemetry.DEFAULT_LATENCY_BUCKETS)
_LATENCY = telemetry.histogram(
    "serving_request_latency_seconds", "Time from submit to completion",
    buckets=telemetry.DEFAULT_LATENCY_BUCKETS)
# inter-token latency needs finer buckets than TTFT: a healthy decode
# wave is sub-millisecond-to-tens-of-ms, right at the default latency
# buckets' floor (these span 100us..~3.3s)
TPOT_BUCKETS = telemetry.exponential_buckets(0.0001, 2.0, 16)
_TPOT = telemetry.histogram(
    "serving_tpot_seconds",
    "Inter-token latency (gap between consecutive streamed tokens of "
    "one request; the first token's latency is TTFT, not TPOT)",
    buckets=TPOT_BUCKETS)
# resilience counters (the chaos harness proves each one moves —
# scripts/chaos_serving.py; kinds are a small closed set)
_FAULTS = telemetry.counter(
    "serving_faults_total",
    "Faults handled by the resilience layer (isolated, retried, or "
    "degraded — never a stack trace to the caller)",
    labelnames=("kind",))
_REJECTED = telemetry.counter(
    "serving_rejected_total",
    "Requests shed at admission: queue full, draining, degraded, or "
    "invalid prompt")
_WAVE_RETRIES = telemetry.counter(
    "serving_wave_retries_total",
    "Decode-wave retry attempts after a transient wave failure")
_CALLBACK_ERRORS = telemetry.counter(
    "serving_callback_errors_total",
    "Exceptions raised by client on_token callbacks (contained "
    "per-request, never poisoning the shared wave loop)")
# paged KV cache (serving/paged): pool pressure + prefix-cache efficacy
_CACHE_BLOCKS_USED = telemetry.gauge(
    "serving_cache_blocks_used",
    "KV-cache blocks currently referenced by live requests (paged "
    "engine block pool)")
_CACHE_BLOCKS_TOTAL = telemetry.gauge(
    "serving_cache_blocks_total",
    "Usable KV-cache blocks in the paged engine's pool (scratch "
    "excluded) — used/total is the utilization that replaces dense "
    "slot occupancy")
_PREFIX_HITS = telemetry.counter(
    "serving_prefix_cache_hits_total",
    "Full prompt blocks served from the hash-based prefix cache "
    "(shared system prompts dedupe onto the same physical blocks)")
_PREFIX_MISSES = telemetry.counter(
    "serving_prefix_cache_misses_total",
    "Full prompt blocks that had to be computed by prefill (no cached "
    "block with a matching chain hash)")
_PREFIX_EVICTIONS = telemetry.counter(
    "serving_prefix_cache_evictions_total",
    "Blocks the pool handed out by dropping a cached prefix hash (no "
    "free block without one was left): 0 while plain blocks last, one "
    "per allocated block once every free block is cached")
_MHC_ROWS = telemetry.counter(
    "serving_mhc_rows_mixed_total",
    "Token rows put through the residual-stream maps of a model with "
    "more than one residual stream (hc_mult > 1): tokens staged into "
    "chunks and waves times the sub-layers each passes (0 for a model "
    "with one stream)")
# speculative decoding (serving/paged SpeculativePagedEngine): the
# draft-k/verify-once wave's economics — acceptance rate IS the
# speedup knob (mean accepted/wave > 0 means decode rounds per
# generated token dropped below 1:1)
_SPEC_PROPOSED = telemetry.counter(
    "serving_spec_tokens_proposed_total",
    "Draft tokens proposed to the verify wave (speculative decoding; "
    "per-lane spec_len after horizon/token-mask clamps)")
_SPEC_ACCEPTED = telemetry.counter(
    "serving_spec_tokens_accepted_total",
    "Draft tokens accepted by the exact acceptance-rejection tail "
    "(the bonus/correction token per lane is not a draft's and is "
    "never counted here)")
_SPEC_RATE = telemetry.gauge(
    "serving_spec_acceptance_rate",
    "Cumulative accepted/proposed ratio of the speculative decode "
    "path (draft-model quality at the currently served traffic)")


def record_block_usage(used, total):
    """Export the paged pool's occupancy (called by BlockPool on every
    alloc/release)."""
    _CACHE_BLOCKS_USED.set(int(used))
    _CACHE_BLOCKS_TOTAL.set(int(total))


def record_prefix_lookup(hits, misses):
    """Count one admission's prefix-cache outcome, block-granular."""
    if hits:
        _PREFIX_HITS.inc(int(hits))
    if misses:
        _PREFIX_MISSES.inc(int(misses))


def record_prefix_evictions(n):
    """Count the cached blocks one allocation evicted."""
    _PREFIX_EVICTIONS.inc(int(n))


def record_callback_error(request, error):
    """Count + journal a contained client-callback exception (called
    from Request._emit — client bugs stay visible without breaking the
    per-request isolation that swallows them)."""
    _CALLBACK_ERRORS.inc()
    rec = flight_recorder.get_recorder()
    if rec is not None:
        rec.fault(kind="callback_error", action="contained",
                  request_id=request.request_id, error=repr(error))


#: what `PagedServingEngine.take_model_counts` counts, and the snapshot
#: carries under the same names (0 for a model that has none of it)
MODEL_COUNTS = ("state_resets", "moe_picks", "mla_rows_attended",
                "mla_rows_expanded", "prefill_tokens", "prefill_chunks",
                "ssm_records_stepped", "ssm_lanes_stepped",
                "mhc_rows_mixed")


class ServingMetrics:
    """Per-engine aggregation on top of the process-wide sinks: bounded
    TTFT/latency histograms (for this instance's p50/p99) and the
    occupancy integral (active-slot-waves / total-slot-waves)."""

    def __init__(self, num_slots):
        self.num_slots = num_slots
        self._lock = threading.Lock()
        # instance-local (unregistered) histograms: a fresh Scheduler
        # gets fresh percentiles while the registered process-wide
        # histograms keep accumulating for /metrics
        self._ttft = telemetry.Histogram(
            "serving_ttft_seconds", buckets=telemetry.DEFAULT_LATENCY_BUCKETS)
        self._latency = telemetry.Histogram(
            "serving_request_latency_seconds",
            buckets=telemetry.DEFAULT_LATENCY_BUCKETS)
        self._tpot = telemetry.Histogram(
            "serving_tpot_seconds", buckets=TPOT_BUCKETS)
        self._active_slot_waves = 0
        self._total_slot_waves = 0
        # waves put on the device's queue, and those of them that went
        # out while the wave before was still unread (the scheduler's
        # one-deep pipeline was full)
        self._waves_dispatched = 0
        self._waves_dispatched_ahead = 0
        self._tokens = 0
        self._queue_peak = 0
        self._first_token_time = None
        self._last_token_time = None
        self._faults = {}
        self._rejected = 0
        self._wave_retries = 0
        # paged-pool tracking (None until a paged engine reports):
        # utilization is the block-wave integral — the paged analog of
        # slot occupancy — and the prefix tallies are deltas of the
        # pool's monotonic counters over THIS instance's lifetime
        self._block_used_waves = 0
        self._block_total_waves = 0
        self._prefix_base = None
        self._prefix_last = None
        # block-table entries the paged-attention core visited in this
        # instance's decode waves, and the entries their tables held
        self._pages_visited = 0
        self._pages_spanned = 0
        # grid steps its kernel ran in the waves and the prefill chunks,
        # and those of them that scored pages (a layer)
        self._steps_run = 0
        self._steps_visited = 0
        # what a model with slot state, experts or a latent cache was
        # staged (0 for any other): slot records zeroed at admission,
        # records the waves stepped and lanes that decoded in them,
        # (token, expert) pairs routed, latent rows attended by the
        # waves and expanded by the chunks, the chunks and their
        # prompt tokens, token rows put through the maps of a
        # multi-stream residual path
        self._model_counts = dict.fromkeys(MODEL_COUNTS, 0)
        # [V] rows and [S, V] matrices of logit bias the engine sent to
        # the device (0 while no request brings a bias: the engine
        # keeps a zero row and a zero matrix there)
        self._bias_uploads = 0
        # per-phase wall time (seconds, folded in once per scheduler
        # round)
        self._phase_seconds = {}
        # speculative decoding tallies (0 on non-speculative engines)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_waves = 0

    # ---------------------------------------------------------- recording
    def on_submit(self):
        monitor.stat_add(REQUESTS_SUBMITTED)
        _REQUESTS.labels(state="submitted").inc()

    def on_reject(self):
        monitor.stat_add(REQUESTS_REJECTED)
        _REQUESTS.labels(state="rejected").inc()
        _REJECTED.inc()
        with self._lock:
            self._rejected += 1

    def on_fault(self, kind):
        _FAULTS.labels(kind=kind).inc()
        with self._lock:
            self._faults[kind] = self._faults.get(kind, 0) + 1

    def on_wave_retry(self):
        _WAVE_RETRIES.inc()
        with self._lock:
            self._wave_retries += 1

    def on_prefill(self):
        monitor.stat_add(PREFILLS)
        _PREFILLS.inc()

    def on_wave(self, n_active, ahead=False):
        """One dispatched decode wave over `n_active` lanes; `ahead`:
        it went out before the previous wave's tokens were read."""
        monitor.stat_add(DECODE_WAVES)
        _WAVES.inc()
        _SLOTS_ACTIVE.set(int(n_active))
        with self._lock:
            self._active_slot_waves += int(n_active)
            self._total_slot_waves += self.num_slots
            self._waves_dispatched += 1
            self._waves_dispatched_ahead += bool(ahead)

    def on_spec(self, proposed, accepted):
        """One speculative wave's draft economics (scheduler-reported:
        proposed = sum of per-lane spec_len, accepted = draft tokens the
        acceptance kept). Updates the process-wide counters and the
        cumulative acceptance-rate gauge."""
        if proposed:
            _SPEC_PROPOSED.inc(int(proposed))
        if accepted:
            _SPEC_ACCEPTED.inc(int(accepted))
        with self._lock:
            self._spec_proposed += int(proposed)
            self._spec_accepted += int(accepted)
            self._spec_waves += 1
            if self._spec_proposed:
                _SPEC_RATE.set(self._spec_accepted / self._spec_proposed)

    def on_phases(self, seconds):
        """Fold one scheduler round's {phase: seconds} (the keys the
        `PHASES` comment lists) into the accumulated split that
        snapshot() reports: one lock a round."""
        with self._lock:
            acc = self._phase_seconds
            for phase, s in seconds.items():
                acc[phase] = acc.get(phase, 0.0) + s

    def on_pages(self, visited, spanned, steps_run, steps_visited):
        """One round's decode waves: table entries inside the lanes'
        `nn.paged_attention.attended_pages`, and all of them (lanes x
        blocks per lane); the grid steps the attention kernel ran in
        the round's waves and chunks, and those that scored pages
        (`nn.paged_attention.count_steps`, a layer)."""
        with self._lock:
            self._pages_visited += int(visited)
            self._pages_spanned += int(spanned)
            self._steps_run += int(steps_run)
            self._steps_visited += int(steps_visited)

    def on_model_counts(self, counts):
        """One round's `PagedServingEngine.take_model_counts()`."""
        with self._lock:
            for k, v in counts.items():
                self._model_counts[k] += int(v)
        if counts.get("mhc_rows_mixed"):
            _MHC_ROWS.inc(int(counts["mhc_rows_mixed"]))

    def on_bias_uploads(self, n):
        """One round's `ServingEngine.take_bias_uploads()`."""
        with self._lock:
            self._bias_uploads += int(n)

    def on_queue_depth(self, depth):
        monitor.stat_max(QUEUE_DEPTH_PEAK, int(depth))  # process-wide peak
        _QUEUE_DEPTH.set(int(depth))
        with self._lock:
            self._queue_peak = max(self._queue_peak, int(depth))

    def on_blocks(self, used, total):
        """One scheduling round's paged-pool occupancy sample."""
        with self._lock:
            self._block_used_waves += int(used)
            self._block_total_waves += int(total)

    def on_prefix_totals(self, hits, misses, evictions):
        """Track the pool's monotonic prefix counters; snapshot reports
        the delta across this metrics instance (per-load-point rates in
        the bench, which builds a fresh Scheduler per point)."""
        totals = (int(hits), int(misses), int(evictions))
        with self._lock:
            if self._prefix_base is None:
                self._prefix_base = totals
            self._prefix_last = totals

    def on_token(self, t_now, prev_t=None):
        """One streamed token; `prev_t` is the SAME request's previous
        token timestamp (None for its first token), so the gap is a
        TPOT sample — per-request inter-token latency, not the
        engine-wide token cadence."""
        monitor.stat_add(TOKENS_GENERATED)
        _TOKENS.inc()
        if prev_t is not None:
            gap = t_now - prev_t
            self._tpot.observe(gap)
            _TPOT.observe(gap)
        with self._lock:
            self._tokens += 1
            if self._first_token_time is None:
                self._first_token_time = t_now
            self._last_token_time = t_now

    def on_complete(self, request):
        monitor.stat_add(REQUESTS_COMPLETED)
        _REQUESTS.labels(state="completed").inc()
        if request.ttft is not None:
            self._ttft.observe(request.ttft)
            _TTFT.observe(request.ttft)
        if request.latency is not None:
            self._latency.observe(request.latency)
            _LATENCY.observe(request.latency)

    # ---------------------------------------------------------- reporting
    def snapshot(self):
        """Point-in-time summary dict (the bench script serializes this).
        Keys are byte-compatible with the raw-sample-list era; the
        percentiles are now bucket-interpolated estimates."""
        with self._lock:
            active, total = self._active_slot_waves, self._total_slot_waves
            tokens = self._tokens
            first_t, last_t = (self._first_token_time,
                               self._last_token_time)
            span = (None if first_t is None or last_t is None
                    else last_t - first_t)
            queue_peak = self._queue_peak
            faults = dict(self._faults)
            rejected, wave_retries = self._rejected, self._wave_retries
            blk_used, blk_total = (self._block_used_waves,
                                   self._block_total_waves)
            if self._prefix_base is None:
                p_hits = p_misses = p_evictions = 0
            else:
                p_hits, p_misses, p_evictions = (
                    last - base for last, base in
                    zip(self._prefix_last, self._prefix_base))
            phase_seconds = dict(self._phase_seconds)
            pages_v, pages_s = self._pages_visited, self._pages_spanned
            steps_r, steps_v = self._steps_run, self._steps_visited
            model_counts = dict(self._model_counts)
            bias_uploads = self._bias_uploads
            spec_p, spec_a = self._spec_proposed, self._spec_accepted
            spec_w = self._spec_waves
            waves, waves_ahead = (self._waves_dispatched,
                                  self._waves_dispatched_ahead)
        return {
            "requests_completed": self._latency.count(),
            "tokens_generated": tokens,
            "tokens_per_s": (tokens / span if span else None),
            "ttft_p50_s": self._ttft.percentile(50),
            "ttft_p99_s": self._ttft.percentile(99),
            "latency_p50_s": self._latency.percentile(50),
            "latency_p99_s": self._latency.percentile(99),
            "slot_occupancy": (active / total if total else 0.0),
            "queue_depth_peak": queue_peak,   # this instance, not the
                                              # process-wide monitor stat
            # resilience tallies (this instance): shedding onset vs
            # offered load shows up in bench rows through these
            "faults": faults,
            "rejected": rejected,
            "wave_retries": wave_retries,
            # paged KV pool (None/0 on a dense engine): utilization is
            # the block-wave integral — HBM held by ACTUAL tokens, the
            # number that replaces dense slot occupancy
            "block_utilization": (blk_used / blk_total if blk_total
                                  else None),
            "prefix_hits": p_hits,
            "prefix_misses": p_misses,
            "prefix_hit_rate": (p_hits / (p_hits + p_misses)
                                if p_hits + p_misses else None),
            # blocks the pool handed out by dropping a cached hash
            "prefix_evictions": p_evictions,
            # fleet PR: raw span endpoints (monotonic clock), so a
            # multi-replica rollup can compute the FLEET's first-to-
            # last-token span (max(last) - min(first)) and keep its
            # tokens/s denominator comparable with single-engine rows
            "first_token_time": first_t,
            "last_token_time": last_t,
            # observability PR: inter-token latency (the second half of
            # the TTFT/TPOT request-latency decomposition) and the
            # per-round phase split
            "tpot_p50_s": self._tpot.percentile(50),
            "tpot_p99_s": self._tpot.percentile(99),
            "phase_seconds": phase_seconds,
            # speculative decoding (perf PR): 0/None on engines without
            # a draft model. accepted_per_wave is the headline number —
            # > 0 means each wave nets more than one token per lane
            "spec_tokens_proposed": spec_p,
            "spec_tokens_accepted": spec_a,
            "spec_acceptance_rate": (spec_a / spec_p if spec_p
                                     else None),
            "spec_accepted_per_wave": (spec_a / spec_w if spec_w
                                       else None),
            # how much of its block tables the paged-attention core
            # walks in the decode waves (0 / 0 on a dense engine)
            "paged_pages_visited": pages_v,
            "paged_pages_spanned": pages_s,
            # the grid steps its kernel ran in waves and chunks, and
            # those of them that fetched and scored pages (a layer)
            "paged_steps_run": steps_r,
            "paged_steps_visited": steps_v,
            # slot records zeroed and (token, expert) pairs routed, for
            # a model that has either (serving/paged/engine.py)
            **model_counts,
            # bias rows and matrices uploaded (serving/engine.py)
            "bias_uploads": bias_uploads,
            # the interpreter's collector: seconds it held the process and
            # its collections (process totals; a reader takes the delta)
            **telemetry.gc_totals(),
            # decode waves dispatched, and those dispatched before the
            # wave before them was read (serving/scheduler.py: a round
            # dispatches first and reads the last wave afterwards)
            "waves_dispatched": waves,
            "waves_dispatched_ahead": waves_ahead,
        }
