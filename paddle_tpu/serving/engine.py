"""ServingEngine: slot-based continuous batching over the causal-LM
decode paths (nlp/gpt.py, nlp/llama.py).

The engine owns `num_slots` decode slots backed by ONE batched KV cache
[num_slots, kv_heads, max_len, head_dim] per layer and exactly TWO
compiled programs, both with fully static shapes so XLA compiles each
once for the life of the engine (compile-once discipline — the whole
request stream reuses the same executable):

  * decode wave — one token for every slot at once. Per-slot state rides
    as vectors: position [S] (each slot at its own depth — decode_step's
    position-vector path), active mask [S] (retired slots are frozen
    with `where`, their lanes compute and are discarded; that is the
    price of fixed shapes and it is the right trade in the
    memory-bandwidth-bound decode regime, where the [S,...] cache stream
    dominates and a masked lane adds nothing).
  * prefill — one slot's prompt, padded to a fixed bucket, through the
    model's prompt-phase forward (`prefill`), then the slot's cache
    region is spliced into the batched cache with dynamic_update_slice
    at a TRACED slot index (so one program serves every slot). The
    frontier logits yield the request's first token: TTFT is paid at
    admission, not at the next wave.

Retire-and-refill happens BETWEEN waves by rewriting the per-slot
vectors — in-flight decodes never stall and never recompile.

Slot bookkeeping is split by who decides it. What the host decides
(active, the sampling knobs, block tables) is host-authoritative: numpy
arrays of the programs' own dtypes, written in place between waves and
packed into a wave's one small upload. What the programs decide, each
lane's last token and next position, lives ON THE DEVICE: two int32[S]
arrays carried from program to program, donated, as the caches and the
key are. A wave reads and returns them; a prompt's last chunk writes
the first token it selected and the prompt's length into its slot's
row. So the host is not on the token's path: `dispatch_wave` enqueues a
wave and returns a ticket, `collect_wave` reads that wave's tokens, and
a scheduler may enqueue the next wave (and the next chunks) before it
collects the last one. A token is therefore read one program after it
is made. The host keeps `slot_pos` as a mirror by counting (a lane in a
wave advances by one; a position never depends on a token's value) and
`slot_tok` as what it last read.

A stage (`serving/prefill/stage`, `serving/wave/stage`) puts nothing on
the device's queue: nothing it builds is a `jnp` value (each
`jnp.int32(..)` or `jnp.asarray(<list>)` is a tiny device program of its
own, run while the device waits), and the PRNG key is split INSIDE the
program, which returns the next key beside its outputs. A program's
small arguments travel as ONE numpy array, int32, packed afresh by the
stage (`pack_lanes` for a wave, `pack_prompt` for a prefill; the float32
knobs go by their bits): every host argument of a jitted call is a
transfer of its own, 0.16 ms of the call on a v5e whatever its size
(PERF.md, PR 32), and a chunk had nine of them. What does not change
stays on the device: the bias row and matrix of a server without biases.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..utils import chaos, telemetry
from ..utils.profiler import RecordEvent

HEALTH_STATES = ("ok", "degraded", "draining")


def _infer_cache_dtype(params):
    """Majority element dtype of the params — a bf16 model gets bf16 KV
    caches (halves the per-token HBM stream that bounds decode), an f32
    model keeps f32 (same policy as nlp.gpt.generate's cached path)."""
    # normalize to np.dtype keys: leaf.dtype is an np.dtype, and probing
    # a dict of those with the jnp scalar TYPE hashes differently even
    # though == compares true
    f32 = np.dtype(jnp.float32)
    floats = {np.dtype(jnp.bfloat16), np.dtype(jnp.float16), f32}
    counts = {}
    for leaf in jax.tree_util.tree_leaves(params):
        dt = np.dtype(leaf.dtype)
        if dt in floats:
            counts[dt] = counts.get(dt, 0) + int(np.prod(leaf.shape))
    low = {d: c for d, c in counts.items() if d != f32}
    if low and sum(low.values()) > counts.get(f32, 0):
        return max(low, key=low.get)
    return jnp.float32


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


#: a wave program's small arguments, one int32 row a lane: the lane's
#: block-table row (paged engines; no column on a dense one), then these
#: columns: what the host decides. A lane's token and position are not
#: here: they stay on the device (the module comment). `spec_len` is the
#: speculative engine's (0 elsewhere); `temp` and `top_p` are float32,
#: carried by their bits.
LANE_FIELDS = ("active", "sample", "top_k", "poison", "spec_len", "temp",
               "top_p")
#: a prefill program's small arguments, one int32 vector: the slot's
#: table row (paged engines), the chunk's tokens, then these. `start` is
#: the chunk's offset in the prompt, `valid` its real tokens (the dense
#: bucket's prompt length), `frontier` the position whose logits pick
#: the first token, `last` whether this is the prompt's last chunk (the
#: program then arms the slot's lane: `arm_lane`).
PROMPT_FIELDS = ("start", "valid", "frontier", "slot", "last", "sample",
                 "top_k", "temp", "top_p")
_FLOAT_FIELDS = ("temp", "top_p")
_FLAG_FIELDS = ("active", "sample", "poison", "last")


def _as_bits(x):
    """float32 value(s) as the int32 of the same bits (host side)."""
    return np.asarray(x, np.float32).view(np.int32)


def pack_lanes(tables=None, **columns):
    """One wave's small arguments as one fresh int32 array
    [S, nblk + len(LANE_FIELDS)]: `tables` [S, nblk] or None, and a
    vector [S] for every name in LANE_FIELDS."""
    vectors = [_as_bits(columns[f]) if f in _FLOAT_FIELDS else columns[f]
               for f in LANE_FIELDS]
    nblk = 0 if tables is None else tables.shape[1]
    block = np.empty((len(vectors[0]), nblk + len(LANE_FIELDS)), np.int32)
    if nblk:
        block[:, :nblk] = tables
    for i, vec in enumerate(vectors):
        block[:, nblk + i] = vec
    return block


def pack_prompt(tokens, table=None, **scalars):
    """One prefill call's small arguments as one fresh int32 vector
    [nblk + C + len(PROMPT_FIELDS)]: the slot's `table` row or None, the
    chunk's `tokens` [C], and a value for every name in PROMPT_FIELDS."""
    tail = [_as_bits(scalars[f]) if f in _FLOAT_FIELDS else scalars[f]
            for f in PROMPT_FIELDS]
    lead = () if table is None else (table,)
    return np.concatenate([*lead, tokens, np.asarray(tail, np.int32)],
                          dtype=np.int32)


def _unpack(fields, columns):
    """{name: value} of a packed argument's named columns, inside a
    program: flags as bool, the float32 knobs back from their bits."""
    out = dict(zip(fields, columns))
    for f in _FLOAT_FIELDS:
        out[f] = jax.lax.bitcast_convert_type(out[f], jnp.float32)
    for f in _FLAG_FIELDS:
        if f in out:
            out[f] = out[f] != 0
    return out


def unpack_lanes(block):
    """(tables [S, nblk], {name: [S] vector}) of a `pack_lanes` array."""
    nblk = block.shape[1] - len(LANE_FIELDS)
    return block[:, :nblk], _unpack(
        LANE_FIELDS, [block[:, nblk + i] for i in range(len(LANE_FIELDS))])


def unpack_prompt(block, chunk_len):
    """(table row [nblk], tokens [chunk_len], {name: scalar}) of a
    `pack_prompt` vector."""
    nblk = block.shape[0] - chunk_len - len(PROMPT_FIELDS)
    tail = block[nblk + chunk_len:]
    return block[:nblk], block[nblk:nblk + chunk_len], _unpack(
        PROMPT_FIELDS, [tail[i] for i in range(len(PROMPT_FIELDS))])


def _filter_top_k_top_p(lo, top_k, top_p):
    """Per-ROW top-k / nucleus filtering over already-temperature-scaled
    logits [S, V] with traced per-slot knobs top_k [S] int32 (<=0 = off)
    and top_p [S] f32 (>=1 = off). Same SEQUENTIAL semantics as
    nn.decode.top_k_top_p_filtering — top-k first (kth-value threshold,
    ties kept), then top-p over the RENORMALIZED top-k survivors (keep
    the smallest prefix whose cumulative prob reaches p, best token
    always kept) — vectorized so every slot carries its own knobs in
    ONE compiled program, with one sort serving both stages. Disabled
    rows pass through bitwise-identical (`where(True, lo, _)` is the
    identity), which is what keeps the pre-existing fixed-seed sampling
    streams unchanged."""
    v = lo.shape[-1]
    sort_idx = jnp.argsort(-lo, axis=-1)
    sorted_lo = jnp.take_along_axis(lo, sort_idx, axis=-1)
    kth = jnp.take_along_axis(
        sorted_lo, (jnp.clip(top_k, 1, v) - 1)[:, None], axis=-1)
    in_k = (sorted_lo >= kth) | (top_k <= 0)[:, None]   # sorted space
    # nucleus over the top-k-FILTERED distribution (softmax of the
    # masked row renormalizes it), exactly like applying the reference
    # filters back to back
    probs = jax.nn.softmax(
        jnp.where(in_k, sorted_lo, jnp.float32(-1e9)), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (((cum - probs) < top_p[:, None])
                   | (top_p >= 1.0)[:, None]).at[:, 0].set(True)
    keep_sorted &= in_k
    inv = jnp.argsort(sort_idx, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, lo, jnp.float32(-1e9))


def _if_any_samples(sample, draw, greedy):
    """`draw()` where some lane samples, `greedy` as it stands where none
    does: the filter sorts and gathers the whole `[lanes, vocab]` matrix
    twice (at 128 x 131072 that was 470 ms of a 500 ms wave on a v5e,
    PERF.md PR 27), and a wave of greedy lanes has no use for it. Both
    branches are in the one compiled program; a lane's token does not
    depend on which ran."""
    return jax.lax.cond(jnp.any(sample), draw, lambda: greedy)


def _select_wave_tokens(lo, tok, pos, active, sample, temps, top_k,
                        top_p, bias, poison, key):
    """The decode wave's token-selection tail, shared by the dense AND
    paged programs — the paged/dense token-parity contract depends on
    this math staying identical, so it lives exactly once. The
    speculative verify tail reuses the same pieces position-by-position
    (engine subclasses never reimplement the selection math).

    Scenario surface: `bias` [S, V] is the per-request logit-bias /
    token-mask hook (0 = untouched; -1e9 = forbidden — constrained/JSON
    decoding uploads a fresh mask row per wave), `top_k`/`top_p` are
    per-slot sampling knobs applied after temperature. Greedy lanes take
    argmax over the BIASED logits (top-k/p cannot change an argmax).

    poison is all-False in production; the chaos harness sets a lane to
    inject NaN logits WITHOUT a second compiled program. The fused
    non-finite sentinel (the jit.TrainStep isfinite pattern) rides home
    as one [S] bool with the tokens — no extra device sync; a poisoned
    lane is frozen in-program and retired by the scheduler with
    finish_reason "error". Inactive (or poisoned) lanes keep their
    token and position via where — fixed shapes, no recompiles."""
    lo = jnp.where(poison[:, None], jnp.float32(jnp.nan), lo + bias)
    finite = jnp.all(jnp.isfinite(lo), axis=-1)
    greedy = jnp.argmax(lo, axis=-1).astype(jnp.int32)

    def draw():
        scaled = lo / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(
            key, _filter_top_k_top_p(scaled, top_k, top_p),
            axis=-1).astype(jnp.int32)
        return jnp.where(sample, sampled, greedy)

    nxt = _if_any_samples(sample, draw, greedy)
    ok = active & finite
    nxt = jnp.where(ok, nxt, tok)
    new_pos = jnp.where(ok, pos + 1, pos)
    return nxt, new_pos, finite


def _select_first_token(lo, sample, temp, top_k, top_p, bias, key):
    """The prefill programs' first-token selection ([V] frontier logits
    -> token), shared by the dense AND paged chunked programs — same
    parity contract as _select_wave_tokens: this math lives exactly
    once. Takes the admitted request's full sampling params (the first
    token must obey the same temperature/top-k/top-p/bias as the decode
    tail will)."""
    lo = lo + bias
    greedy = jnp.argmax(lo).astype(jnp.int32)

    def draw():
        scaled = (lo / jnp.maximum(temp, 1e-6))[None, :]
        return jax.random.categorical(
            key, _filter_top_k_top_p(scaled, top_k[None], top_p[None])[0]
        ).astype(jnp.int32)

    return _if_any_samples(sample, draw, greedy)


def arm_lane(lane_tok, lane_pos, a, first):
    """A prefill program's last step, shared by every engine's: on a
    prompt's last chunk the slot's lane takes the first token and the
    prompt's length (the position the next wave writes), on any other
    chunk it keeps what it had."""
    slot, n = a["slot"], a["start"] + a["frontier"] + 1
    return (lane_tok.at[slot].set(jnp.where(a["last"], first,
                                            lane_tok[slot])),
            lane_pos.at[slot].set(jnp.where(a["last"], n, lane_pos[slot])))


def wave_read(nxt, finite):
    """What the host reads of a wave, one int32[2, S] array of its own
    (the lane state beside it is donated to the next program before the
    host gets to it): the tokens, and whether each lane's logits were
    finite."""
    return jnp.stack([nxt, finite.astype(jnp.int32)])


class WaveTicket:
    """A dispatched wave that the host has not read yet.

    lanes: the slots in the wave (int array); epochs: each slot's epoch
    at dispatch (a slot retired, or armed for another request, since then
    has another, and its entry is dropped at collect); full: the slots
    whose position after this wave is at the cache horizon; read: the
    device array `wave_read` built; tokens: the result, for a wave that
    was read when it was dispatched (the speculative engine's)."""
    __slots__ = ("lanes", "epochs", "full", "read", "tokens")

    def __init__(self, lanes, epochs, full, read=None, tokens=None):
        self.lanes, self.epochs, self.full = lanes, epochs, full
        self.read, self.tokens = read, tokens


class ServingEngine:
    """Fixed-shape batched decode executor. The Scheduler decides WHICH
    request occupies which slot and when; the engine only knows slots.

    `pipelined`: a wave's tokens can be read after the next programs
    were dispatched (`dispatch_wave` returns before the wave has run).

    model: a causal LM exposing prefill / decode_step / init_cache
        (GPTForPretraining, LlamaForCausalLM).
    num_slots: concurrent sequences per wave.
    max_len: per-slot cache horizon (prompt + generated tokens).
    prefill_len: prompt padding bucket (<= max_len; default max_len).
        One bucket => one prefill compile for every prompt length.
    jit_compile=False runs both programs uncompiled per call (the
        inference Config's ir_optim=False analog) — for debugging;
        decode_compiles stays 0 on that path.
    """

    pipelined = True

    def __init__(self, model, num_slots=4, max_len=256, prefill_len=None,
                 cache_dtype=None, jit_compile=True, seed=0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.model = model
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.prefill_len = int(prefill_len or max_len)
        if self.prefill_len > self.max_len:
            raise ValueError(
                f"prefill_len {self.prefill_len} > max_len {self.max_len}")
        model.eval()
        self._params, self._buffers = model.functional_state()
        self.cache_dtype = (cache_dtype if cache_dtype is not None
                            else _infer_cache_dtype(self._params))
        # the pool (or the dense cache) and a model's slot state, waited
        # for: what the engine reserves on the device, and how long
        # reserving it takes
        pool = getattr(self, "block_pool", None)
        with telemetry.startup_span(
                "pool", num_slots=self.num_slots,
                blocks=pool.num_blocks if pool is not None else 0) as span:
            self._caches = jax.block_until_ready(self._make_caches())
            self.pool_bytes = span.ids["pool_bytes"] = sum(
                a.nbytes for a in jax.tree_util.tree_leaves(self._caches))
        # the one PRNG chain every sampled request on this engine draws
        # from — recorded (blackbox `run_start` harness / per-request
        # seed provenance) so a fresh engine built with the same seed
        # replays sampled streams token-exact
        self.seed = int(seed)
        # every program that draws takes this key, splits it as its first
        # instruction (next key, subkey: the chain an eager
        # `key, sub = jax.random.split(key)` a dispatch would walk) and
        # returns the next key, which is kept here as it comes back
        self._key = jax.random.PRNGKey(seed)

        # host-authoritative per-slot state: numpy arrays of the wave
        # program's dtypes, written in place (_arm_slot, retire_slot);
        # a wave stage packs them (pack_lanes)
        S = self.num_slots
        # vocab width: the logit-bias / token-mask rows are [V] uploads
        self.vocab_size = int(model.cfg.vocab_size)
        self.slot_active = np.zeros((S,), bool)
        # each lane's last token and next cache write position, on the
        # device: every program takes them donated and returns them
        self._lane_tok = jax.device_put(np.zeros((S,), np.int32))
        self._lane_pos = jax.device_put(np.zeros((S,), np.int32))
        # the host's mirror of the positions, by counting (set when a
        # slot is armed, +1 when its lane goes into a wave): what block
        # allocation, page counts and the horizon read
        self.slot_pos = np.zeros((S,), np.int32)
        # the token the host last READ of each lane (hand-off export)
        self.slot_tok = np.zeros((S,), np.int32)
        # bumped when a slot is armed and when it is retired: a wave or
        # a first token dispatched under another epoch than the slot
        # has at collect served a request that is gone
        self._slot_epoch = np.zeros((S,), np.int64)
        # (slot, epoch, device scalar) of the prompts whose last chunk
        # went out and whose first token is unread
        self._first_pending = []
        self.slot_sample = np.zeros((S,), bool)
        self.slot_temp = np.ones((S,), np.float32)
        # per-request scenario surface (all flow through the one shared
        # sampling tail, _select_wave_tokens): top-k / nucleus knobs and
        # a [S, V] additive logit-bias/token-mask matrix (0 = untouched,
        # -1e9 = forbidden). A slot with a DYNAMIC mask (a token_mask
        # callable refreshed per wave by the scheduler) is flagged so a
        # speculative engine clamps its draft span to 0 for that lane —
        # drafting ahead of a mask that depends on emitted tokens would
        # break exactness.
        self.slot_top_k = np.zeros((S,), np.int32)
        self.slot_top_p = np.ones((S,), np.float32)
        self.slot_dynamic_mask = np.zeros((S,), bool)
        self._slot_bias = np.zeros((S, self.vocab_size), np.float32)
        # device-resident copy of the bias matrix, re-uploaded only
        # when a row actually changes: the [S, V] upload would
        # otherwise ride EVERY wave of every engine (V can be 50k+),
        # and the common case is all-zeros. The wave programs never
        # donate it, so the same device array serves every wave. The
        # prefill programs' [V] row likewise: one resident zero row
        # serves every request whose row is all zeros.
        self._slot_bias_dev = jax.device_put(self._slot_bias)
        self._slot_bias_nonzero = [False] * S
        self._zero_bias_row = jax.device_put(
            np.zeros((self.vocab_size,), np.float32))
        # rows and matrices of bias sent to the device since the
        # scheduler last took the count (take_bias_uploads, once a
        # round): 0 for as long as no request brings a bias
        self._bias_uploads = 0

        # admissions mid-prefill (slot -> engine-specific state): the
        # scheduler admits via begin_prefill and advances one
        # prefill_step per scheduling round, so a long admission can be
        # folded BETWEEN decode waves (the dense engine completes in one
        # step; the paged engine runs one chunk per step)
        self._pending_prefill = {}
        self.last_nonfinite_slots = []
        # paged engines report lanes whose next cache write could not be
        # backed by a block (pool exhausted) — the scheduler preempts
        # them; dense engines never starve
        self.last_starved_slots = []
        self.health_state = "ok"
        # the scheduler attaches its queue-depth probe here so /healthz
        # carries real load state (a router or LB reads ONE endpoint
        # instead of scraping /metrics); 0 until a scheduler attaches
        self._queue_depth_fn = None
        # optional dict-returning probe merged into /healthz (the
        # scheduler's SLO engine reports burn-rate state this way);
        # newest wins, like the queue probe
        self._health_probe_fn = None
        # slot -> (trace_id, trace_pid): the scheduler parks the
        # admitted request's trace context so engine-internal progress
        # (the paged engine's per-chunk prefill) can emit
        # request-correlated trace events
        self._slot_trace = {}
        # chrome-trace process row of this engine's spans (the
        # scheduler's `trace_pid` setter keeps it equal to its own)
        self.trace_pid = 0
        # seconds of this engine's own phases (`wave.*`, `prefill.*`,
        # `unfed`: serving/metrics.PHASES) since the scheduler last took
        # them (take_phase_seconds, once a round). The engine is driven
        # by one thread at a time, so no lock.
        self._phase_acc = {}
        # perf_counter() at which the host saw the device out of work: a
        # blocking read returned, or the scheduler asked between reads
        # (poll_unfed), with the NEWEST program finished and none
        # dispatched since (programs run in order and each takes the
        # donated cache of the one before, so then nothing is queued; a
        # read of an older program may return with later ones still
        # running). None while a program is in flight, before the first
        # one, and after drop_unfed().
        self._unfed_since = None
        # an output of the newest program dispatched: ready when the
        # device has run everything it was given
        self._newest_out = None

        self._jit = bool(jit_compile)
        self._metrics_server = None
        self._build_programs()

    def _make_caches(self):
        return self.model.init_cache(self.num_slots, self.max_len,
                                     dtype=self.cache_dtype)

    # ---------------------------------------------------------- programs
    def _build_programs(self):
        model, L = self.model, self.max_len
        cache_dtype = self.cache_dtype

        bucket = self.prefill_len

        def decode_wave(p, b, caches, lane_tok, lane_pos, lanes, bias, key):
            key, sub = jax.random.split(key)
            _, a = unpack_lanes(lanes)
            out, _ = model.functional_call(p, b, lane_tok[:, None], caches,
                                           lane_pos, method="decode_step")
            logits, new_caches = out
            lo = _raw(logits)[:, 0, :].astype(jnp.float32)
            nxt, new_pos, finite = _select_wave_tokens(
                lo, lane_tok, lane_pos, a["active"], a["sample"],
                a["temp"], a["top_k"], a["top_p"], bias, a["poison"], sub)
            return wave_read(nxt, finite), new_caches, nxt, new_pos, key

        def prefill(p, b, caches, lane_tok, lane_pos, prompt, bias, key):
            key, sub = jax.random.split(key)
            _, tokens, a = unpack_prompt(prompt, bucket)
            # the model applies its LM head to the frontier position
            # alone (the prompt's last), not the whole padded bucket
            out, _ = model.functional_call(p, b, tokens[None, :],
                                           method="prefill", max_len=L,
                                           dtype=cache_dtype,
                                           frontier=a["frontier"])
            logits, slot_caches = out
            lo = _raw(logits)[0, 0].astype(jnp.float32)    # [V]
            first = _select_first_token(lo, a["sample"], a["temp"],
                                        a["top_k"], a["top_p"], bias, sub)
            slot = a["slot"]
            new_caches = []
            for (ck, cv), (sck, scv) in zip(caches, slot_caches):
                ck = jax.lax.dynamic_update_slice(
                    ck, _raw(sck).astype(ck.dtype), (slot, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, _raw(scv).astype(cv.dtype), (slot, 0, 0, 0))
                new_caches.append((ck, cv))
            return (first, new_caches,
                    *arm_lane(lane_tok, lane_pos, a, first), key)

        # raw closures + jit spec, kept for the compile-level audit
        # (tools/xprof lowers THE functions the engine serves — and can
        # re-jit a deliberately degraded copy for its injection test —
        # rather than a drifting reimplementation)
        self._decode_wave_fn = decode_wave
        self._prefill_fn = prefill
        self._program_donate_argnums = (2, 3, 4)
        self._prefill_donate_argnums = self._program_donate_argnums

        if self._jit:
            # donate the batched cache: the engine always replaces its
            # cache reference with the program output, so XLA may update
            # it in place — without this every wave would transiently
            # hold 2x the [S, Hkv, L, D] pair in HBM. The two lane-state
            # vectors beside it are replaced the same way.
            # instrument_jit attributes XLA compile events to these
            # labels (xla_compiles_total{function=...}) — the
            # compile-once invariant as a live metric, not just the
            # _cache_size() test assertion.
            self._decode_wave = telemetry.instrument_jit(
                jax.jit(decode_wave,
                        donate_argnums=self._program_donate_argnums),
                "serving_decode_wave")
            self._prefill = telemetry.instrument_jit(
                jax.jit(prefill,
                        donate_argnums=self._prefill_donate_argnums),
                "serving_prefill")
        else:
            self._decode_wave = decode_wave
            self._prefill = prefill

    @property
    def decode_compiles(self):
        """Number of compiled decode-wave programs (the compile-once
        invariant: stays 1 across the whole request stream)."""
        return self._decode_wave._cache_size() if self._jit else 0

    @property
    def prefill_compiles(self):
        return self._prefill._cache_size() if self._jit else 0

    # --------------------------------------------------------- telemetry
    def start_metrics_server(self, port=0, host="127.0.0.1"):
        """Expose /metrics (Prometheus), /metrics.json and /healthz on a
        stdlib-http.server background thread. port=0 picks a free port
        (read it back from the returned server's .port). Idempotent for
        matching args; asking for a DIFFERENT host/port while a server
        is live raises instead of silently keeping the old address."""
        if self._metrics_server is not None:
            srv = self._metrics_server
            if host != srv.host or port not in (0, srv.port):
                raise RuntimeError(
                    f"metrics server already running at {srv.url}; call "
                    "stop_metrics_server() before rebinding to "
                    f"{host}:{port}")
            return srv
        self._metrics_server = telemetry.MetricsServer(
            host=host, port=port, health_fn=self._health).start()
        return self._metrics_server

    def stop_metrics_server(self):
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None

    def release(self):
        """Give the caches' device memory back (the K/V or latent pool,
        slot state): the end of the engine's life, for a caller that
        keeps the process and the weights (the benchmark's referee runs
        its float32 forward beside them). Waits for the programs in
        flight, whose results the caches are."""
        leaves = [a for a in jax.tree_util.tree_leaves(self._caches)
                  if isinstance(a, jax.Array) and not a.is_deleted()]
        jax.block_until_ready(leaves)
        for a in leaves:
            a.delete()

    def attach_queue_probe(self, fn):
        """Register a zero-arg queue-depth callable (the Scheduler's) —
        folded into /healthz so load balancers and the fleet router get
        queue state without a /metrics scrape. The newest scheduler
        wins (benches build a fresh Scheduler per load point over one
        engine)."""
        self._queue_depth_fn = fn

    def attach_health_probe(self, fn):
        """Register a zero-arg dict-returning callable merged into the
        /healthz payload — the scheduler's SLO engine serves its
        burn-rate verdict through this. Newest wins, same contract as
        the queue probe."""
        self._health_probe_fn = fn

    def set_slot_trace(self, slot, trace_id, trace_pid=0):
        """Park the admitted request's trace context on its slot so
        engine-internal progress events (chunked prefill) can correlate
        to the request's chrome flow. Cleared at retirement."""
        self._slot_trace[slot] = (int(trace_id), int(trace_pid))

    # ------------------------------------------------- phases and unfed
    def _acc(self, phase, ev):
        """Add a finished span's seconds to this round's `phase`."""
        acc = self._phase_acc
        acc[phase] = acc.get(phase, 0.0) + ev.elapsed

    def _dispatched(self, phase, ev, out):
        """A program was enqueued (`ev`: its dispatch span; `out`: one of
        its outputs): the device is fed. The seconds since the host saw
        it empty are the host's: `unfed`."""
        self._acc(phase, ev)
        self._newest_out = out
        t = self._unfed_since
        if t is not None:
            self._unfed_since = None
            acc = self._phase_acc
            acc["unfed"] = acc.get("unfed", 0.0) + ev.end - t

    def _read_back(self, phase, ev):
        """A blocking read of a program's output returned (`ev`: its
        span). If the newest program dispatched has finished (it was the
        one read, or it ran while the host read an older one), nothing
        is queued on the device until the next dispatch; if it has not,
        the device still works and no unfed interval opens."""
        self._acc(phase, ev)
        self.poll_unfed(ev.end)

    def poll_unfed(self, since=None):
        """Open the unfed interval if the device has finished everything
        it was given and the host has not seen that yet: at `since`, or
        now. The scheduler asks at a round's two ends, where no read
        tells: at its end, and at its start with `since` the end of the
        round before (the last moment it saw work queued: what its
        caller did in between, the device had nothing)."""
        out = self._newest_out
        if self._unfed_since is None and out is not None \
                and out.is_ready():
            self._unfed_since = (time.perf_counter() if since is None
                                 else since)

    def drop_unfed(self):
        """Forget the open unfed interval, and the program that could
        open one: the server is empty, and an empty server is not a slow
        host."""
        self._unfed_since = self._newest_out = None

    def take_phase_seconds(self):
        """{phase: seconds} accumulated since the last call (the
        scheduler folds it into ServingMetrics once a round)."""
        acc, self._phase_acc = self._phase_acc, {}
        return acc

    def set_health_state(self, state):
        """ok | degraded | draining — the scheduler flips this so
        /healthz reports REAL engine state (a degraded engine must not
        answer "ok" to a load balancer)."""
        if state not in HEALTH_STATES:
            raise ValueError(f"health state must be one of "
                             f"{HEALTH_STATES}, got {state!r}")
        self.health_state = state

    def _health(self):
        qfn = self._queue_depth_fn
        h = {
            "status": self.health_state,
            "num_slots": self.num_slots,
            "slots_active": len(self.active_slots()),
            "queue_depth": int(qfn()) if qfn is not None else 0,
            "max_len": self.max_len,
            "decode_compiles": self.decode_compiles,
            "prefill_compiles": self.prefill_compiles,
        }
        if self._health_probe_fn is not None:
            # e.g. {"slo": {...burn-rate verdict...}} — the handler
            # already degrades the payload if a probe raises
            h.update(self._health_probe_fn() or {})
        return h

    # ------------------------------------------------------------- slots
    def free_slots(self):
        return [i for i in np.flatnonzero(~self.slot_active).tolist()
                if i not in self._pending_prefill]

    def active_slots(self):
        return np.flatnonzero(self.slot_active).tolist()

    def prefilling_slots(self):
        """Slots admitted but still mid-prefill (paged chunked prefill;
        at most one scheduling round for the dense engine)."""
        return sorted(self._pending_prefill)

    def describe(self):
        """Replay-relevant construction config. The black-box journal
        records this in `run_start` harness metadata so
        scripts/replay_incident.py can rebuild an identical engine
        (same seed => same PRNG chain => sampled streams replay
        token-exact)."""
        return {"engine": "dense", "num_slots": self.num_slots,
                "max_len": self.max_len, "prefill_len": self.prefill_len,
                "seed": self.seed,
                "cache_dtype": np.dtype(self.cache_dtype).name}

    def validate_prompt(self, prompt):
        """Admission check: the prompt must fit the prefill bucket and
        leave room to decode at least one token under the cache horizon."""
        n = len(prompt)
        if n > self.prefill_len:
            return (f"prompt length {n} exceeds the prefill bucket "
                    f"{self.prefill_len} (engine prefill_len)")
        if n + 1 > self.max_len:
            return (f"prompt length {n} leaves no room to decode under "
                    f"max_len {self.max_len}")
        return None

    def _normalize_bias(self, logit_bias):
        """One [V] float32 bias row from the request surface: None,
        a {token_id: bias} dict, or a [V] array-like (a boolean array is
        read as an ALLOWED mask: True = untouched, False = -1e9)."""
        row = np.zeros((self.vocab_size,), np.float32)
        if logit_bias is None:
            return row
        if isinstance(logit_bias, dict):
            for t, v in logit_bias.items():
                row[int(t)] = float(v)
            return row
        arr = np.asarray(logit_bias)
        if arr.shape != (self.vocab_size,):
            raise ValueError(
                f"logit bias/mask must be [{self.vocab_size}] "
                f"(vocab), got {arr.shape}")
        if arr.dtype == bool:
            return np.where(arr, 0.0, -1e9).astype(np.float32)
        return arr.astype(np.float32)

    def set_slot_bias(self, slot, bias, dynamic=True):
        """Replace the slot's logit-bias/token-mask row mid-stream — the
        scheduler's per-wave token_mask refresh (constrained decoding:
        the allowed set changes as tokens land). `dynamic` keeps the
        lane flagged so a speculative engine won't draft ahead of it."""
        self._set_bias_row(slot, self._normalize_bias(bias))
        self.slot_dynamic_mask[slot] = bool(dynamic)

    def _set_bias_row(self, slot, row, nonzero=None):
        """Write one slot's bias row (None: all zeros), invalidating the
        device copy only when the row's content actually changes
        zero-ness — a stream of bias-free requests never uploads the
        [S, V] matrix. `nonzero`: what `np.any(row)` is, where the
        caller knows."""
        if nonzero is None:
            nonzero = row is not None and bool(np.any(row))
        if nonzero or self._slot_bias_nonzero[slot]:
            self._slot_bias_dev = None
            self._slot_bias[slot] = row if nonzero else 0.0
        self._slot_bias_nonzero[slot] = nonzero

    def _arm_slot(self, slot, n, sampling):
        """Slot arming shared by the dense and paged admission paths,
        when the prompt's last chunk has been DISPATCHED (the program
        arms the lane on the device: `arm_lane`): the request's whole
        sampling surface becomes per-slot vectors for the next wave, the
        position mirror starts at the prompt's length."""
        self.slot_active[slot] = True
        self.slot_pos[slot] = n
        self._slot_epoch[slot] += 1
        self.slot_sample[slot] = sampling["sample"]
        self.slot_temp[slot] = sampling["temp"]
        self.slot_top_k[slot] = sampling["top_k"]
        self.slot_top_p[slot] = sampling["top_p"]
        self._set_bias_row(slot, sampling["bias"],
                           sampling["bias_nonzero"])
        self.slot_dynamic_mask[slot] = sampling["dynamic_mask"]

    def _sampling_state(self, do_sample, temperature, top_k, top_p,
                        logit_bias, dynamic_mask):
        """One request's sampling surface. `bias` is the [V] row, or
        None where the request brings none; `bias_nonzero` is computed
        here, once: a request whose row is all zeros is served by the
        resident zero row."""
        bias = (None if logit_bias is None
                else self._normalize_bias(logit_bias))
        return {"sample": bool(do_sample), "temp": float(temperature),
                "top_k": int(top_k), "top_p": float(top_p),
                "bias": bias,
                "bias_nonzero": bias is not None and bool(np.any(bias)),
                "dynamic_mask": bool(dynamic_mask)}

    def _prompt_args(self, slot, tokens, start, valid, frontier, sampling,
                     table=None, last=True):
        """A prefill program's arguments after the donated caches: the
        two lane-state vectors (donated too), the packed vector
        (pack_prompt), the [V] bias row, the key. The row is uploaded
        once a request, and only if it holds a bias."""
        if not sampling["bias_nonzero"]:
            bias = self._zero_bias_row
        elif "bias_dev" in sampling:
            bias = sampling["bias_dev"]
        else:
            bias = sampling["bias_dev"] = jax.device_put(sampling["bias"])
            self._bias_uploads += 1
        prompt = pack_prompt(
            tokens, table, start=start, valid=valid, frontier=frontier,
            slot=slot, last=last, sample=sampling["sample"],
            top_k=sampling["top_k"], temp=sampling["temp"],
            top_p=sampling["top_p"])
        return self._lane_tok, self._lane_pos, prompt, bias, self._key

    def take_bias_uploads(self):
        """Rows and matrices of bias sent to the device since the last
        call (the scheduler folds it into ServingMetrics once a
        round)."""
        n, self._bias_uploads = self._bias_uploads, 0
        return n

    def begin_prefill(self, slot, prompt, do_sample=False,
                      temperature=1.0, top_k=0, top_p=1.0,
                      logit_bias=None, dynamic_mask=False):
        """Stage an admission: validate and park the prompt on the slot.
        The work itself runs in prefill_step — the scheduler's advance
        phase — so engines whose prefill spans several rounds (paged
        chunked prefill) keep decode waves flowing while a long prompt
        is mid-admission. The dense engine completes in ONE
        prefill_step."""
        why = self.validate_prompt(prompt)
        if why:
            raise ValueError(why)
        if self.slot_active[slot] or slot in self._pending_prefill:
            raise RuntimeError(f"slot {slot} is busy")
        self._pending_prefill[slot] = (
            list(prompt),
            self._sampling_state(do_sample, temperature, top_k, top_p,
                                 logit_bias, dynamic_mask))

    def prefill_step(self, slot):
        """Advance the slot's admission one step: DISPATCH one prefill
        program and return, without reading anything. True when that was
        the prompt's last (the slot is armed and rides the next wave; its
        first token is read by `collect_first_tokens`, one program or
        more after it was made), False while more steps remain (the dense
        bucket prefill always completes here). Routed through
        prefill_slot so engine users (and test seams) that override it
        see every admission."""
        prompt, sampling = self._pending_prefill.pop(slot)
        self.prefill_slot(
            slot, prompt, do_sample=sampling["sample"],
            temperature=sampling["temp"], top_k=sampling["top_k"],
            top_p=sampling["top_p"], logit_bias=sampling["bias"],
            dynamic_mask=sampling["dynamic_mask"], wait=False)
        return True

    def prefill_chunk_index(self, slot):
        """Which chunk of the slot's prompt the next prefill_step runs
        (the `chunk` id of its span); the dense bucket is one chunk."""
        return 0

    def prefill_slot(self, slot, prompt, do_sample=False, temperature=1.0,
                     top_k=0, top_p=1.0, logit_bias=None,
                     dynamic_mask=False, wait=True):
        """Admit a prompt into a free slot: run the prefill program,
        splice the slot's cache region, arm the slot for the next wave.
        Returns the request's FIRST generated token (host int); with
        `wait=False` nothing is read and None is returned (the token
        comes with the next `collect_first_tokens`)."""
        why = self.validate_prompt(prompt)
        if why:
            raise ValueError(why)
        self._dispatch_prefill(
            slot, list(prompt),
            self._sampling_state(do_sample, temperature, top_k, top_p,
                                 logit_bias, dynamic_mask))
        return self.collect_first_tokens()[slot] if wait else None

    def _dispatch_prefill(self, slot, prompt, sampling):
        if self.slot_active[slot]:
            raise RuntimeError(f"slot {slot} is busy")
        if chaos.enabled():
            # host-side, before any state mutates or the donated cache
            # reaches the program — a fired fault leaves the engine
            # exactly as it was, so the scheduler can fail JUST this
            # request and keep serving
            chaos.fire(chaos.PREFILL, slot=slot)
        pid = self.trace_pid
        with RecordEvent("serving/prefill/stage", pid=pid) as ev:
            n = len(prompt)
            padded = np.zeros((self.prefill_len,), np.int32)
            padded[:n] = prompt
            args = (self._params, self._buffers, self._caches,
                    *self._prompt_args(slot, padded, 0, n, n - 1,
                                       sampling))
        self._acc("prefill.stage", ev)
        with RecordEvent("serving/prefill/dispatch", pid=pid) as ev:
            first, self._caches, self._lane_tok, self._lane_pos, \
                self._key = self._prefill(*args)
        self._dispatched("prefill.dispatch", ev, first)
        self._armed(slot, first, n, sampling)

    def _armed(self, slot, first, n, sampling):
        """The prompt's last chunk is on the device's queue: arm the
        slot and park its first token (a device scalar) for
        `collect_first_tokens`."""
        self._arm_slot(slot, n, sampling)
        self._first_pending.append((slot, int(self._slot_epoch[slot]),
                                    first))

    @property
    def first_tokens_pending(self):
        """Prompts whose last chunk was dispatched and whose first token
        has not been read."""
        return len(self._first_pending)

    def collect_first_tokens(self):
        """{slot: first token} of the prompts whose last chunk was
        dispatched since the last call: one blocking read of their
        scalars (`serving/prefill/first_token`). A slot retired since its
        chunk went out has no entry."""
        pending, self._first_pending = self._first_pending, []
        if not pending:
            return {}
        with RecordEvent("serving/prefill/first_token",
                         pid=self.trace_pid) as ev:
            firsts = jax.device_get([p[2] for p in pending])
        self._read_back("prefill.first_token", ev)
        out = {}
        for (slot, epoch, _), first in zip(pending, firsts):
            if self._slot_epoch[slot] == epoch:
                out[slot] = self.slot_tok[slot] = int(first)
        return out

    def decode_wave(self):
        """One batched decode step over all slots, dispatched and read:
        `dispatch_wave` then `collect_wave`. Returns {slot: token} for
        the slots that were active this wave AND produced finite logits
        (see `collect_wave`)."""
        ticket = self.dispatch_wave()
        if ticket is None:
            self.last_nonfinite_slots = []
            return {}
        return self.collect_wave(ticket)

    def dispatch_wave(self, skip=()):
        """Enqueue one batched decode step over the active slots less
        `skip` (the lanes the caller knows have no token left: their
        budget or the horizon is met by a token still in flight) and
        return its WaveTicket without reading anything; None when no
        lane is left to decode. Inactive lanes ride along frozen. The
        position mirror of the wave's lanes advances here.

        Raise-type faults (chaos, or a real host-side error) fire
        BEFORE the donated cache reaches the program, and the key
        advances only when the program returns the next one, so a
        failed dispatch mutates nothing and a retry replays exactly.
        An error from inside the compiled call itself may have consumed
        the donated cache — the retry then fails too and the scheduler
        degrades gracefully instead of looping."""
        active_now = self.slot_active.copy()
        active_now[np.asarray(skip, np.intp)] = False
        if not active_now.any():
            self.last_starved_slots = []
            return None
        if chaos.enabled():
            chaos.fire(chaos.DECODE_WAVE,
                       active=int(np.count_nonzero(active_now)))
        # back each lane's next cache write (paged engines allocate
        # blocks here; a starved lane is excluded from this wave and
        # reported in last_starved_slots for the scheduler to preempt).
        # Idempotent, so a retried wave replays exactly.
        pid = self.trace_pid
        with RecordEvent("serving/wave/blocks", pid=pid) as ev:
            active_now = self._prepare_wave(active_now)
        self._acc("wave.blocks", ev)
        if not active_now.any():
            return None
        with RecordEvent("serving/wave/stage", pid=pid) as ev:
            args = self._wave_args(active_now, self._wave_poison(),
                                   self._key)
        self._acc("wave.stage", ev)
        with RecordEvent("serving/wave/dispatch", pid=pid) as ev:
            read, self._caches, self._lane_tok, self._lane_pos, \
                self._key = self._decode_wave(*args)
        self._dispatched("wave.dispatch", ev, read)
        return self._ticket(np.flatnonzero(active_now), read=read)

    def _ticket(self, lanes, advance=1, **kw):
        """The ticket of a wave over `lanes` that was just dispatched;
        their mirrored positions move on by `advance`."""
        self.slot_pos[lanes] += advance
        full = lanes[self.slot_pos[lanes] >= self.max_len]
        return WaveTicket(lanes, self._slot_epoch[lanes].copy(),
                          frozenset(full.tolist()), **kw)

    def collect_wave(self, ticket):
        """Read a dispatched wave (`serving/wave/wait`: the one blocking
        read). Returns {slot: token} for the wave's lanes that produced
        finite logits and whose slot still serves the request it served
        at dispatch: a slot retired, or armed for another request, since
        then drops its entry (a lane that ended on a token's VALUE has
        by then run one step more than its request had use for). Lanes
        whose logits went non-finite are excluded, frozen in-program,
        and listed in `last_nonfinite_slots` for the scheduler to retire
        (finish_reason "error")."""
        if ticket.tokens is not None:
            return ticket.tokens
        with RecordEvent("serving/wave/wait", pid=self.trace_pid) as ev:
            tok, finite = jax.device_get(ticket.read)
        self._read_back("wave.wait", ev)
        lanes = ticket.lanes[self._slot_epoch[ticket.lanes]
                             == ticket.epochs]
        finite = finite[lanes] != 0
        ok, bad = lanes[finite], lanes[~finite]
        # a lane whose logits went non-finite is frozen in-program (it
        # did not advance); the caller must retire it
        self.slot_pos[bad] -= 1
        self.slot_tok[ok] = tok[ok]
        self.last_nonfinite_slots = bad.tolist()
        return dict(zip(ok.tolist(), tok[ok].tolist()))

    def _wave_poison(self):
        """[S] bool of lanes whose logits the chaos harness poisons in
        this wave (all False in production)."""
        poison = np.zeros((self.num_slots,), bool)
        if chaos.enabled():
            hit = chaos.value(chaos.DECODE_WAVE_NAN)
            if hit is not None:
                for s in np.atleast_1d(hit):
                    poison[int(s)] = True
        return poison

    def _prepare_wave(self, active_now):
        """Hook: ensure each active lane's next cache write has backing
        storage. Dense rows always do; the paged engine allocates blocks
        on demand and drops starved lanes from the wave."""
        self.last_starved_slots = []
        return active_now

    def _lane_args(self, active_now, poison, tables=None, spec_len=0):
        """A wave program's arguments after the donated caches, before
        the key: the packed per-lane state (pack_lanes: one place, so
        the dense, paged and speculative waves cannot drift) and the
        [S, V] bias/mask matrix, resident until a row changes."""
        if self._slot_bias_dev is None:
            self._slot_bias_dev = jax.device_put(self._slot_bias)
            self._bias_uploads += 1
        lanes = pack_lanes(
            tables, active=active_now, sample=self.slot_sample,
            top_k=self.slot_top_k, poison=poison, spec_len=spec_len,
            temp=self.slot_temp, top_p=self.slot_top_p)
        return lanes, self._slot_bias_dev

    def _wave_args(self, active_now, poison, key):
        """The decode-wave program's argument tuple (the paged engine
        packs its block tables in with the lanes). `key` is the
        engine's: the program splits it."""
        return (self._params, self._buffers, self._caches,
                self._lane_tok, self._lane_pos,
                *self._lane_args(active_now, poison), key)

    def retire_slot(self, slot):
        """Free a slot between waves. The cache region is left as-is:
        the next prefill overwrites [0, P) and the decode frontier
        rewrites every position before the ks<=pos mask exposes it.
        Also aborts a mid-prefill admission parked on the slot."""
        self.slot_active[slot] = False
        self._slot_epoch[slot] += 1
        self.slot_sample[slot] = False
        self.slot_temp[slot] = 1.0
        self.slot_top_k[slot] = 0
        self.slot_top_p[slot] = 1.0
        self.slot_dynamic_mask[slot] = False
        self._set_bias_row(slot, None)
        self._pending_prefill.pop(slot, None)
        self._slot_trace.pop(slot, None)
