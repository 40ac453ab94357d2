"""PagedServingEngine: block-table KV cache over the ServingEngine
wave machinery.

The dense engine pays `num_slots * max_len` HBM per layer whatever the
traffic actually holds, and most of that stream is padding (the batch
cell's pool is a fifth live: PERF.md, `pool_live_share`). Here the cache
is a fixed POOL of KV blocks per layer (`nn/paged_attention.py` owns its
form) and slots reference block TABLES (host-managed int32 id rows,
`serving.paged.BlockPool`): HBM scales with the blocks you configure,
utilisation scales with actual tokens, and identical prompt prefixes
dedupe onto shared blocks.

Still exactly TWO compiled programs, fully static shapes (the
compile-once discipline — table entries are VALUES, not shapes):

  * decode wave — the dense wave plus one traced `[S, nblk]` block
    table: each lane's K/V is written through its table row and
    attention reads the pool through the table (one call,
    `nn/paged_attention.py paged_attend`).
  * prefill chunk — ONE fixed-size chunk of one slot's prompt at a
    traced absolute offset. Long prompts run chunk-by-chunk BETWEEN
    decode waves (the scheduler advances one chunk per round), so
    admission never stalls decoding; prompts shorter than a chunk
    complete in one step, and chunks fully covered by prefix-cache hits
    are skipped outright.

Block bookkeeping is host-authoritative like the rest of what the host
decides (numpy, packed with the lanes into a wave's one small argument:
serving/engine.py says what a stage may and may not do, and that a
lane's token and position stay on the device): the table upload is
`S * nblk` int32 per wave. Allocation happens before a wave is
dispatched, by the host's mirror of the positions, which does not wait
for the last wave's tokens; a wave whose lane cannot get a block (pool
exhausted) is
excluded from that wave and reported in `last_starved_slots` — the
scheduler preempts it by recompute (requeue with prompt + generated
tokens; the freed blocks' prefix hashes make the re-prefill mostly
cache hits).
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from ...nn import paged_attention
from ...ops.pallas import grouped_mlp
from ...utils import chaos, telemetry
from ...utils.profiler import RecordEvent
from .. import blackbox
from ..metrics import EXPERT_COUNTS, MODEL_COUNTS
from ..engine import (ServingEngine, WaveTicket, _filter_top_k_top_p, _raw,
                      _select_first_token, _select_wave_tokens, arm_lane,
                      unpack_lanes, unpack_prompt, wave_read)
from .block_pool import (BlockPool, BlockPoolExhausted, SlotPages,
                         WindowBlockPool)

#: block-level KV handoff payload schema version (export_slot_kv /
#: import_handoff) — bumped when the payload layout changes so a
#: mixed-version fleet refuses the transfer instead of mis-scattering
#: (2: one [blocks, Hkv, BS, 2D] leaf a layer, K beside V)
HANDOFF_VERSION = 2


class HandoffRefused(RuntimeError):
    """A block-level KV handoff payload failed verification (digest
    mismatch, incompatible pool geometry, or a version skew). This is a
    REQUEST fault, never capacity: the importing scheduler fails only
    the handed-off request — decoding over corrupt or misaligned K/V
    would silently produce wrong tokens, which is strictly worse than
    an error (the PR 10/11 digest-verified-state discipline)."""


def _handoff_digest(layers, n_tokens, block_size):
    """sha256 over the payload's device content + the geometry that
    gives it meaning — the serving analog of the checkpoint manifest's
    per-file digests (and of the replica supervisor's weight digest):
    the importing engine verifies bytes, not trust."""
    h = hashlib.sha256()
    h.update(f"v{HANDOFF_VERSION}:{n_tokens}:{block_size}".encode())
    for arr in layers:
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _copy_page(caches, src, dst):
    return jax.tree_util.tree_map(lambda pool: pool.at[dst].set(pool[src]),
                                  caches)


def _gather_pages(caches, idx):
    return [leaf[idx] for leaf in jax.tree_util.tree_leaves(caches)]


def _scatter_pages(caches, idx, data):
    flat, treedef = jax.tree_util.tree_flatten(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf.at[idx].set(arr) for leaf, arr in zip(flat, data)])


def _set_lane(lane_tok, lane_pos, row):
    return (lane_tok.at[row[0]].set(row[1]),
            lane_pos.at[row[0]].set(row[2]))


class PagedServingEngine(ServingEngine):
    """Block-table batched decode executor.

    model: a causal LM that declares its `paged_layout()`
        (nn/paged_layout.py; docs/serving.md, "What a model declares to
        be served") and exposes init_paged_cache(num_blocks, block_size,
        max_len, dtype=, num_slots=) and the two paged steps,
        decode_step(tok, caches, pos, block_tables=, active=) and
        prefill_chunk(tok_chunk, caches, block_tables, chunk_start,
        valid_len, frontier=, slot=). A step's `block_tables` is the
        slot's row of every pool the layout names, packed in its order.
    max_len: per-request horizon; must be a multiple of block_size
        (table width = max_len // block_size).
    num_blocks: pool size INCLUDING the scratch block (block 0).
        Default num_slots * max_len // block_size + 1 — dense-equivalent
        capacity; size it smaller to oversubscribe (utilisation follows
        actual tokens, starved lanes preempt gracefully).
    prefill_chunk_len: prompt chunk size (default min(64, max_len)).
    prefix_sharing: hash full prompt blocks and dedupe identical
        prefixes (copy-on-write guarded; see BlockPool).
    paged_kernel: which paged-attention core the engine's programs
        trace: "reference" | "pallas", or None for what the backend
        decides ("pallas" on a TPU, "reference" elsewhere; see
        nn/paged_attention.py). Resolved at construction and pinned for
        every program this engine compiles; reported in /healthz.
    """

    def __init__(self, model, num_slots=4, max_len=256, block_size=16,
                 num_blocks=None, prefill_chunk_len=None, cache_dtype=None,
                 jit_compile=True, seed=0, prefix_sharing=True,
                 paged_kernel=None):
        self.paged_kernel = paged_attention.resolve_kernel(paged_kernel)
        if max_len % block_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"block_size {block_size}")
        self.block_size = int(block_size)
        self.blocks_per_slot = int(max_len) // self.block_size
        if num_blocks is None:
            num_blocks = int(num_slots) * self.blocks_per_slot + 1
        self.prefill_chunk_len = int(prefill_chunk_len
                                     or min(64, int(max_len)))
        if self.prefill_chunk_len > max_len:
            raise ValueError(
                f"prefill_chunk_len {self.prefill_chunk_len} > max_len "
                f"{max_len}")
        self.layout = layout = model.paged_layout()
        self.window = layout.window
        self.prefix_sharing = (bool(prefix_sharing)
                               and layout.shares_prefixes)
        self.block_pool = BlockPool(num_blocks, self.block_size)
        # the full pool, then a window pool if the layout names one; a
        # slot's row packs their tables in that order (what the programs
        # take), and each pool's SlotPages keeps its part of the rows
        pools = [self.block_pool] + [
            WindowBlockPool(int(num_slots) * self._window_pages(w) + 1,
                            self.block_size) for w in (self.window,) if w]
        nblk = self.blocks_per_slot
        self._rows = np.zeros((int(num_slots), len(pools) * nblk), np.int32)
        self._pages = [SlotPages(pool, self._rows[:, i * nblk:(i + 1) * nblk])
                       for i, pool in enumerate(pools)]
        self._windowed = self._pages[1:]
        self._tables = self._pages[0].table
        self._slot_blocks = self._pages[0].blocks
        super().__init__(model, num_slots=num_slots, max_len=max_len,
                         prefill_len=self.prefill_chunk_len,
                         cache_dtype=cache_dtype, jit_compile=jit_compile,
                         seed=seed)
        # small programs, each compiled on its first call: the
        # copy-on-write copy, a hand-off's gather and scatter over the
        # whole bundle and the lane it arms where no chunk did
        self._copy_fn = self.jit(_copy_page, "paged_cow_copy", donate=(0,))
        # the gather reads the pools, which stay the engine's
        self._handoff_gather_fn = self.jit(
            _gather_pages, "paged_handoff_gather", donate=())
        self._handoff_scatter_fn = self.jit(
            _scatter_pages, "paged_handoff_scatter", donate=(0,))
        self._set_lane_fn = self.jit(_set_lane, "paged_set_lane",
                                     donate=(0, 1))
        # table entries the attention core visits in the decode waves
        # staged since the scheduler last took them (take_page_counts,
        # once a round), and the entries those waves' tables hold; the
        # grid steps its kernel runs, and those that fetch and score
        # pages, in those waves and the prefill chunks, a layer (a
        # layout of several groups: each group times its layers)
        self._pages_visited = self._pages_spanned = 0
        self._steps_run = self._steps_visited = 0
        self._groups = layout.weighted()
        self._itemsize = np.dtype(self.cache_dtype).itemsize
        # what the model's own layers were staged since the scheduler
        # last took them (take_model_counts, once a round)
        self.counts_model_work = layout.counts_work
        self._model_counts = dict.fromkeys(MODEL_COUNTS, 0)
        # of a model with routed experts, the experts its sparse layers
        # store, which every wave's kernel calls could fetch; the waves
        # read since the last take report those they did fetch. A chunk
        # runs its sparse layers, each in the kernel calls it cuts the
        # chunk's tokens into
        self._chunk_calls, self._chunk_layers = layout.chunk_calls(
            self.prefill_chunk_len)
        self._expert_counts = dict.fromkeys(EXPERT_COUNTS, 0)

    def _window_pages(self, window):
        """Window-pool pages a lane holds at most: the window behind a
        chunk's first query and the chunk, on pages that need not align."""
        return -(-(window - 1 + self.prefill_chunk_len)
                 // self.block_size) + 1

    window_pages = property(lambda self: self._window_pages(self.window))
    window_pool = property(lambda self: self._windowed[0].pool)

    def _count_steps(self, start, c):
        """Count each group's kernel grid for one staged call of `c`
        queries a lane at `start` (`AttentionGroup.steps`)."""
        for group, layers in self._groups:
            run, visited = group.steps(start, c, self.block_size,
                                       self.blocks_per_slot, self._itemsize)
            self._steps_run += layers * run
            self._steps_visited += layers * visited

    def _make_caches(self):
        caches = self.model.init_paged_cache(
            self.block_pool.num_blocks, self.block_size, self.max_len,
            dtype=self.cache_dtype, num_slots=self.num_slots,
            **{"window_blocks": p.pool.num_blocks for p in self._windowed})
        # what the layout serves, with the bytes of slot records (/healthz)
        self._layout_health = self.layout.health(caches)
        return caches

    # ---------------------------------------------------------- programs
    def _build_programs(self):
        model, kern = self.model, self.paged_kernel
        chunk_len = self.prefill_chunk_len

        def decode_wave(p, b, caches, lane_tok, lane_pos, lanes, bias, key):
            key, sub = jax.random.split(key)
            tables, a = unpack_lanes(lanes)
            # the scope pins this engine's kernel at TRACE time: the
            # compiled wave keeps the core the engine was built with. A
            # routed model's layers note the experts their kernel calls
            # fetch, which the wave's read carries (none for any other)
            with paged_attention.kernel_scope(kern), \
                    grouped_mlp.fetch_tally() as fetched:
                out, _ = model.functional_call(
                    p, b, lane_tok[:, None], caches, lane_pos,
                    method="decode_step", block_tables=tables,
                    active=a["active"])
            logits, new_caches = out
            lo = _raw(logits)[:, 0, :].astype(jnp.float32)
            nxt, new_pos, finite = _select_wave_tokens(
                lo, lane_tok, lane_pos, a["active"], a["sample"],
                a["temp"], a["top_k"], a["top_p"], bias, a["poison"], sub)
            return (wave_read(nxt, finite, fetched), new_caches, nxt,
                    new_pos, key)

        def prefill_chunk(p, b, caches, lane_tok, lane_pos, prompt, bias,
                          key):
            key, sub = jax.random.split(key)
            table, chunk, a = unpack_prompt(prompt, chunk_len)
            with paged_attention.kernel_scope(kern):
                out, _ = model.functional_call(
                    p, b, chunk[None, :], caches, method="prefill_chunk",
                    block_tables=table[None, :], chunk_start=a["start"],
                    valid_len=a["valid"], frontier=a["frontier"],
                    slot=a["slot"])
            logits, new_caches = out
            # frontier logits [1, 1, V]: only the FINAL chunk's value is
            # consumed (it arms the slot's lane, and the host reads it);
            # earlier chunks compute a [V] row that is simply ignored
            # (static shapes beat a conditional head)
            lo = _raw(logits)[0, 0].astype(jnp.float32)
            first = _select_first_token(lo, a["sample"], a["temp"],
                                        a["top_k"], a["top_p"], bias, sub)
            return (first, new_caches,
                    *arm_lane(lane_tok, lane_pos, a, first), key)

        def state_reset(caches, slot):
            """Zero one slot's record in every state array (a small
            program of its own: the pools pass through it aliased)."""
            state = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_update_slice_in_dim(
                    a, jnp.zeros((1,) + a.shape[1:], a.dtype), slot, 0),
                caches["state"])
            return {**caches, "state": state}

        self._decode_wave_fn = decode_wave
        self._prefill_fn = prefill_chunk
        self._program_donate_argnums = (2, 3, 4)
        self._prefill_donate_argnums = self._program_donate_argnums
        # compiled on the first admission of a layout with slot state
        self._state_reset = self.jit(state_reset, "paged_state_reset",
                                     donate=(0,))
        # the block pools (and the lane state) are donated exactly like
        # the dense cache: XLA updates the pool in place
        self._decode_wave = self.jit(decode_wave, "paged_decode_wave",
                                     donate=self._program_donate_argnums)
        self._prefill = self.jit(prefill_chunk, "paged_prefill_chunk",
                                 donate=self._prefill_donate_argnums)

    def describe(self):
        """Replay-relevant construction config (see ServingEngine
        .describe): the paged extras on top of the dense fields."""
        d = super().describe()
        d.update({"engine": "paged", "block_size": self.block_size,
                  "num_blocks": self.block_pool.num_blocks,
                  "prefill_chunk_len": self.prefill_chunk_len,
                  "prefix_sharing": self.prefix_sharing,
                  "slot_state": self.layout.slot_record,
                  "paged_kernel": self.paged_kernel})
        return d

    # --------------------------------------------------------- admission
    def validate_prompt(self, prompt):
        """Chunked prefill removes the dense bucket limit: any prompt
        that fits the horizon (with one position to decode into) and the
        pool's total capacity is admissible."""
        n = len(prompt)
        if n + 1 > self.max_len:
            return (f"prompt length {n} leaves no room to decode under "
                    f"max_len {self.max_len}")
        need = (n + 1 + self.block_size - 1) // self.block_size
        if need > self.block_pool.usable:
            return (f"prompt needs {need} KV blocks, pool has only "
                    f"{self.block_pool.usable} usable")
        return None

    def begin_prefill(self, slot, prompt, do_sample=False,
                      temperature=1.0, top_k=0, top_p=1.0,
                      logit_bias=None, dynamic_mask=False):
        """Admit a prompt: match shared prefix blocks, allocate the rest
        (BlockPoolExhausted = capacity, handled by the scheduler as
        queueing pressure, never a request fault), and stage the chunk
        schedule. Chunks fully covered by prefix-cache hits are
        skipped — a fully-cached prompt still runs its LAST chunk, which
        produces the frontier logits (the K/V are cached; the first
        TOKEN never is)."""
        why = self.validate_prompt(prompt)
        if why:
            raise ValueError(why)
        if self.slot_active[slot] or slot in self._pending_prefill:
            raise RuntimeError(f"slot {slot} is busy")
        prompt = [int(t) for t in prompt]
        n, bs = len(prompt), self.block_size
        need = (n + 1 + bs - 1) // bs
        shared, hashes = ([], [])
        if self.prefix_sharing:
            shared, hashes = self.block_pool.match_prefix(prompt)
        try:
            fresh = self.block_pool.alloc(need - len(shared))
        except BaseException:
            # exhaustion AND crash paths (e.g. an injected allocator
            # raise): the matched prefix references must go back, or a
            # failed admission permanently shrinks pool capacity
            self.block_pool.release(shared)
            raise
        if self.prefix_sharing:
            # counted only now, on successful admission — exhaustion
            # retries at the queue head must not inflate the rate
            self.block_pool.count_prefix(len(shared),
                                         n // bs - len(shared))
        if self.layout.slot_record:
            # the slot's last request left its record behind: this one
            # starts from zero, at token 0 (nothing was shared)
            with RecordEvent("serving/state/reset", pid=self.trace_pid,
                             slot=slot) as ev:
                self._caches = self._state_reset(self._caches,
                                                 np.int32(slot))
            self._acc("state.reset", ev)
            self._model_counts["state_resets"] += 1
        blocks = shared + fresh
        self._slot_blocks[slot] = blocks
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks
        chunk = self.prefill_chunk_len
        start = (len(shared) * bs // chunk) * chunk
        start = min(start, ((n - 1) // chunk) * chunk)
        self._pending_prefill[slot] = {
            "prompt": prompt, "n": n, "next": start,
            "sampling": self._sampling_state(do_sample, temperature,
                                             top_k, top_p, logit_bias,
                                             dynamic_mask),
            "hashes": (self.block_pool.prompt_hashes(prompt)
                       if self.prefix_sharing else []),
            "next_hash": len(shared),
        }

    def prefill_step(self, slot):
        """DISPATCH one chunk of the slot's staged prompt and return,
        without reading anything. True when that was the final chunk
        (the slot is armed and rides the next wave; its first token is
        read by `collect_first_tokens`, one program or more after it was
        made), False while chunks remain (decode waves continue in
        between)."""
        st = self._pending_prefill[slot]
        pid = self.trace_pid
        with RecordEvent("serving/prefill/stage", pid=pid) as ev:
            if chaos.enabled():
                # host-side, before the donated pool reaches the program
                # — a fired fault leaves device state untouched; the
                # scheduler fails just this request and frees its blocks
                chaos.fire(chaos.PREFILL, slot=slot,
                           chunk_start=st["next"])
            c0, C, n, bs = st["next"], self.prefill_chunk_len, st["n"], \
                self.block_size
            trace = self._slot_trace.get(slot)
            if trace is not None:
                # chunk-indexed progress marker inside the request's
                # PREFILL span: a long chunked admission's folding
                # between decode waves is visible per chunk in the
                # exported trace
                telemetry.trace_instant(
                    trace[0], f"PREFILL_CHUNK[{c0 // C}]", pid=trace[1],
                    slot=slot, chunk_start=c0, prompt_len=n)
            valid = min(C, n - c0)
            chunk = np.zeros((C,), np.int32)
            chunk[:valid] = st["prompt"][c0:c0 + valid]
            last = c0 + C >= n
            frontier = (n - 1) - c0 if last else 0
            sampling = st["sampling"]
            # a window pool's pages behind the chunk's window go back,
            # and the chunk's are backed (the full pool's were at
            # admission)
            self._release_behind([slot], [c0])
            for pages in self._windowed:
                pages.back(slot, (c0 + valid - 1) // bs)
            args = (*self._prefill_chunk_args(slot),
                    *self._prompt_args(slot, chunk, c0, valid, frontier,
                                       sampling, self._slot_table(slot),
                                       last))
            counts = self._model_counts
            counts["prefill_tokens"] += valid
            counts["prefill_keys"] += valid * c0 + valid * (valid + 1) // 2
            counts["prefill_chunks"] += 1
            for name, add in self.layout.chunk_counts(
                    c0, valid, C, bs, self.blocks_per_slot).items():
                counts[name] += add
            self._count_steps(np.int32([c0]), C)
            self._expert_counts["moe_chunk_calls"] += self._chunk_calls
            self._expert_counts["moe_chunk_layers"] += self._chunk_layers
        self._acc("prefill.stage", ev)
        with RecordEvent("serving/prefill/dispatch", pid=pid) as ev:
            first, self._caches, self._lane_tok, self._lane_pos, \
                self._key = self._prefill(*args)
        self._dispatched("prefill.dispatch", ev, first)
        # full prompt blocks written by this chunk enter the prefix
        # cache — only now, so a concurrent admission can never share a
        # block whose content is not on the device yet
        if self.prefix_sharing:
            end = c0 + valid
            while (st["next_hash"] < len(st["hashes"])
                   and (st["next_hash"] + 1) * bs <= end):
                i = st["next_hash"]
                self.block_pool.register_hash(self._slot_blocks[slot][i],
                                              st["hashes"][i])
                st["next_hash"] += 1
        st["next"] = c0 + C
        if not last:
            return False
        del self._pending_prefill[slot]
        self._armed(slot, first, n, sampling)
        return True

    def prefill_chunk_index(self, slot):
        st = self._pending_prefill[slot]
        return st["next"] // self.prefill_chunk_len

    def _prefill_chunk_args(self, slot):
        """Leading argument tuple of the prefill-chunk program (the
        speculative engine appends its draft params here so ONE chunk
        program writes both models' K/V)."""
        return (self._params, self._buffers, self._caches)

    def prefill_slot(self, slot, prompt, **kw):
        """Synchronous admission (runs every chunk back-to-back) — the
        dense-engine surface, kept for direct engine users; the
        scheduler uses begin_prefill/prefill_step to fold chunks between
        waves. Accepts the full per-request sampling surface
        (do_sample, temperature, top_k, top_p, logit_bias)."""
        self.begin_prefill(slot, prompt, **kw)
        while not self.prefill_step(slot):
            pass
        return self.collect_first_tokens()[slot]

    # -------------------------------------------------- block-level handoff
    def export_slot_kv(self, slot):
        """Package a prefilled slot's populated KV blocks for a
        block-level handoff to another replica: the allocator manifest
        (BlockPool.export_blocks) plus the per-layer device content
        gathered at the slot's block ids, digest-sealed. The gather is
        its own tiny program (compiled lazily, like the COW copy) —
        tree-generic over the cache bundle, so the speculative engine's
        (target, draft) pools ride the same path with no override.

        The slot itself is left untouched: the caller retires it (which
        frees the blocks but keeps their prefix hashes) only once the
        payload is safely in hand."""
        self._refuse_handoff("export_slot_kv")
        if not self.slot_active[slot]:
            raise RuntimeError(f"slot {slot} is not active "
                               "(handoff export needs a completed prefill)")
        if slot in self._pending_prefill:
            raise RuntimeError(f"slot {slot} is mid-prefill")
        blocks = list(self._slot_blocks[slot])
        manifest = self.block_pool.export_blocks(blocks)
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        layers = [np.asarray(x)
                  for x in self._handoff_gather_fn(self._caches, idx)]
        n = int(self.slot_pos[slot])
        payload = {
            "version": HANDOFF_VERSION,
            "n_tokens": n,
            "next_token": int(self.slot_tok[slot]),
            "block_size": self.block_size,
            "blocks": len(blocks),
            "manifest": manifest,
            "layers": layers,
            "nbytes": sum(a.nbytes for a in layers),
            "digest": _handoff_digest(layers, n, self.block_size),
        }
        bb = blackbox.get_recorder()
        if bb is not None:
            bb.hop(kind="kv_export", slot=slot, digest=payload["digest"],
                   blocks=payload["blocks"], nbytes=payload["nbytes"],
                   n_tokens=n)
        return payload

    def _refuse_handoff(self, name):
        """Hand-off moves the K/V pages of one pool alone: what the
        layout refuses it (`PagedLayout.refusal`)."""
        why = self.layout.refusal(f"{name} (block-level hand-off)")
        if why:
            raise HandoffRefused(why)

    def import_handoff(self, slot, prompt, payload, do_sample=False,
                       temperature=1.0, top_k=0, top_p=1.0,
                       logit_bias=None, dynamic_mask=False):
        """Admit a request from an exported KV payload: verify the
        digest and geometry (HandoffRefused = request fault — decoding
        over corrupt or misaligned K/V would silently emit wrong
        tokens), allocate local blocks (BlockPoolExhausted = capacity,
        exactly an admission under pool pressure), scatter the content
        in, and arm the slot as if the final prefill chunk had just run
        here. `prompt` is the handed-off request's continuation
        (original prompt + the first token the prefill side produced):
        the slot arms at position len(prompt) - 1 holding prompt[-1],
        and the next decode wave writes that token's K/V — bit-for-bit
        the single-replica schedule. No prefill-chunk program runs (the
        scatter is a separate lazy jit), which is the whole point:
        a handoff costs bytes on the wire, not recompute."""
        self._refuse_handoff("import_handoff")
        why = self.validate_prompt(prompt)
        if why:
            raise ValueError(why)
        if self.slot_active[slot] or slot in self._pending_prefill:
            raise RuntimeError(f"slot {slot} is busy")
        prompt = [int(t) for t in prompt]
        layers = list(payload.get("layers", ()))
        if chaos.enabled():
            # injected wire corruption: flip payload content out from
            # under its digest (host-side copies; the exporter's arrays
            # are untouched) — the digest check below MUST refuse it
            if chaos.value(chaos.HANDOFF_IMPORT, slot=slot,
                           blocks=payload.get("blocks")):
                corrupt = np.array(layers[0])
                corrupt.flat[0] += np.asarray(1, corrupt.dtype)
                layers[0] = corrupt
        if payload.get("version") != HANDOFF_VERSION:
            raise HandoffRefused(
                f"handoff version {payload.get('version')!r} != "
                f"{HANDOFF_VERSION} (mixed-version fleet)")
        if int(payload["block_size"]) != self.block_size:
            raise HandoffRefused(
                f"payload block_size {payload['block_size']} != pool "
                f"block_size {self.block_size}")
        n = int(payload["n_tokens"])
        if n != len(prompt) - 1 or int(payload["next_token"]) != prompt[-1]:
            raise HandoffRefused(
                "payload token state does not match the continuation "
                f"(payload n={n}, next={payload['next_token']}; "
                f"continuation len={len(prompt)})")
        nblk = len(payload["manifest"])
        if nblk * self.block_size < n + 1 or nblk != payload.get("blocks"):
            raise HandoffRefused(
                f"{nblk} exported block(s) cannot back {n} tokens "
                "plus the decode frontier")
        leaves = jax.tree_util.tree_leaves(self._caches)
        if len(layers) != len(leaves) or any(
                a.shape != (nblk,) + l.shape[1:] or a.dtype != l.dtype
                for a, l in zip(layers, leaves)):
            raise HandoffRefused(
                "payload layer layout does not match this engine's "
                "cache bundle (engine-flavor or geometry mismatch)")
        if _handoff_digest(layers, n, self.block_size) != payload["digest"]:
            raise HandoffRefused(
                "handoff digest mismatch: payload content is corrupt")
        fresh = self.block_pool.import_blocks(payload["manifest"])
        try:
            idx = jnp.asarray(np.asarray(fresh, np.int32))
            self._caches = self._handoff_scatter_fn(self._caches, idx,
                                                    layers)
            self._slot_blocks[slot] = fresh
            self._tables[slot, :] = 0
            self._tables[slot, :len(fresh)] = fresh
            if self.prefix_sharing:
                # content is on the device NOW — full prompt blocks may
                # enter the prefix cache (first writer wins), so the
                # decode replica's follow-up admissions share them
                for i, h in enumerate(self.block_pool.prompt_hashes(
                        prompt[:n])[:len(fresh)]):
                    self.block_pool.register_hash(fresh[i], h)
        except BaseException:
            self.block_pool.release(fresh)
            self._slot_blocks[slot] = []
            self._tables[slot, :] = 0
            raise
        first = prompt[-1]
        self._arm_slot(slot, n,
                       self._sampling_state(do_sample, temperature, top_k,
                                            top_p, logit_bias,
                                            dynamic_mask))
        self._set_lane(slot, first, n)
        bb = blackbox.get_recorder()
        if bb is not None:
            bb.hop(kind="kv_import", slot=slot, digest=payload["digest"],
                   blocks=nblk, nbytes=payload.get("nbytes"), n_tokens=n)
        return first

    def _set_lane(self, slot, tok, pos):
        """Arm one lane on the device where no prefill chunk did (a
        hand-off import)."""
        self._lane_tok, self._lane_pos = self._set_lane_fn(
            self._lane_tok, self._lane_pos, np.int32([slot, tok, pos]))
        self.slot_tok[slot] = tok

    # ------------------------------------------------------------- waves
    def _prepare_wave(self, active_now):
        """Back each active lane's next write position with a block.
        Allocation failure excludes the lane from this wave (its table
        row still maps unallocated entries to scratch, so the frozen
        lane's in-program write is harmless) and reports it for
        preemption. A shared write target (safety net — full-block
        sharing keeps the frontier private by construction) is
        copy-on-write'd first."""
        starved = []
        block_of = (self.slot_pos // self.block_size).tolist()
        for s in np.flatnonzero(active_now).tolist():
            bi = block_of[s]
            blocks = self._slot_blocks[s]
            try:
                if bi >= len(blocks):
                    self._pages[0].back(s, bi)
                elif self.block_pool.refcount(blocks[bi]) > 1:
                    self._ensure_private(s, bi)
                for pages in self._windowed:
                    pages.back(s, bi)
            except BlockPoolExhausted:
                starved.append(s)
                active_now[s] = False
        self.last_starved_slots = starved
        return active_now

    def _wave_tables(self, active_now):
        """The tables a wave takes. The program scatters EVERY lane's
        K/V unconditionally (fixed shapes); a lane not in THIS wave
        (free, mid-prefill, starved) would write its stale token through
        its table row into a live block — a mid-chunked-prefill slot's
        table is already populated, possibly with SHARED blocks. Those
        lanes get scratch rows, so the write lands in block 0 by
        design. A row packs every pool's, in the layout's order."""
        return np.where(np.asarray(active_now, bool)[:, None],
                        self._rows, np.int32(BlockPool.SCRATCH))

    def _wave_args(self, active_now, poison, key):
        lanes = np.flatnonzero(active_now)
        self._release_behind(lanes, self.slot_pos[lanes])
        tables = self._wave_tables(active_now)
        # every lane rides the wave at its `slot_pos`, the ones not in
        # it at a stale one over a scratch row: the core walks those too
        for group, layers in self._groups:
            self._pages_visited += layers * group.pages(
                self.slot_pos, 1, self.block_size, self.blocks_per_slot)
            self._pages_spanned += layers * self._tables.size
        self._count_steps(self.slot_pos, 1)
        for name, add in self.layout.wave_counts(self.slot_pos[lanes],
                                                 self.num_slots).items():
            self._model_counts[name] += add
        self._expert_counts["moe_experts_stored"] += \
            self.layout.experts_stored
        return (self._params, self._buffers, self._caches,
                self._lane_tok, self._lane_pos,
                *self._lane_args(active_now, poison, tables), key)

    # ------------------------------------------------------ window pools
    def _slot_table(self, slot):
        """A prefill chunk's table row: every pool's, in the layout's order."""
        return self._rows[slot]

    def _release_behind(self, slots, starts):
        """Give back the window pools' pages of `slots` behind what a call
        whose first query sits at `starts` attends (`SlotPages.behind`)."""
        for pages in self._windowed:
            counts = pages.behind(slots, starts, self.window)
            with RecordEvent("serving/window/release", pid=self.trace_pid,
                             pages=sum(counts)) as ev:
                pages.release_behind(slots, counts)
            self._acc("window.release", ev)
            self._model_counts["window_pages_released"] += sum(counts)

    # ----------------------------------------------------- copy-on-write
    def _ensure_private(self, slot, bi):
        """Give the slot a private copy of table entry `bi` (the pool
        moves the reference; the device content is copied by a tiny
        jitted program, compiled lazily — the normal flow never diverges
        into a shared block, so this almost never runs)."""
        blocks = self._slot_blocks[slot]
        blk = blocks[bi]
        new = self.block_pool.cow(blk)
        if new == blk:
            return
        self._caches = self._copy_block(self._caches, blk, new)
        blocks[bi] = new
        self._tables[slot, bi] = new

    def _copy_block(self, caches, src, dst):
        """Copy-on-write over the whole bundle, every pool in its stored
        form (the speculative engine's target AND draft pools: one block
        id names the same token span in both, so a half-copied block
        would desynchronize the draft cache from the tokens it claims
        to hold). A model with slot state shares no block, so its
        records never come here."""
        return self._copy_fn(caches, np.int32(src), np.int32(dst))

    def take_page_counts(self):
        """(visited, spanned) table entries of the decode waves staged
        since the last call, and the (run, visited) grid steps of the
        attention kernel in those waves and the prefill chunks, a layer
        (the scheduler folds them into ServingMetrics once a round)."""
        out = (self._pages_visited, self._pages_spanned, self._steps_run,
               self._steps_visited)
        self._pages_visited = self._pages_spanned = 0
        self._steps_run = self._steps_visited = 0
        return out

    def take_model_counts(self):
        """What the model's own layers were staged since the last call:
        the chunks, their prompt tokens and the keys those attend in a
        full layer (position + 1 each); what the layout counts of chunks
        and waves (`PagedLayout.wave_counts`, `chunk_counts`); of a window
        pool, the pages given back behind the window and the pages and
        lanes holding any as the call finds them (summed once a round);
        of routed experts, `EXPERT_COUNTS`. Taken by the scheduler once a
        round, from an engine whose `counts_model_work` is set."""
        out = self._model_counts
        self._model_counts = dict.fromkeys(MODEL_COUNTS, 0)
        for pages in self._windowed:
            out["window_pages_held"] += pages.pool.used
            out["window_lanes_held"] += sum(map(bool, pages.blocks))
        if self.layout.experts_stored:
            out.update(self._expert_counts)
            self._expert_counts = dict.fromkeys(EXPERT_COUNTS, 0)
        return out

    def _wave_counted(self, row):
        self._expert_counts["moe_experts_fetched"] += int(row[0])

    # ------------------------------------------------------------- slots
    def retire_slot(self, slot):
        """Free the slot AND its blocks. Freed blocks keep their prefix
        hashes (lazy eviction), so a follow-up request with the same
        prompt — or this request re-admitted after preemption — re-hits
        the cache instead of recomputing."""
        super().retire_slot(slot)
        for pages in self._pages:
            pages.retire(slot)

    def _health(self):
        # cache_blocks_used/total mirror the gauges of the same name:
        # the fleet router (and any LB) reads pool pressure from ONE
        # /healthz fetch instead of scraping /metrics
        h = super()._health()
        h.update(block_size=self.block_size,
                 paged_kernel=self.paged_kernel,
                 cache_blocks_used=self.block_pool.used,
                 cache_blocks_total=self.block_pool.usable,
                 prefix_cache_hits=self.block_pool.prefix_hits,
                 prefix_cache_misses=self.block_pool.prefix_misses,
                 prefix_sharing=self.prefix_sharing, **self._layout_health)
        for pages in self._windowed:
            h.update(window_blocks_used=pages.pool.used,
                     window_blocks_total=pages.pool.usable)
        return h


def _spec_verify_tail(lo, tok, pos, active, sample, temps, top_k, top_p,
                      bias, spec_len, draft_toks, draft_probs, poison,
                      key):
    """The speculative wave's acceptance–rejection tail: the
    _select_wave_tokens math applied position-by-position over the
    verify chunk's [S, C, V] target logits (C = k + 1), with EXACT
    acceptance–rejection so the output distribution equals the target
    model's own — and the greedy path is bitwise the target trajectory.

    Greedy lanes accept the longest draft prefix agreeing with the
    target argmax (over BIASED logits, like the non-speculative tail)
    and emit the correcting argmax at the first mismatch. Sampled lanes
    accept draft token d_i with probability min(1, p_t(d_i)/p_d(d_i))
    and resample the first rejection from the normalized residual
    max(p_t - p_d, 0); with all k accepted, the bonus token is the
    a == k case of the same formula because p_d is zero-extended at
    position k (residual = p_t). Both p_t and p_d are the PROCESSED
    distributions (temperature, top-k/top-p, logit-bias applied), so
    the scenario surface composes with speculation exactly.

    Per-lane spec_len clamps acceptance (horizon, dynamic token-mask
    lanes run at spec_len 0 == plain decode). Frozen lanes (inactive,
    poisoned, non-finite) emit 0 tokens and keep their position — the
    scheduler retires poisoned lanes exactly like the non-spec wave."""
    s, c, v = lo.shape
    k = c - 1
    lo = jnp.where(poison[:, None, None], jnp.float32(jnp.nan),
                   lo + bias[:, None, :])
    finite = jnp.all(jnp.isfinite(lo), axis=(1, 2))
    greedy = jnp.argmax(lo, axis=-1).astype(jnp.int32)          # [S, C]
    scaled = lo / jnp.maximum(temps, 1e-6)[:, None, None]
    filt = _filter_top_k_top_p(
        scaled.reshape(s * c, v), jnp.repeat(top_k, c),
        jnp.repeat(top_p, c)).reshape(s, c, v)
    p_t = jax.nn.softmax(filt, axis=-1)                         # [S, C, V]
    valid = jnp.arange(k)[None, :] < spec_len[:, None]          # [S, k]
    ok_greedy = draft_toks == greedy[:, :k]
    key_u, key_r, key_f = jax.random.split(key, 3)
    u = jax.random.uniform(key_u, (s, k))
    pt_d = jnp.take_along_axis(p_t[:, :k, :], draft_toks[..., None],
                               axis=-1)[..., 0]                 # [S, k]
    pd_d = jnp.take_along_axis(draft_probs, draft_toks[..., None],
                               axis=-1)[..., 0]
    ok_sample = u * pd_d < pt_d
    ok = jnp.where(sample[:, None], ok_sample, ok_greedy) & valid
    accepted = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    a = jnp.sum(accepted, axis=1)                    # [S] in [0, k]
    # the one non-draft token per lane: correction at the rejection,
    # bonus past a fully-accepted span. p_d is zeroed at every position
    # the lane did NOT draft (i >= its spec_len, the k-th position
    # included) — there the formula must degenerate to sampling p_t
    # itself: a horizon- or token-mask-clamped lane proposed nothing at
    # its frontier, and subtracting a draft distribution it never
    # offered would skew the output away from the target's (the
    # "spec_len 0 == plain decode" exactness contract)
    p_d_ext = jnp.concatenate(
        [draft_probs, jnp.zeros((s, 1, v), draft_probs.dtype)], axis=1)
    p_d_ext = jnp.where(
        (jnp.arange(c)[None, :] < spec_len[:, None])[:, :, None],
        p_d_ext, 0.0)
    p_t_a = jnp.take_along_axis(p_t, a[:, None, None], axis=1)[:, 0]
    p_d_a = jnp.take_along_axis(p_d_ext, a[:, None, None], axis=1)[:, 0]
    residual = jnp.maximum(p_t_a - p_d_a, 0.0)
    res_tok = jax.random.categorical(
        key_r, jnp.log(jnp.maximum(residual, 1e-30)),
        axis=-1).astype(jnp.int32)
    # float round-off can zero a residual row that is positive in exact
    # arithmetic — fall back to the target distribution itself (a
    # measure-zero correction, never reached in exact math)
    fallback = jax.random.categorical(
        key_f, jnp.log(jnp.maximum(p_t_a, 1e-30)),
        axis=-1).astype(jnp.int32)
    res_tok = jnp.where(jnp.sum(residual, axis=-1) > 0, res_tok,
                        fallback)
    greedy_a = jnp.take_along_axis(greedy, a[:, None], axis=1)[:, 0]
    extra = jnp.where(sample, res_tok, greedy_a).astype(jnp.int32)
    draft_pad = jnp.concatenate(
        [draft_toks, jnp.zeros((s, 1), jnp.int32)], axis=1)
    out = jnp.where(jnp.arange(c)[None, :] < a[:, None], draft_pad,
                    extra[:, None])
    ok_lane = active & finite
    n_emit = jnp.where(ok_lane, a + 1, 0)
    new_pos = pos + n_emit
    nxt = jnp.where(ok_lane, extra, tok)
    return out, n_emit, nxt, new_pos, finite


class SpeculativePagedEngine(PagedServingEngine):
    """Draft-k / verify-once speculative decoding over the paged engine.

    A small DRAFT model proposes up to k tokens per slot per wave; the
    target model scores all k + 1 positions in ONE batched forward built
    on the model's one paged step over the SAME block tables (its
    `prefill_chunk` at [S, k + 1] with per-lane starts — the C == 1 case
    IS the plain decode wave, so this is a third compiled program, not a
    new attention path). Exact acceptance–rejection (see
    `_spec_verify_tail`) keeps outputs
    distribution-identical to the target model — bitwise-identical under
    greedy — while a wave advances each lane by 1..k+1 tokens: decode
    rounds per generated token drop by the acceptance rate.

    Memory discipline: the draft model's paged KV pools share the block
    TABLES (and therefore the allocator, refcounts, prefix sharing and
    copy-on-write) with the target pools — one block id names the same
    token span in both. The prefill-chunk program writes BOTH models'
    K/V, so a prefix-cache hit serves the draft cache too, and
    `retire_slot` frees both at once. Speculated-ahead blocks that the
    acceptance did not commit are rolled back after every wave
    (`_rollback_spec_blocks`) — the pool never holds blocks for tokens
    that were rejected.

    Compile-once holds as THREE programs with fully static shapes:
    `paged_spec_draft_wave` (k+1 draft decode steps in one executable),
    `paged_spec_verify` (the chunk-scored target forward + acceptance
    tail), and `paged_spec_prefill_chunk` (target + draft chunk
    prefill). Per-lane spec_len (horizon clamp, dynamic token-mask
    lanes) is a traced VALUE, not a shape.

    Not `pipelined`: the accepted lengths have to reach the host before
    the next wave (they roll blocks back and set its spans), so
    `dispatch_wave` reads its wave and returns a ticket that holds the
    result. The lane state is on the device all the same: the draft wave
    reads it, the verify wave reads and returns it, the chunk arms it.
    """
    pipelined = False

    def __init__(self, model, draft_model, spec_k=4, **kw):
        if draft_model is None:
            raise ValueError("SpeculativePagedEngine needs a draft_model")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        for m in (model, draft_model):
            why = m.paged_layout().refusal(
                "speculative decoding (the draft and verify waves and the "
                "roll-back of a rejected draft)")
            if why:
                raise ValueError(why)
        self.spec_k = int(spec_k)
        draft_model.eval()
        self.draft_model = draft_model
        self._draft_params, self._draft_buffers = \
            draft_model.functional_state()
        if int(draft_model.cfg.vocab_size) != int(model.cfg.vocab_size):
            raise ValueError(
                f"draft vocab {draft_model.cfg.vocab_size} != target "
                f"vocab {model.cfg.vocab_size}: acceptance-rejection "
                "compares distributions over ONE vocabulary")
        self._wave_spec_len = None
        self.last_spec_proposed = 0
        self.last_spec_accepted = 0
        super().__init__(model, **kw)

    def describe(self):
        d = super().describe()
        d.update({"engine": "spec_paged", "spec_k": self.spec_k})
        return d

    # ---------------------------------------------------------- caches
    def _make_caches(self):
        # ONE bundle, donated through every program: the target pools
        # and the draft pools ride together so each program updates its
        # half in place and passes the other through aliased
        tgt = super()._make_caches()
        draft = self.draft_model.init_paged_cache(
            self.block_pool.num_blocks, self.block_size, self.max_len,
            dtype=self.cache_dtype)
        return (tgt, draft)

    # -------------------------------------------------------- programs
    def _build_programs(self):
        model, draft, k = self.model, self.draft_model, self.spec_k
        kern, chunk_len = self.paged_kernel, self.prefill_chunk_len

        def draft_wave(dp, db, caches, lane_tok, lane_pos, lanes, bias, key):
            """k+1 draft decode steps in ONE executable: step j writes
            the fed token's K/V at pos+j and proposes the next; the
            final step is write-only (it commits d_k's K/V so a fully
            accepted span leaves the draft cache synchronized). Writes
            past a lane's spec_len land in the scratch block via a
            scratch table row — per-step, per-lane, still one program.
            Takes the engine's key and returns the next one; the steps
            draw from a chain of the subkey's own."""
            engine_key, key = jax.random.split(key)
            tables, a = unpack_lanes(lanes)
            pos, spec_len, sample = lane_pos, a["spec_len"], a["sample"]
            tgt_caches, dr_caches = caches
            cur = lane_tok
            toks, probs = [], []
            for j in range(k + 1):
                tab_j = jnp.where((j <= spec_len)[:, None], tables,
                                  jnp.int32(BlockPool.SCRATCH))
                with paged_attention.kernel_scope(kern):
                    out, _ = draft.functional_call(
                        dp, db, cur[:, None], dr_caches, pos + j,
                        method="decode_step", block_tables=tab_j)
                logits, dr_caches = out
                if j == k:
                    break               # write-only step: no proposal
                lo = _raw(logits)[:, 0, :].astype(jnp.float32) + bias
                greedy = jnp.argmax(lo, axis=-1).astype(jnp.int32)
                scaled = lo / jnp.maximum(a["temp"], 1e-6)[:, None]
                filt = _filter_top_k_top_p(scaled, a["top_k"], a["top_p"])
                key, sub = jax.random.split(key)
                sampled = jax.random.categorical(
                    sub, filt, axis=-1).astype(jnp.int32)
                cur = jnp.where(sample, sampled, greedy)
                toks.append(cur)
                probs.append(jax.nn.softmax(filt, axis=-1))
            return (jnp.stack(toks, axis=1), jnp.stack(probs, axis=1),
                    (tgt_caches, dr_caches), engine_key)

        def spec_verify(p, b, caches, lane_tok, lane_pos, lanes, bias,
                        draft_toks, draft_probs, key):
            """Verify-once: ONE target forward scores all k+1 positions
            of every lane (the chunk program's own model call, at
            [S, k + 1] with every lane's start and span and no frontier),
            then the exact acceptance-rejection tail."""
            key, sub = jax.random.split(key)
            tables, a = unpack_lanes(lanes)
            tgt_caches, dr_caches = caches
            chunk = jnp.concatenate([lane_tok[:, None], draft_toks],
                                    axis=1)
            with paged_attention.kernel_scope(kern):
                out, _ = model.functional_call(
                    p, b, chunk, tgt_caches, method="prefill_chunk",
                    block_tables=tables, chunk_start=lane_pos,
                    valid_len=a["spec_len"] + 1)
            logits, tgt_caches = out
            lo = _raw(logits).astype(jnp.float32)       # [S, k+1, V]
            out_toks, n_emit, nxt, new_pos, finite = _spec_verify_tail(
                lo, lane_tok, lane_pos, a["active"], a["sample"],
                a["temp"], a["top_k"], a["top_p"], bias, a["spec_len"],
                draft_toks, draft_probs, a["poison"], sub)
            return (out_toks, n_emit, finite, (tgt_caches, dr_caches),
                    nxt, new_pos, key)

        def prefill_chunk(p, b, caches, dp, db, lane_tok, lane_pos, prompt,
                          bias, key):
            """The spec configuration's ONE prefill program: the chunk
            writes the TARGET pools (frontier logits select the first
            token, exactly the non-spec chunk) AND the DRAFT pools — a
            draft cache synchronized at admission is what lets the
            first decode wave start drafting immediately, and a
            prefix-cache hit skips the chunk for both models at once."""
            key, sub = jax.random.split(key)
            table, chunk, a = unpack_prompt(prompt, chunk_len)
            step = dict(method="prefill_chunk", block_tables=table[None, :],
                        chunk_start=a["start"], valid_len=a["valid"],
                        frontier=a["frontier"])
            tgt_caches, dr_caches = caches
            with paged_attention.kernel_scope(kern):
                out, _ = model.functional_call(p, b, chunk[None, :],
                                               tgt_caches, **step)
                logits, tgt_caches = out
                dout, _ = draft.functional_call(dp, db, chunk[None, :],
                                                dr_caches, **step)
            _, dr_caches = dout         # draft frontier logits unused
            lo = _raw(logits)[0, 0].astype(jnp.float32)
            first = _select_first_token(lo, a["sample"], a["temp"],
                                        a["top_k"], a["top_p"], bias, sub)
            return (first, (tgt_caches, dr_caches),
                    *arm_lane(lane_tok, lane_pos, a, first), key)

        self._draft_wave_fn = draft_wave
        self._decode_wave_fn = spec_verify
        self._prefill_fn = prefill_chunk
        # the verify wave's, as the plain wave's; the draft wave reads
        # the lane state and leaves it to the verify, and the chunk takes
        # the draft's weights between the caches and the lane state
        self._program_donate_argnums = (2, 3, 4)
        self._draft_donate_argnums = (2,)
        self._prefill_donate_argnums = (2, 5, 6)
        self._draft_wave = self.jit(draft_wave, "paged_spec_draft_wave",
                                    donate=self._draft_donate_argnums)
        self._decode_wave = self.jit(spec_verify, "paged_spec_verify",
                                     donate=self._program_donate_argnums)
        self._prefill = self.jit(prefill_chunk, "paged_spec_prefill_chunk",
                                 donate=self._prefill_donate_argnums)

    @property
    def draft_compiles(self):
        """Compiled draft-wave programs (compile-once: stays 1)."""
        return self._draft_wave._cache_size() if self._jit else 0

    def _prefill_chunk_args(self, slot):
        return (self._params, self._buffers, self._caches,
                self._draft_params, self._draft_buffers)

    # ----------------------------------------------------------- waves
    def _prepare_wave(self, active_now):
        """Back every position the wave may write — pos .. pos+spec_len
        per lane (draft writes + the verify chunk's span) — with
        allocated, exclusively-owned blocks. Allocation is atomic per
        lane; a lane that cannot get its full span is starved out of
        the wave and preempted by recompute, exactly like the
        single-token engine."""
        starved, bs = [], self.block_size
        first_of = (self.slot_pos // bs).tolist()
        last_of = ((self.slot_pos + self._wave_spec_len) // bs).tolist()
        for s in np.flatnonzero(active_now).tolist():
            last_bi = last_of[s]
            blocks = self._slot_blocks[s]
            try:
                self._pages[0].back(s, last_bi)
                for bi in range(first_of[s], last_bi + 1):
                    if self.block_pool.refcount(blocks[bi]) > 1:
                        self._ensure_private(s, bi)
            except BlockPoolExhausted:
                starved.append(s)
                active_now[s] = False
        self.last_starved_slots = starved
        return active_now

    def _rollback_spec_blocks(self, wave_slots):
        """Return speculated-ahead blocks the acceptance did not commit:
        after the wave, a lane needs exactly the blocks covering its
        committed positions [0, pos) — anything past that was allocated
        for rejected draft tokens and goes straight back to the pool
        (refcount-clean: fresh spec blocks are never hashed and never
        shared). Skipping this (the chaos no-rollback control) leaves
        the pool holding blocks for tokens that never existed."""
        bs = self.block_size
        for s in wave_slots:
            blocks = self._slot_blocks[s]
            needed = max(1, (int(self.slot_pos[s]) + bs - 1) // bs)
            if len(blocks) > needed:
                extra = blocks[needed:]
                del blocks[needed:]
                self._tables[s, needed:] = 0
                self.block_pool.release(extra)

    def dispatch_wave(self, skip=()):
        """One speculative wave: draft k, verify once, accept exactly,
        and READ (this engine is not `pipelined`). The ticket holds
        {slot: [tokens]} — 1..k+1 tokens per healthy lane (the
        scheduler streams them in order and retires mid-batch on
        eos/budget/stop). Poisoned/non-finite lanes emit nothing, are
        listed in `last_nonfinite_slots`, and their speculation is
        rolled back with the rest."""
        if len(skip):
            raise ValueError("a speculative wave is read before the next "
                             "is dispatched: no lane has a token in flight")
        self.last_nonfinite_slots = []
        active_now = self.slot_active.copy()
        if not active_now.any():
            self.last_starved_slots = []
            return None
        if chaos.enabled():
            chaos.fire(chaos.DECODE_WAVE,
                       active=int(np.count_nonzero(active_now)))
        # per-lane draft span: the horizon clamps it (writes stop at
        # max_len - 1), a dynamic token-mask lane runs at 0 — the
        # verify chunk then degenerates to the plain single-token wave
        # for that lane, mask applied, same program
        limit = np.maximum(self.max_len - 1 - self.slot_pos, 0)
        spec_len = np.where(active_now & ~self.slot_dynamic_mask,
                            np.minimum(self.spec_k, limit),
                            0).astype(np.int32)
        self._wave_spec_len = spec_len
        pid = self.trace_pid
        with RecordEvent("serving/wave/blocks", pid=pid) as ev:
            active_now = self._prepare_wave(active_now)
        self._acc("wave.blocks", ev)
        if not active_now.any():
            return None
        with RecordEvent("serving/wave/stage", pid=pid) as ev:
            lanes, bias = self._lane_args(
                active_now, self._wave_poison(),
                self._wave_tables(active_now), spec_len)
        self._acc("wave.stage", ev)
        with RecordEvent("serving/wave/dispatch", pid=pid) as ev:
            # both programs take the one packed argument, sent once. The
            # draft wave reads no active mask out of it: inactive lanes
            # ride scratch table rows and their proposals are discarded
            # by the verify tail's active where. Each program splits the
            # key it is given and hands the next on: the draft's subkey,
            # then the verify's, as two eager splits in a row would draw
            # them
            lanes = jax.device_put(lanes)
            draft_toks, draft_probs, self._caches, self._key = \
                self._draft_wave(
                    self._draft_params, self._draft_buffers, self._caches,
                    self._lane_tok, self._lane_pos, lanes, bias, self._key)
            out_toks, n_emit, finite, self._caches, self._lane_tok, \
                self._lane_pos, self._key = self._decode_wave(
                    self._params, self._buffers, self._caches,
                    self._lane_tok, self._lane_pos, lanes, bias,
                    draft_toks, draft_probs, self._key)
        self._dispatched("wave.dispatch", ev, finite)
        with RecordEvent("serving/wave/wait", pid=pid) as ev:
            # the lane state is read before the next program takes it
            out_toks, n_emit, finite, nxt, new_pos = jax.device_get(
                (out_toks, n_emit, finite, self._lane_tok, self._lane_pos))
        self._read_back("wave.wait", ev)
        # a lane whose logits went non-finite is frozen in-program; the
        # caller must retire it before the next wave
        ok = active_now & finite
        self.slot_pos[ok] = new_pos[ok]
        self.slot_tok[ok] = nxt[ok]
        waved = np.flatnonzero(active_now).tolist()
        emitted = np.flatnonzero(ok).tolist()
        out = {s: out_toks[s, :n].tolist()
               for s, n in zip(emitted, n_emit[ok].tolist())}
        self.last_nonfinite_slots = np.flatnonzero(
            active_now & ~finite).tolist()
        self.last_spec_proposed = int(spec_len[ok].sum())
        # the extra token is never a draft's
        self.last_spec_accepted = int(n_emit[ok].sum()) - len(emitted)
        # rejected-token blocks go back NOW, poisoned lanes included —
        # the pool must never hold blocks for tokens that don't exist
        self._rollback_spec_blocks(waved)
        return self._ticket(np.flatnonzero(active_now), advance=0,
                            tokens=out)

    def _health(self):
        h = super()._health()
        h.update(speculative=True, spec_k=self.spec_k,
                 draft_compiles=self.draft_compiles)
        return h
