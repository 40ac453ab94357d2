"""paddle_tpu.serving.paged — block-table KV cache, chunked prefill,
prefix sharing.

Tier-1 tests share ONE tiny LLaMA model between a paged and a dense
engine (2 layers, hidden 64 — the scale every serving suite uses, so
the persistent cache shares compiles with tests/test_serving.py and
scripts/chaos_serving.py) and prove the acceptance contract:

  * the paged engine is TOKEN-IDENTICAL to the dense baseline under a
    fixed seed — single request and a multi-wave mixed-length stream —
    while both compiled programs stay at exactly one executable;
  * prefix sharing dedupes identical prompt prefixes onto the same
    physical blocks WITHOUT changing a single output token (and the
    BlockPool's refcount/COW machinery holds at the unit level);
  * chunked prefill folds a long prompt between decode waves — decoding
    lanes make progress while the long admission is mid-prefill;
  * pool exhaustion never crashes: admission waits for blocks,
    mid-decode starvation preempts by recompute and the resumed request
    still produces the same tokens.
"""
import collections

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (BlockPool, BlockPoolExhausted,
                                PagedServingEngine, Scheduler,
                                ServingEngine)
from paddle_tpu.utils import chaos, telemetry

VOCAB = 128
MAX_LEN = 64
BLOCK = 8
CHUNK = 16
MAX_NEW = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def paged(model):
    return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                              block_size=BLOCK, num_blocks=33,
                              prefill_chunk_len=CHUNK)


@pytest.fixture(scope="module")
def dense(model):
    return ServingEngine(model, num_slots=4, max_len=MAX_LEN,
                         prefill_len=CHUNK)


def _prompt(seed, n=5):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _stream(engine, jobs):
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=m) for p, m in jobs]
    sched.run()
    return sched, reqs


# ---------------------------------------------------------------------------
# parity vs the dense baseline
# ---------------------------------------------------------------------------

def test_single_request_token_identical_to_dense(paged, dense):
    for seed in (0, 3):
        prompt = _prompt(seed)
        assert Scheduler(paged).generate(prompt, max_tokens=MAX_NEW) == \
            Scheduler(dense).generate(prompt, max_tokens=MAX_NEW)


def test_mixed_length_multiwave_stream_token_identical(paged, dense):
    """12 requests on 4 slots (3 admission waves), mixed prompt lengths
    and budgets: every request's tokens equal the dense engine's, with
    retire/refill churn on both sides and ONE compiled program each."""
    rng = np.random.RandomState(1)
    jobs = [(rng.randint(0, VOCAB, (int(rng.randint(2, 14)),)).tolist(),
             int(rng.randint(2, 10))) for _ in range(12)]
    _, pr = _stream(paged, jobs)
    _, dr = _stream(dense, jobs)
    assert [r.output_tokens for r in pr] == [r.output_tokens for r in dr]
    assert [r.finish_reason for r in pr] == [r.finish_reason for r in dr]
    assert paged.decode_compiles == 1
    assert paged.prefill_compiles == 1


def test_block_utilization_reported(paged):
    """The scheduler samples the pool each round: a paged stream's
    snapshot carries a real utilization and it reflects tokens, not
    num_slots * max_len (4 short requests can't plausibly fill the
    pool)."""
    rng = np.random.RandomState(2)
    jobs = [(rng.randint(0, VOCAB, (6,)).tolist(), 4) for _ in range(4)]
    sched, _ = _stream(paged, jobs)
    snap = sched.metrics.snapshot()
    assert snap["block_utilization"] is not None
    assert 0 < snap["block_utilization"] < 1
    assert paged.block_pool.used == 0          # all blocks back home


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------

def test_prefix_sharing_hits_and_tokens_unchanged(model, paged):
    """Two requests sharing a 3-full-block prefix: the second admission
    hits the prefix cache (counted per block), shares physical blocks,
    and BOTH produce exactly the tokens an engine with sharing DISABLED
    produces."""
    rng = np.random.RandomState(9)
    prefix = rng.randint(0, VOCAB, (3 * BLOCK,)).tolist()
    prompts = [prefix + [5, 6], prefix + [9, 11]]

    noshare = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                 block_size=BLOCK, num_blocks=33,
                                 prefill_chunk_len=CHUNK,
                                 prefix_sharing=False)
    want = [Scheduler(noshare).generate(p, max_tokens=MAX_NEW)
            for p in prompts]

    h0, m0 = paged.block_pool.prefix_hits, paged.block_pool.prefix_misses
    sched = Scheduler(paged)
    r1 = sched.submit(prompt=prompts[0], max_tokens=MAX_NEW)
    sched.run()                              # first writes + registers
    r2 = sched.submit(prompt=prompts[1], max_tokens=MAX_NEW)
    sched.run()                              # second re-hits the blocks
    assert [r1.output_tokens, r2.output_tokens] == want
    assert paged.block_pool.prefix_hits - h0 == 3
    snap = sched.metrics.snapshot()
    assert snap["prefix_hits"] == 3
    assert snap["prefix_hit_rate"] > 0


def test_shared_blocks_live_while_both_requests_decode(model):
    """Concurrent sharing: two requests admitted back-to-back share the
    prefix blocks (refcount 2) while BOTH decode, and still match the
    unshared outputs — divergence lands in private blocks only."""
    engine = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                block_size=BLOCK, num_blocks=33,
                                prefill_chunk_len=CHUNK)
    rng = np.random.RandomState(12)
    prefix = rng.randint(0, VOCAB, (2 * BLOCK,)).tolist()
    prompts = [prefix + [3], prefix + [7]]
    want = [Scheduler(engine).generate(p, max_tokens=MAX_NEW)
            for p in prompts]                 # serial = no concurrency
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=MAX_NEW) for p in prompts]
    sched.step()                              # both admitted
    shared = set(engine._slot_blocks[reqs[0].slot][:2]) & \
        set(engine._slot_blocks[reqs[1].slot][:2])
    assert len(shared) == 2                   # physical dedup, live
    assert all(engine.block_pool.refcount(b) == 2 for b in shared)
    sched.run()
    assert [r.output_tokens for r in reqs] == want


def test_block_pool_refcount_and_cow_units():
    """Host-level BlockPool semantics: alloc/release/refcounts, hash
    retention on the free list with LRU eviction, revival of a cached
    block, and the copy-on-write guard."""
    pool = BlockPool(num_blocks=5, block_size=4)      # 4 usable
    a = pool.alloc(2)
    assert pool.used == 2 and pool.refcount(a[0]) == 1
    with pytest.raises(BlockPoolExhausted):
        pool.alloc(3)
    # share + cow: a shared block is never handed back to the writer
    pool.acquire(a[0])
    assert pool.refcount(a[0]) == 2
    new = pool.cow(a[0])
    assert new != a[0] and pool.refcount(a[0]) == 1
    assert pool.refcount(new) == 1
    exclusive = pool.cow(new)
    assert exclusive == new                    # refcount 1: no copy
    # prefix cache: hash survives release, revives on match, evicts LRU
    toks = list(range(4))
    h, = pool.prompt_hashes(toks)
    pool.register_hash(a[1], h)
    pool.release([a[1]])
    assert pool.refcount(a[1]) == 0
    blocks, hashes = pool.match_prefix(toks + [9])   # revive off free
    assert blocks == [a[1]] and pool.refcount(a[1]) == 1
    assert pool.prefix_hits == 0               # counted at ADMISSION,
    pool.count_prefix(len(blocks), 0)          # not per lookup (queue-
    assert pool.prefix_hits == 1               # head retries don't
                                               # inflate the rate)
    pool.release([a[1]])
    # allocation prefers uncached blocks and evicts the cached one LAST
    got = pool.alloc(2)
    assert got[0] != a[1]                      # uncached first
    assert a[1] in got                         # then evicted (hash gone)
    assert pool.match_prefix(toks)[0] == []    # the hash is gone
    with pytest.raises(ValueError, match="double free"):
        pool.release([a[0], a[0]])


class _OneListPool(BlockPool):
    """The allocator as it stood before the two free lists: ONE list in
    freeing order, scanned from its oldest entry for a block without a
    hash, the oldest evicted when there is none. Linear in the free
    list for every block, which is why it went; kept here as the oracle
    for the block ids the two-list pool has to hand out."""

    def __init__(self, num_blocks, block_size):
        self._free = collections.OrderedDict(
            (b, None) for b in range(1, num_blocks))
        super().__init__(num_blocks, block_size)
        del self._free_plain, self._free_cached

    @property
    def used(self):
        return self.usable - len(self._free)

    def alloc(self, n):
        if n > len(self._free):
            raise BlockPoolExhausted(f"need {n}, {len(self._free)} free")
        out = []
        for _ in range(n):
            blk = next((b for b in self._free
                        if b not in self._block_hash), None)
            if blk is None:
                blk = next(iter(self._free))       # evict oldest cached
            del self._free[blk]
            h = self._block_hash.pop(blk, None)
            if h is not None:
                del self._hash_to_block[h]
                self.evictions += 1
            self._ref[blk] = 1
            out.append(blk)
        return out

    def release(self, blocks):
        for blk in blocks:
            if self._ref[blk] < 1:
                raise ValueError(f"double free of block {blk}")
            self._ref[blk] -= 1
            if self._ref[blk] == 0:
                self._free[blk] = None

    def match_prefix(self, tokens):
        blocks, hashes = [], []
        for h in self.prompt_hashes(tokens):
            blk = self._hash_to_block.get(h)
            if blk is None:
                break
            if self._ref[blk] == 0:
                del self._free[blk]
            self._ref[blk] += 1
            blocks.append(blk)
            hashes.append(h)
        return blocks, hashes


_POOL_OPS = ("admit", "release", "grow", "acquire", "cow", "import",
             "exhaust", "drain")
_POOL_OP_P = (0.30, 0.27, 0.15, 0.06, 0.06, 0.06, 0.06, 0.04)


def _drive_pool(pool, seed, density, steps):
    """One random life of a pool: admissions that share prefixes,
    decode growth, releases, extra references, copy-on-write, block
    hand-off and exhaustion. `density` is the share of admitted prompts
    whose full blocks are hashed. Returns the log of everything the
    pool answered; what a step does follows from the answers before
    it, so two pools that answer alike are driven alike."""
    rng = np.random.RandomState(seed)
    bs = pool.block_size
    longest = max(1, min(pool.usable // 3, 160))        # blocks a prompt
    docs = [rng.randint(0, 50, (rng.randint(1, longest + 1) * bs,)).tolist()
            for _ in range(4)]
    held, log = [], []

    def take(fn, arg):
        """What an allocating call answered: (its result, the cached
        blocks it evicted with their hashes), or "exhausted" with
        nothing taken."""
        cached, before = dict(pool._block_hash), pool.outstanding()
        try:
            got = fn(arg)
        except BlockPoolExhausted:
            assert pool.outstanding() == before
            assert pool._block_hash == cached
            return "exhausted"
        gone = sorted(set(cached.items()) - set(pool._block_hash.items()))
        return got, gone

    for step in range(steps):
        op = _POOL_OPS[rng.choice(len(_POOL_OPS), p=_POOL_OP_P)]
        if not held and op in ("release", "grow", "acquire", "cow",
                               "import"):
            op = "admit"
        pick = rng.randint(len(held)) if held else 0
        if op == "admit":
            if rng.rand() < 0.7:                # begins with a document
                toks = docs[rng.randint(len(docs))] + rng.randint(
                    0, 50, (rng.randint(0, 2 * bs),)).tolist()
            else:
                toks = rng.randint(50, 99, (rng.randint(
                    1, longest * bs + 1),)).tolist()
            hashed = rng.rand() < density
            shared, _ = pool.match_prefix(toks)
            got = take(pool.alloc, -(-len(toks) // bs) - len(shared))
            if got == "exhausted":
                pool.release(shared)
            else:
                held.append(shared + got[0])
                if hashed:
                    for blk, h in zip(held[-1], pool.prompt_hashes(toks)):
                        pool.register_hash(blk, h)
            log.append((shared, got))
        elif op == "release":
            pool.release(held.pop(pick))
        elif op == "grow":
            got = take(pool.alloc, 1)
            if got != "exhausted":
                held[pick].extend(got[0])
            log.append(got)
        elif op == "acquire":
            blk = held[pick][rng.randint(len(held[pick]))]
            pool.acquire(blk)
            held.append([blk])
        elif op == "cow":
            i = rng.randint(len(held[pick]))
            got = take(pool.cow, held[pick][i])
            if got != "exhausted":
                held[pick][i] = got[0]
            log.append(got)
        elif op == "import":
            manifest = pool.export_blocks(held[pick])
            got = take(pool.import_blocks, manifest)
            if got != "exhausted":
                for blk, entry in zip(got[0], manifest):
                    if entry["hash"] is not None:
                        pool.register_hash(blk, entry["hash"])
                held.append(got[0])
            log.append(got)
        elif op == "exhaust":
            log.append(take(pool.alloc, pool.usable - pool.used + 1))
        else:
            while held:
                pool.release(held.pop())
        log.append((step, op, pool.stats()))
        if step % 20 == 0:
            log.append((pool.outstanding(),
                        sorted(pool._block_hash.items())))
    while held:
        pool.release(held.pop())
    log.append((pool.outstanding(), pool.stats(),
                sorted(pool._block_hash.items())))
    return log


@pytest.mark.parametrize("density", [0.0, 0.5, 1.0],
                         ids=["none", "half", "all"])
@pytest.mark.parametrize("num_blocks", [5, 64, 4097])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_two_free_lists_hand_out_the_one_list_pools_ids(seed, num_blocks,
                                                        density):
    """The two-list pool against the one-list oracle over one random
    life each: the same block ids in the same order, the same
    refcounts, `stats()`, eviction victims, and BlockPoolExhausted at
    the same calls with nothing taken."""
    steps = 800 if num_blocks > 64 else 400
    want = _drive_pool(_OneListPool(num_blocks, 4), seed, density, steps)
    got = _drive_pool(BlockPool(num_blocks, 4), seed, density, steps)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, f"log entry {i}"
    assert len(got) == len(want)
    final = got[-1][1]
    assert got[-1][0] == {}                     # every reference returned
    # the life reached both regimes it is there for
    assert (final["evictions"] > 0) == (density > 0)
    assert any(e == "exhausted" for e in got)


class _CountedFreeList(collections.OrderedDict):
    """A free list that refuses to be walked and counts the calls it
    serves: each of them touches one entry."""
    calls = 0

    def _walked(self, *args, **kwargs):
        raise AssertionError("a BlockPool method iterated a free list")

    __iter__ = __reversed__ = keys = values = items = _walked

    def _counted(name):
        def method(self, *args, **kwargs):
            type(self).calls += 1
            return getattr(collections.OrderedDict, name)(
                self, *args, **kwargs)
        return method

    popitem = _counted("popitem")
    __getitem__ = _counted("__getitem__")
    __setitem__ = _counted("__setitem__")
    __delitem__ = _counted("__delitem__")
    del _counted


def _free_list_calls(num_blocks):
    """Calls on the free lists for 200 x `alloc(1)`, their release and
    a reviving `match_prefix` over 100 blocks, on a pool whose every
    free block carries a hash."""
    pool = BlockPool(num_blocks, 4)
    toks = list(range(100 * 4))
    blocks = pool.alloc(pool.usable)
    for blk, h in zip(blocks, pool.prompt_hashes(toks)):
        pool.register_hash(blk, h)
    for blk in blocks[100:]:
        pool.register_hash(blk, blk)            # any hashable will do
    pool.release(blocks[100:] + blocks[:100])   # the prompt's: newest
    pool._free_plain = _CountedFreeList(pool._free_plain)
    pool._free_cached = _CountedFreeList(pool._free_cached)
    _CountedFreeList.calls = 0
    taken = [pool.alloc(1)[0] for _ in range(200)]
    assert taken == blocks[100:300] and pool.evictions == 200
    pool.release(taken)
    revived, _ = pool.match_prefix(toks)
    assert revived == blocks[:100]
    assert pool.used == 100
    return _CountedFreeList.calls


def test_block_pool_never_walks_a_free_list():
    """The cost that went does not come back: with 50,000 free hashed
    blocks `alloc(1)`, `release` and a reviving `match_prefix` touch a
    constant number of free-list entries a block (the one-list scan
    visited all 50,000 for every block), and iterate neither list."""
    calls = _free_list_calls(50_001)
    assert calls == _free_list_calls(501)       # whatever the pool's size
    assert calls <= 4 * (200 + 200 + 100)


def test_evictions_counted_once_plain_blocks_are_gone():
    """`evictions`: 0 while plain blocks remain, one a block once they
    are gone, unchanged by a revival; in `stats()` and as a telemetry
    counter."""
    name = "serving_prefix_cache_evictions_total"
    base = telemetry.value(name, default=0)
    pool = BlockPool(num_blocks=6, block_size=4)        # 5 usable
    toks = list(range(8))
    a = pool.alloc(5)
    for blk, h in zip(a, pool.prompt_hashes(toks)):
        pool.register_hash(blk, h)                      # a[0], a[1] cached
    pool.release(a)
    assert pool.alloc(3) == a[2:]                       # plain ones first
    assert pool.evictions == 0
    revived, _ = pool.match_prefix(toks)                # off the cached list
    assert revived == a[:2] and pool.evictions == 0
    pool.release(revived)
    assert pool.alloc(1) == a[:1] and pool.evictions == 1
    assert pool.alloc(1) == a[1:2] and pool.evictions == 2
    assert pool.stats()["evictions"] == 2
    assert pool.stats()["cached_hashes"] == 0
    assert telemetry.value(name, default=0) - base == 2
    with pytest.raises(BlockPoolExhausted):
        pool.alloc(1)
    assert pool.evictions == 2


def test_cow_under_forced_sharing_keeps_tokens(model, paged):
    """Safety net made real: force-share a slot's decode write target
    mid-stream — the engine must copy-on-write (one lazily compiled
    copy program) and finish with tokens identical to an unshared run."""
    prompt = _prompt(21, n=BLOCK + 1)          # decode writes block 1
    want = Scheduler(paged).generate(prompt, max_tokens=MAX_NEW)
    sched = Scheduler(paged)
    req = sched.submit(prompt=prompt, max_tokens=MAX_NEW)
    sched.step()                               # admit + first wave
    slot = req.slot
    bi = paged.slot_pos[slot] // paged.block_size
    blk = paged._slot_blocks[slot][bi]
    paged.block_pool.acquire(blk)              # simulate another holder
    try:
        sched.run()
    finally:
        paged.block_pool.release([blk])
    assert req.output_tokens == want
    assert paged.decode_compiles == 1          # COW is a separate tiny
                                               # program, not a recompile


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_long_prompt_chunks_fold_between_decode_waves(paged):
    """A 3-chunk prompt admits while three short requests decode: the
    decoding lanes gain tokens during rounds in which the long prompt
    is still mid-prefill — admission never stalls the wave — and the
    long request's output equals a solo run (chunked == monolithic)."""
    rng = np.random.RandomState(4)
    long_prompt = rng.randint(0, VOCAB, (2 * CHUNK + 5,)).tolist()
    # NOTE: the solo reference runs AFTER the measured stream — running
    # it first would register the prompt's block hashes and the measured
    # admission would skip its first two chunks via the prefix cache
    # (cached chunks fold to nothing, which is the point, but not THIS
    # test's point)

    sched = Scheduler(paged)
    shorts = [sched.submit(prompt=_prompt(30 + i), max_tokens=12)
              for i in range(3)]
    sched.step()                               # shorts active + decoding
    long_req = sched.submit(prompt=long_prompt, max_tokens=5)
    progressed_mid_prefill = 0
    while long_req.state == "QUEUED" or \
            long_req.slot in paged.prefilling_slots():
        before = sum(len(r.output_tokens) for r in shorts)
        sched.step()
        mid = (long_req.slot is not None
               and long_req.slot in paged.prefilling_slots())
        if mid and sum(len(r.output_tokens) for r in shorts) > before:
            progressed_mid_prefill += 1
    sched.run()
    assert progressed_mid_prefill >= 1
    want = Scheduler(paged).generate(long_prompt, max_tokens=5)
    assert long_req.output_tokens == want      # chunked == solo (which
                                               # itself re-hits the cache)
    assert all(r.finish_reason == "max_tokens" for r in shorts)
    assert paged.prefill_compiles == 1         # every chunk, one program


def test_unaligned_final_chunk_rope_exact(model, paged):
    """Regression: a final chunk overrunning the RoPE table (chunk 24,
    50-token prompt -> last chunk covers [48, 72) over the 64-row
    table) must gather rotations per position — a dynamic_slice clamps
    the slice START and silently shifts RoPE for the chunk's VALID
    tokens. Reference: the module engine's aligned 16-token chunks."""
    engine = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                block_size=BLOCK, num_blocks=33,
                                prefill_chunk_len=24)
    prompt = _prompt(80, n=50)
    assert Scheduler(engine).generate(prompt, max_tokens=MAX_NEW) == \
        Scheduler(paged).generate(prompt, max_tokens=MAX_NEW)


def test_decode_wave_never_writes_through_midprefill_tables(model):
    """Regression: the wave program scatters EVERY lane's K/V (fixed
    shapes) — while a multi-chunk prompt is mid-prefill, a decode wave
    driven by OTHER lanes must not write its stale token through the
    pending slot's already-populated (possibly shared) block table. The
    wave uploads scratch rows for non-wave lanes; the chunk's written
    content must survive bit-exact."""
    engine = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                block_size=BLOCK, num_blocks=33,
                                prefill_chunk_len=CHUNK)
    engine.prefill_slot(0, _prompt(70))            # active decoder
    engine.begin_prefill(1, _prompt(71, n=2 * CHUNK + 3))   # 3 chunks
    assert not engine.prefill_step(1)              # chunk 0 written
    blk0 = engine._slot_blocks[1][0]
    before = np.asarray(engine._caches[0])[blk0].copy()
    engine.decode_wave()                           # slot 0 decodes
    after = np.asarray(engine._caches[0])[blk0]
    np.testing.assert_array_equal(before, after)


def test_drain_mid_chunked_prefill_completes_request(model):
    """drain() arriving while a chunked prefill is mid-fold (the gap
    PR 9's staged admission left): the remaining chunks still run, the
    request emits its full output, and only THEN does the engine report
    drained — an accepted long prompt is never abandoned half-folded."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=33,
                             prefill_chunk_len=CHUNK)
    prompt = _prompt(82, n=2 * CHUNK + 5)          # 3 chunks
    # the solo reference runs AFTER the measured stream: running it
    # first would register the prompt's block hashes and the measured
    # admission would skip its chunks via the prefix cache — leaving
    # nothing mid-fold for drain() to arrive during
    sched = Scheduler(eng)
    req = sched.submit(prompt=prompt, max_tokens=4)
    sched.step()                        # admit + chunk 1 of 3
    assert req.slot in eng.prefilling_slots()      # genuinely mid-fold
    sched.drain()
    assert eng.health_state == "draining"
    assert req.slot in eng.prefilling_slots()      # drain didn't abort it
    waves = sched.run()
    assert waves >= 1
    assert req.finish_reason == "max_tokens"
    assert sched.in_flight() == 0 and sched.queue_depth() == 0
    assert not eng.prefilling_slots()
    assert eng.block_pool.used == 0
    want = Scheduler(eng).generate(prompt, max_tokens=4)
    assert req.output_tokens == want    # chunked-through-drain == solo


def test_paged_healthz_reports_pool_and_queue(paged):
    """/healthz satellite fields on the paged engine: queue_depth (from
    the attached scheduler) and cache_blocks_used/cache_blocks_total
    (mirroring the gauges) in one payload."""
    import json as _json

    from paddle_tpu.utils import telemetry
    sched = Scheduler(paged)
    reqs = [sched.submit(prompt=_prompt(90 + i), max_tokens=3)
            for i in range(6)]                     # 4 slots + 2 queued
    sched.step()
    status, _, body = telemetry.http_get_inline(
        "/healthz", health_fn=paged._health)
    payload = _json.loads(body)
    assert status == 200 and payload["status"] == "ok"
    assert payload["queue_depth"] == sched.queue_depth() >= 1
    assert payload["cache_blocks_total"] == paged.block_pool.usable
    assert payload["cache_blocks_used"] == paged.block_pool.used > 0
    sched.run()
    assert all(r.done for r in reqs)
    status, _, body = telemetry.http_get_inline(
        "/healthz", health_fn=paged._health)
    payload = _json.loads(body)
    assert payload["queue_depth"] == 0
    assert payload["cache_blocks_used"] == 0


def test_prompt_longer_than_chunk_but_full_horizon_rejected(paged):
    """Chunked prefill removes the dense bucket limit — a prompt longer
    than the chunk admits fine — but the horizon still binds."""
    ok = _prompt(40, n=CHUNK + 3)              # > chunk: fine now
    assert Scheduler(paged).generate(ok, max_tokens=2)
    too_long = _prompt(41, n=MAX_LEN)          # no room to decode
    with pytest.raises(ValueError, match="no room to decode"):
        Scheduler(paged).submit(prompt=too_long, max_tokens=2)


# ---------------------------------------------------------------------------
# pool exhaustion: queueing + preemption by recompute
# ---------------------------------------------------------------------------

def test_injected_admission_exhaustion_requeues_and_completes(paged):
    """Payload-injected allocator exhaustion on the second admission:
    the request waits at the queue head behind in-flight work, then
    admits — outputs identical to a fault-free run."""
    jobs = [(_prompt(50 + i, n=4 + i), 6) for i in range(4)]
    _, ref = _stream(paged, jobs)
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.CACHE_ALLOC, action="payload", payload=True, times=(2,))])
    with chaos.active(monkey):
        sched, reqs = _stream(paged, jobs)
    assert monkey.fired
    assert [r.output_tokens for r in reqs] == \
        [r.output_tokens for r in ref]
    assert sched.metrics.snapshot()["faults"]["cache_exhausted"] == 1


def test_organic_starvation_preempts_by_recompute(model):
    """A pool too small for four long-running requests: starved lanes
    are preempted (blocks freed, request requeued with prompt +
    generated tokens), everyone completes, and every output equals a
    solo run — recompute + prefix re-hits are exact, not approximate."""
    small = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                               block_size=BLOCK, num_blocks=9,
                               prefill_chunk_len=CHUNK)   # 8 usable
    rng = np.random.RandomState(6)
    jobs = [(rng.randint(0, VOCAB, (14,)).tolist(), 12)
            for _ in range(4)]
    sched, reqs = _stream(small, jobs)
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert sum(r.preemptions for r in reqs) >= 1
    assert sched.metrics.snapshot()["faults"]["cache_exhausted"] >= 1
    for (p, m), r in zip(jobs, reqs):
        assert Scheduler(small).generate(p, max_tokens=m) == \
            r.output_tokens
    assert small.decode_compiles == 1
    assert small.prefill_compiles == 1


def test_never_fitting_prompt_rejected_cleanly(model):
    """A prompt needing more blocks than the pool owns is shed at
    submit with a clean ValueError, not an exhaustion loop."""
    tiny = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                              block_size=BLOCK, num_blocks=3,
                              prefill_chunk_len=CHUNK)    # 2 usable
    with pytest.raises(ValueError, match="KV blocks"):
        Scheduler(tiny).submit(prompt=_prompt(60, n=3 * BLOCK),
                               max_tokens=2)


# ---------------------------------------------------------------------------
# scheduler bookkeeping under the paged engine (review regressions)
# ---------------------------------------------------------------------------

def test_prefix_hits_sampled_on_immediate_retire(model):
    """A request whose prefill emits the first token and retires in the
    SAME round (max_tokens=1) still leaves its prefix hits and a pool
    sample in the snapshot — the working-round sample must key off the
    round's admissions, not just post-round active/prefilling sets."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=33,
                             prefill_chunk_len=CHUNK)
    prompt = _prompt(75, n=2 * BLOCK)
    Scheduler(eng).generate(prompt, max_tokens=2)   # warm the prefix cache
    sched = Scheduler(eng)
    req = sched.submit(prompt=prompt, max_tokens=1)
    sched.run()
    assert req.finish_reason == "max_tokens"
    assert len(req.output_tokens) == 1
    snap = sched.metrics.snapshot()
    assert snap["prefix_hits"] >= 2                 # both full blocks re-hit
    assert snap["block_utilization"] is not None


def test_prefix_evictions_reach_the_snapshot(model):
    """A pool that distinct prompts have filled with cached blocks hands
    the next ones out by eviction, and the scheduler's snapshot reports
    the pool's count over its own lifetime."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=9,
                             prefill_chunk_len=CHUNK)   # 8 usable
    pool = eng.block_pool
    sched = Scheduler(eng)
    for seed in range(80, 83):                  # 3 blocks each, 2 hashed
        sched.generate(_prompt(seed, n=2 * BLOCK), max_tokens=2)
    assert pool.evictions == 0                  # plain blocks lasted
    assert sched.metrics.snapshot()["prefix_evictions"] == 0
    later = Scheduler(eng)
    for seed in range(83, 86):
        later.generate(_prompt(seed, n=2 * BLOCK), max_tokens=2)
    assert pool.evictions > 0
    assert later.metrics.snapshot()["prefix_evictions"] == pool.evictions
    assert pool.stats()["evictions"] == pool.evictions


def test_timeout_mid_chunked_prefill_retires_without_tokens(model):
    """An expired request must not keep consuming prefill chunk
    programs or emit a post-expiry first token: the round after its
    deadline passes retires it with finish_reason "timeout"."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=33,
                             prefill_chunk_len=CHUNK)
    sched = Scheduler(eng)
    req = sched.submit(prompt=_prompt(76, n=3 * CHUNK), max_tokens=4,
                       timeout=30.0)
    sched.step()                        # admit + chunk 1 of 3
    assert eng.prefilling_slots()
    req.submit_time -= 60.0             # expire it between chunks
    sched.step()
    assert req.done
    assert req.finish_reason == "timeout"
    assert req.output_tokens == []
    assert not eng.prefilling_slots()
    assert eng.block_pool.used == 0     # mid-prefill blocks all freed


@pytest.mark.slow
def test_all_starved_wave_not_counted_in_occupancy(model):
    """A wave where every active lane starves dispatches no program and
    must not inflate the occupancy integral: every counted wave emits
    exactly its counted number of tokens."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=5,
                             prefill_chunk_len=CHUNK)     # 4 usable
    sched = Scheduler(eng)
    waves = []
    orig = sched.metrics.on_wave
    sched.metrics.on_wave = (
        lambda n, **kw: (waves.append(n), orig(n, **kw))[1])
    reqs = [sched.submit(prompt=_prompt(70 + i, n=BLOCK), max_tokens=12)
            for i in range(2)]
    sched.run()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    admissions = len(reqs) + sum(r.preemptions for r in reqs)
    assert admissions > len(reqs)       # starvation actually happened
    decode_tokens = sum(len(r.output_tokens) for r in reqs) - admissions
    assert sum(waves) == decode_tokens
    assert all(n >= 1 for n in waves)


# ---------------------------------------------------------------------------
# the step a lane runs too many (serving/scheduler.py: a token is read
# one program after it is made)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3 * BLOCK, 3 * BLOCK + 5, BLOCK - 1],
                         ids=["full-blocks", "part-block", "no-full-block"])
def test_the_step_run_too_many_writes_no_block_a_later_owner_reads(model, n):
    """A request that ends on a stop sequence has ridden one wave more
    when the scheduler finds out, and that wave's K/V row went through
    the lane's table: into a block the lane still owned, at a position
    past the prompt. The prompt's hashed blocks hold bit for bit what the
    chunks wrote, the next request with the same prompt shares them and
    streams what a fresh engine streams, and the pool gets every block
    back."""
    def fresh():
        return PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                                  block_size=BLOCK, num_blocks=17,
                                  prefill_chunk_len=CHUNK)

    prompt = _prompt(130 + n, n=n)
    ref = Scheduler(fresh()).generate(prompt, max_tokens=MAX_NEW)
    # ends where these three tokens first stand at the stream's end (a
    # model from a seed repeats itself: wherever that is, the host finds
    # out by reading them)
    gram = ref[1:4]
    cut = next(i for i in range(2, MAX_NEW) if ref[i - 2:i + 1] == gram)
    eng = fresh()
    sched = Scheduler(eng)
    req = sched.submit(prompt=prompt, max_tokens=MAX_NEW,
                       stop_sequences=[gram])
    hashed = before = None
    while not req.done:
        sched.step()
        if before is None and req.output_tokens:
            hashed = list(eng._slot_blocks[req.slot][:n // BLOCK])
            before = [np.asarray(pool)[hashed].copy()
                      for pool in eng._caches]
    assert req.finish_reason == "stop"
    assert req.output_tokens == ref[:cut + 1]
    # the wave that carried the lane once more is still unread
    assert len(sched._waves) == 1
    assert eng.block_pool.used == 0
    for pool, kept in zip(eng._caches, before):
        np.testing.assert_array_equal(np.asarray(pool)[hashed], kept)
    again = sched.generate(prompt, max_tokens=MAX_NEW)
    assert again == ref
    assert sched.metrics.snapshot()["prefix_hits"] == n // BLOCK
    assert not sched._waves and eng.block_pool.used == 0
