"""BlockPool: host-side memory manager for the paged KV cache.

The device side is a fixed pool of KV blocks per layer —
`[num_blocks, kv_heads, block_size, head_dim]` x2, allocated once at
engine construction (serving pays HBM for the blocks it CONFIGURES, not
`num_slots * max_len`). This class owns the block ids: two free lists,
plain before cached, each oldest-freed first, with refcounts,
per-request allocation, and a hash-based prefix cache so identical
prompt prefixes (the shared-system-prompt pattern that dominates at
millions-of-users scale) map to the SAME physical blocks.

Invariants the engine relies on:

  * block 0 is the scratch block — never allocated, never hashed; the
    compiled programs redirect inactive/invalid lanes' writes there;
  * only FULL, immutable prompt blocks are hashed (chain hash: a
    block's identity covers its entire token prefix, which for a causal
    LM determines its K/V content exactly), and a hash is registered
    only AFTER the prefill chunk that wrote the block ran — a
    concurrent admission can never share a block whose content is not
    on the device yet;
  * a freed block (refcount 0) keeps its hash and stays reusable from
    the cached free list — the prefix cache survives request churn and
    is evicted lazily, oldest-freed first, only when the plain free
    list (blocks that carry no hash) is empty and allocation needs the
    block back. A block's kind cannot change while it is free (a hash
    is registered on a live block and leaves only by eviction), so
    `release` picks the list once and `alloc` pops a head: constant
    time a block, and no method ever iterates a free list;
  * `cow()` is the copy-on-write guard: writing through a block with
    refcount > 1 must first move the writer onto a private copy. With
    full-block-only sharing the decode frontier always lands in a
    private block, so this fires only as a safety net — but it is the
    load-bearing guarantee that sharing can never corrupt a neighbour.

Speculative decoding (serving/paged SpeculativePagedEngine) layers a
DRAFT model's KV pools onto the SAME block ids: one table row names the
same token span in the target pools and the draft pools, so allocation,
refcounts, prefix sharing and copy-on-write govern both at once — there
is no second allocator to leak from. Blocks allocated ahead for drafted
tokens that verification REJECTS are released the same wave
(`_rollback_spec_blocks`); `outstanding()` below is the audit surface
the chaos harness uses to prove no speculative block outlives its
tokens.

Thread-model: driven single-threaded from the scheduler's wave loop
(`Scheduler._wave_lock` serializes every engine call); producer threads
touch only the queue, never the pool.
"""
import collections
import hashlib

from ...utils import chaos
from .. import metrics as serving_metrics


class BlockPoolExhausted(RuntimeError):
    """Allocation failed: every usable block is referenced. The
    scheduler treats this as CAPACITY, not as a request fault — the
    request is queued behind the blocks it is waiting for (or preempted
    to free some), never crashed."""


class BlockPool:
    SCRATCH = 0

    def __init__(self, num_blocks, block_size):
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (one scratch + "
                             f"one usable), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # two free lists, each oldest-freed first: blocks with no
        # prefix hash, handed out first, and blocks whose cached hash
        # alloc drops only when the plain list is empty. OrderedDict
        # because both ends of what is asked are constant time there:
        # pop-oldest (alloc) and remove-by-id (a reviving match_prefix).
        # Block 0 is the scratch block and never enters either.
        self._free_plain = collections.OrderedDict(
            (b, None) for b in range(1, self.num_blocks))
        self._free_cached = collections.OrderedDict()
        self._ref = [0] * self.num_blocks
        self._hash_to_block = {}
        self._block_hash = {}
        self.prefix_hits = 0
        self.prefix_misses = 0
        # blocks handed out by dropping a cached hash: 0 while plain
        # blocks last, one a block once every free block is cached
        self.evictions = 0
        self._publish()

    # ------------------------------------------------------------- state
    @property
    def usable(self):
        """Allocatable blocks (scratch excluded)."""
        return self.num_blocks - 1

    @property
    def used(self):
        """Blocks currently referenced by at least one request."""
        return self.usable - self._nfree()

    def _nfree(self):
        return len(self._free_plain) + len(self._free_cached)

    def refcount(self, block):
        return self._ref[block]

    def outstanding(self):
        """{block_id: refcount} for every live (refcount > 0) block —
        the refcount-audit surface: after a stream drains this must be
        empty, and during one, every entry must be owned by some slot's
        table (the speculative rollback audit names leaked blocks with
        this instead of just counting them)."""
        return {b: r for b, r in enumerate(self._ref) if r > 0}

    def _publish(self):
        serving_metrics.record_block_usage(self.used, self.usable)

    # -------------------------------------------------------- allocation
    def alloc(self, n):
        """Take `n` fresh blocks (refcount 1 each). Prefers blocks with
        no cached hash; evicts prefix-cache entries oldest-freed first
        only when it must. Raises BlockPoolExhausted when fewer than `n`
        blocks are free — atomically: either all `n` or none."""
        n = int(n)
        free = self._nfree()
        if chaos.enabled():
            # payload (truthy) = simulated exhaustion; raise-action =
            # simulated allocator crash (must surface as a fault, not
            # be absorbed as capacity)
            if chaos.value(chaos.CACHE_ALLOC, need=n, free=free):
                raise BlockPoolExhausted(
                    f"injected exhaustion: need {n} block(s)")
        if n > free:
            raise BlockPoolExhausted(
                f"need {n} block(s), {free} free of "
                f"{self.usable} usable")
        out = []
        evicted = 0
        for _ in range(n):
            if self._free_plain:
                blk, _ = self._free_plain.popitem(last=False)
            else:                                  # evict oldest cached
                blk, _ = self._free_cached.popitem(last=False)
                del self._hash_to_block[self._block_hash.pop(blk)]
                evicted += 1
            self._ref[blk] = 1
            out.append(blk)
        if evicted:
            self.evictions += evicted
            serving_metrics.record_prefix_evictions(evicted)
        self._publish()
        return out

    def acquire(self, block):
        """Add one reference to an already-referenced block (sharing)."""
        if self._ref[block] < 1:
            raise ValueError(f"block {block} is not live")
        self._ref[block] += 1

    def release(self, blocks):
        """Drop one reference per block; refcount 0 returns the block to
        the end of its free list: the cached one if it carries a
        prefix-cache hash (the content stays matchable until evicted
        by alloc), the plain one if not."""
        for blk in blocks:
            if self._ref[blk] < 1:
                raise ValueError(f"double free of block {blk}")
            self._ref[blk] -= 1
            if self._ref[blk] == 0:
                if blk in self._block_hash:
                    self._free_cached[blk] = None
                else:
                    self._free_plain[blk] = None
        self._publish()

    def cow(self, block):
        """Copy-on-write guard: `block` unchanged when exclusively owned;
        otherwise allocate a fresh block, move one reference off the
        shared one, and return the new id — the CALLER must copy the
        device content before writing through it."""
        if self._ref[block] <= 1:
            return block
        new, = self.alloc(1)
        self._ref[block] -= 1
        return new

    # ------------------------------------------------------ prefix cache
    @staticmethod
    def chain_hash(prev, tokens):
        """Digest of one full block's tokens chained onto its prefix —
        equal chain hashes mean equal (prefix, block) token content,
        which for a causal LM means equal K/V content at equal
        positions. A chained sha256, NOT the builtin hash(): lookups
        serve K/V content across requests on digest equality alone, so
        a collision (adversarially constructible for hash(), which is
        also salted per process) would leak one request's cache into
        another's decode."""
        h = hashlib.sha256(b"" if prev is None else prev)
        h.update(repr(tuple(int(t) for t in tokens)).encode())
        return h.digest()

    def match_prefix(self, tokens):
        """Longest run of cached full blocks covering `tokens`' prefix.
        Returns (blocks, hashes): per matched block, one NEW reference
        (caller must release on failure) and its chain hash. Does NOT
        count hits/misses — the caller counts via count_prefix only on
        a SUCCESSFUL admission, so a request retrying at the queue head
        under pool pressure doesn't inflate the dedup-efficacy rate."""
        bs = self.block_size
        nfull = len(tokens) // bs
        blocks, hashes, h = [], [], None
        for i in range(nfull):
            h = self.chain_hash(h, tokens[i * bs:(i + 1) * bs])
            blk = self._hash_to_block.get(h)
            if blk is None:
                break
            if self._ref[blk] == 0:            # revive off the free list
                del self._free_cached[blk]
            self._ref[blk] += 1
            blocks.append(blk)
            hashes.append(h)
        self._publish()
        return blocks, hashes

    def peek_prefix_hashes(self, hashes):
        """READ-ONLY affinity probe over a precomputed chain-hash walk
        (`prompt_hashes`): how many LEADING hashes this pool holds
        right now. Takes no references, counts no hits, publishes
        nothing — the fleet router scores every replica per admission
        with this, and a probe that mutated refcounts or the hit rate
        would corrupt both (`match_prefix` is the acquiring variant)."""
        n = 0
        for h in hashes:
            if h not in self._hash_to_block:
                break
            n += 1
        return n

    def count_prefix(self, hits, misses):
        """Count one admitted prompt's prefix-cache outcome (hits =
        full blocks served from cache, misses = full blocks prefill
        must compute)."""
        self.prefix_hits += int(hits)
        self.prefix_misses += int(misses)
        serving_metrics.record_prefix_lookup(int(hits), int(misses))

    def prompt_hashes(self, tokens):
        """Chain hashes for every full block of `tokens` (registration
        schedule for the prefill path)."""
        bs = self.block_size
        out, h = [], None
        for i in range(len(tokens) // bs):
            h = self.chain_hash(h, tokens[i * bs:(i + 1) * bs])
            out.append(h)
        return out

    # --------------------------------------------------- block-level handoff
    def export_blocks(self, blocks):
        """Manifest for a block-level handoff: one entry per live block,
        carrying its prefix-cache chain hash (None for unhashed blocks —
        the partially-filled tail, or hashes another block won). The
        DEVICE content rides separately (the engine's export_slot_kv);
        this is the allocator-side half of the transfer: the importing
        pool re-allocates from the manifest and re-registers the hashes
        only after the content lands, preserving the never-share-an-
        unwritten-block invariant across pools."""
        for blk in blocks:
            if blk == self.SCRATCH:
                raise ValueError("scratch block cannot be exported")
            if self._ref[blk] < 1:
                raise ValueError(f"block {blk} is not live")
        return [{"hash": self._block_hash.get(blk)} for blk in blocks]

    def import_blocks(self, manifest):
        """Allocate fresh local blocks to receive an exported manifest —
        atomically (all or none; BlockPoolExhausted is CAPACITY, handled
        upstream exactly like an admission under pool pressure). Returns
        the new block ids in manifest order. Hashes are NOT registered
        here: the caller registers them via register_hash only after the
        device content is actually written into the new blocks."""
        return self.alloc(len(manifest))

    def register_hash(self, block, chain_hash):
        """Enter a WRITTEN full prompt block into the prefix cache. A
        hash already mapping to another live block keeps the existing
        mapping (first writer wins; the duplicate content is simply not
        shared)."""
        if self._ref[block] < 1:
            raise ValueError(f"block {block} is not live")
        if chain_hash in self._hash_to_block:
            return
        self._hash_to_block[chain_hash] = block
        self._block_hash[block] = chain_hash

    def stats(self):
        return {
            "used": self.used, "usable": self.usable,
            "block_size": self.block_size,
            "cached_hashes": len(self._hash_to_block),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "evictions": self.evictions,
        }
