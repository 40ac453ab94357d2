"""The paged cache: the block pool's stored forms, their one write, and
attention over them through the block tables.

This is the one module that knows how a layer's pool is laid out. A
model file calls `paged_attend` (write the new positions' K/V through
the tables, then attend the new queries over the pool) and names no
shape of the pool; the serving engines allocate it through
`init_block_kv` and pass it through their programs donated. A model
with latent attention keeps one row a position that all heads share: the
second stored form, with its own entry point `paged_attend_latent` and
its two ways through the pool, at the end of this file.

Two cores attend, behind one dispatch point:

  kernel="reference"  gather the lanes' pages into a `[B, Hkv, nblk*BS,
                      D]` view (`gather_block_kv`), then a plain masked
                      softmax over it: plain XLA, runs anywhere, and the
                      oracle every test holds the kernel to.
  kernel="pallas"     a Pallas TPU kernel that reads K/V straight out of
                      the pool through the table with an online softmax,
                      so the gathered view never exists. Grid over
                      (lanes, groups of kv-heads, query tiles, steps of
                      pages). A step takes whole pages as the pool stores
                      them (the [heads, BS, 2D] slab of every kv-head of
                      the group) through the BlockSpec index_map over a
                      scalar-prefetch walk of the table, and only the
                      pages its query tile attends (`attended_pages`,
                      from the tile's position, its queries and the
                      window): a step outside them names the page it
                      already holds, so it moves no bytes, and skips the
                      arithmetic, and the step dimension ends where the
                      call's furthest lane does (a traced grid bound).
                      Accumulators sit in VMEM scratch across the
                      sequential step dimension. What a step scores
                      follows the keys' side (`_tile`): up to 32 pages,
                      hundreds of keys, in both forms. A wave (one
                      query a lane, or the verify wave's k + 1) takes
                      them for every kv-head at once, because a pass
                      through the recurrence costs its latency however
                      little it scores; a chunk takes them for one
                      kv-head against all of the chunk's queries, cut
                      into query tiles (each with its own bounds) only
                      where VMEM would not hold them. `interpret=True`
                      off the TPU so tier-1 exercises the real kernel
                      body; the body keeps every value 2-D, which is
                      what the TPU compiler accepts
                      (tests/test_tpu_compile.py compiles it for v5e at
                      the cells' real shapes).

Which core a call gets (`resolve_kernel`): an explicit `kernel=`
argument, else the innermost active `kernel_scope(...)` (how a serving
engine pins, at trace time, the core it was built with), else what the
backend decides: "pallas" on a TPU, "reference" anywhere else. No
environment variable and no process-wide setting choose; an explicit
"pallas" reaches the compiler as is, and nothing here catches a refusal
or falls back. What the chip measured of the cores is in PERF.md.

One attention shape covers every paged program: C queries a lane at
absolute positions `start + i`. The decode wave is C == 1, the prefill
chunk one lane of C == chunk, the speculative verify wave every lane at
C == k + 1.

Masking contract: masked/out-of-window scores are hard-excluded with
`-inf` *before* the max/exp, and fully-masked rows (all-scratch lanes,
padded chunk tails) renormalise through a guarded `where(l == 0, 0,
acc / l)` instead of softmaxing over a uniform large-negative row.
Scratch-block garbage — which may be non-finite — therefore cannot reach
the engines' isfinite poison sentinel, while a genuine non-finite value
at any *attended* position still propagates to the logits.
"""
import contextlib
import functools

import numpy as np

KERNELS = ("reference", "pallas")

_SCOPE_STACK = []           # innermost kernel_scope override, LIFO


def _check(kernel):
    if kernel not in KERNELS:
        raise ValueError(f"unknown paged kernel {kernel!r}: "
                         f"expected one of {KERNELS}")
    return kernel


@contextlib.contextmanager
def kernel_scope(kernel):
    """Pin the kernel inside a `with` block. The serving engines trace
    their jitted programs inside this scope, so the core an engine was
    built with is the one its programs keep, whichever thread or engine
    traced first (tracing runs the Python body; the compiled program
    keeps whatever the scope resolved)."""
    _SCOPE_STACK.append(_check(kernel))
    try:
        yield
    finally:
        _SCOPE_STACK.pop()


def resolve_kernel(kernel=None):
    """The core a call gets: the explicit argument, else the innermost
    kernel_scope, else "pallas" on a TPU and "reference" elsewhere."""
    if kernel is not None:
        return _check(kernel)
    if _SCOPE_STACK:
        return _SCOPE_STACK[-1]
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "reference"


# ---------------------------------------------------------------------------
# the pool's stored form, and its one write
# ---------------------------------------------------------------------------
# The pool is ONE array a layer, [num_blocks, Hkv, block_size, 2 * D]: a
# position's K row in [..., :D] and its V row beside it in [..., D:]. A
# request's cache is the ordered sequence of pool blocks (pages) named by
# its block TABLE (int32 block ids, host-managed by
# serving.paged.BlockPool). All shapes below are static — table entries
# are VALUES, not shapes — so one compiled program serves every
# allocation pattern (compile-once). Block 0 is the scratch block:
# inactive/invalid lanes are redirected there; nothing in it is kept
# (every write zeroes it) and no surviving lane reads it at a position
# it attends (the ks <= pos mask and the active-lane `where`).
#
# Why this form, and who has to keep it. A serving program is handed the
# pool donated and hands it back; it stays where it is only if the write,
# the attention kernel and the program's parameter and result all take
# one layout. Row-major [.., BS, 2D] is that layout: 2D is 128 lanes at
# head_dim 64 and 256 at 128, so a page of one kv-head is whole (16, 128)
# bf16 tiles with nothing padded, which is what the chip picks for the
# parameter by itself and what the Pallas core's BlockSpec takes; and the
# write below moves whole pages, which the compiler updates in place in
# that layout (a scatter of single rows, `pool.at[blk, :, row].set`, is
# given a layout with the row dimension outermost, and the whole pool is
# copied there and back: PERF.md, PR 28). Every program that touches the
# pool (decode wave, prefill chunk, draft and verify waves, copy-on-write,
# hand-off, state reset) takes and returns this array as it is;
# tests/test_tpu_compile.py holds the serving programs to no pool-sized
# copy at the benchmark's shapes.


def init_block_kv(num_blocks, hkv, block_size, head_dim, dtype):
    """A layer's empty pool in the stored form (see above)."""
    import jax.numpy as jnp
    return jnp.zeros((num_blocks, hkv, block_size, 2 * head_dim), dtype)


def gather_block_kv(pool, tables):
    """Materialise per-row K and V views from the block pool. pool:
    [NB, Hkv, BS, 2D]; tables: [B, nblk] int32 → two [B, Hkv, nblk*BS, D],
    position p of row b living at pool[tables[b, p // BS], :, p % BS].
    One gather — the paged analog of reading the dense [B, Hkv, L, D]
    cache (same bytes streamed when nblk*BS == L)."""
    import jax.numpy as jnp
    g = pool[tables]                           # [B, nblk, Hkv, BS, 2D]
    b, nblk, hkv, bs, d2 = g.shape
    g = jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(b, hkv, nblk * bs, d2)
    return g[..., :d2 // 2], g[..., d2 // 2:]


def write_block_kv(pool, k, v, tables, start, valid_len=None):
    """Write C new positions a lane into the pool: k, v [S, Hkv, C, D]
    land at absolute positions start[s] + i, i < valid_len[s], through
    the block tables [S, nblk]; position p of lane s lives at
    pool[tables[s, p // BS], :, p % BS] (K in [..., :D], V in [..., D:]).
    `start` and `valid_len` are traced scalars or [S] vectors;
    valid_len=None writes all C. The one write of every paged program
    that keeps K/V: the decode wave (C == 1), a prefill chunk (S == 1;
    the padded tail of the last chunk lies past valid_len), the
    speculative verify wave (every lane, its own start and span). How it
    moves pages: `_write_pages`."""
    import jax.numpy as jnp
    return _write_pages(pool, jnp.concatenate([k, v], axis=-1), tables,
                        start, valid_len)


def _write_pages(pool, new, tables, start, valid_len=None):
    """The write of both stored forms: new [S, Hkv, C, W] into pool
    [NB, Hkv, BS, W] (the latent form comes with Hkv == 1, a view).

    It moves whole pages. The C positions of a lane touch at most
    ceil((C - 1) / BS) + 1 pages wherever they start; each is gathered,
    the rows the lane writes are replaced, and the page is scattered
    back, so a row outside [start, start + valid_len) keeps its bits. A
    candidate page that holds no written row (a chunk that ends on a page
    boundary, a lane with valid_len 0, a page past the table's end) is
    redirected to the scratch block, as is every page of a retired lane
    (the host points its table row there). Distinct lanes write distinct
    pages — frontier pages are private by the copy-on-write guard — and
    a prefill chunk that runs over prefix-shared pages rewrites in them
    what they hold.

    Every write also zeroes the scratch block. The queries of a padded
    tail (i >= valid_len) are computed and thrown away, but they attend
    keys past the lane's last written position, which the table maps to
    scratch where the lane has no page yet; a non-finite value there
    would reach the lane's good rows as 0 * nan in `p @ V` (the cores
    zero only the rows that no query attends). So scratch is finite by
    the time a program's first layer attends, whatever an earlier fault
    left in it, and the colliding writes to it all carry the same
    zeros."""
    import jax.numpy as jnp
    s, hkv, c, _ = new.shape
    bs, nblk = pool.shape[2], tables.shape[1]
    kv = new.astype(pool.dtype)
    start = jnp.broadcast_to(jnp.reshape(start, (-1,)), (s,))
    valid = c if valid_len is None else jnp.minimum(
        jnp.broadcast_to(jnp.reshape(valid_len, (-1,)), (s,)), c)
    valid = jnp.reshape(valid, (-1, 1, 1))
    npages = (c - 1 + bs - 1) // bs + 1
    first = (start // bs)[:, None] + jnp.arange(npages)        # [S, np]
    # row r of candidate page i holds the lane's new position number
    # `src` (negative, or past valid_len: not this write's)
    src = (first * bs - start[:, None])[:, :, None] + jnp.arange(bs)
    # a padded tail or a clamped span can reach past the table: nothing
    # is written there, and the table gather is clamped
    mine = (src >= 0) & (src < valid) & (first < nblk)[:, :, None]
    written = jnp.any(mine, axis=-1)                           # [S, np]
    blk = jnp.take_along_axis(tables, jnp.minimum(first, nblk - 1), axis=1)
    blk = jnp.where(written, blk, 0).reshape(-1)
    new = jnp.take_along_axis(
        kv[:, None], jnp.clip(src, 0, c - 1)[:, :, None, :, None], axis=3)
    old = pool[blk].reshape(s, npages, hkv, bs, -1)
    pages = jnp.where(mine[:, :, None, :, None], new, old)
    # scratch gets zeros: from every page redirected there, from a
    # retired lane's table row, and once more in case there is neither
    pages = jnp.where((blk > 0).reshape(s, npages, 1, 1, 1), pages, 0)
    pages = pages.reshape((s * npages,) + pool.shape[1:])
    return pool.at[jnp.append(blk, 0)].set(
        jnp.concatenate([pages, jnp.zeros_like(pages[:1])]))


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def paged_attend(q, k, v, pool, tables, start, valid_len, scale,
                 window=None, kernel=None):
    """What a model's attention layer says to the paged cache: write the
    C new positions' k, v [B, Hkv, C, D] through the tables (nothing at
    i >= valid_len; None writes all C), then attend q [B, H, C, D] over
    the pool, each query at its own absolute position start + i.
    `start` and `valid_len` are traced scalars or [B] vectors. Returns
    (out [B, H, C, D] in pool.dtype, the pool)."""
    pool = write_block_kv(pool, k, v, tables, start, valid_len)
    return attend(q, pool, tables, start, scale, window=window,
                  kernel=kernel), pool


def attend(q, pool, tables, start, scale, window=None, kernel=None):
    """Attention over the block pool as it stands: C queries a lane at
    absolute positions start + i (start: traced scalar or [B] vector).
    q: [B, H, C, D]; pool: [NB, Hkv, BS, 2D] (the stored form); tables:
    [B, nblk] int32. Query row i attends the keys ks <= start + i, banded
    to the last `window` when given. Returns [B, H, C, D] in pool.dtype."""
    if resolve_kernel(kernel) == "pallas":
        return _pallas_core(q, pool, tables, start, scale, window)
    return _reference_core(q, pool, tables, start, scale, window)


# ---------------------------------------------------------------------------
# the oracle: gather the lanes' pages, then a plain masked softmax
# ---------------------------------------------------------------------------

def _reference_core(q, pool, tables, start, scale, window=None):
    """Gather-then-attend over an L = nblk*BS position view of each
    lane's pages (which already holds this call's own K/V). Grouped
    (GQA) without materialising the repeated cache, exactly like the
    dense `cached_decode_attention` (C == 1 of this is that function).
    The gathered view contains scratch-block positions (masked by
    construction) whose garbage may be non-finite, so the V rows NO query
    attends are zeroed: a 0-probability key with non-finite garbage
    would still produce 0 * nan == nan in the probs @ V contraction.
    Keys attended by at least one query keep their value, so a GENUINE
    non-finite at an attended position propagates to that lane's logits
    (the poison sentinel); for finite caches this is bitwise a no-op."""
    import jax.numpy as jnp
    from .transformer import _masked_softmax

    ck, cv = gather_block_kv(pool, tables)
    b, h, c, d = q.shape
    hkv, L = ck.shape[1], ck.shape[2]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, c, d)
    scores = jnp.einsum("bkrcd,bkld->bkrcl", qf,
                        ck.astype(jnp.float32)) * scale
    if jnp.ndim(start):
        start = jnp.reshape(start, (b, 1, 1, 1, 1))
    qpos = start + jnp.arange(c).reshape(1, 1, 1, c, 1)
    ks = jnp.arange(L).reshape(1, 1, 1, 1, L)
    mask = ks <= qpos
    if window is not None:
        mask = mask & (ks > qpos - window)
    probs = _masked_softmax(scores, mask).astype(cv.dtype)
    attended = jnp.any(mask, axis=3)[:, 0, 0, :, None]     # [B, L, 1]
    cv = jnp.where(attended[:, None], cv, jnp.zeros((), cv.dtype))
    out = jnp.einsum("bkrcl,bkld->bkrcd", probs, cv)
    return out.reshape(b, h, c, d)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (kv-head groups, the query tiles' steps of pages
# end to end), table gather in the BlockSpec index_map over a
# scalar-prefetch walk of the block table; only the pages a tile attends
# are stepped over, fetched and computed
# ---------------------------------------------------------------------------

def attended_pages(start, c, bs, nblk, window=None):
    """(lo, hi): the table entries [lo, hi) of a lane whose `c` queries
    sit at `start .. start + c - 1`. Between them the rows attend keys
    in (start - window, start + c - 1], from 0 without a window; a
    padded chunk tail can reach past the table, so `hi` stops at `nblk`.
    A lane that attends nothing the table holds (no key yet, or a window
    wholly past it) gets hi == lo, so `hi - lo` always counts its pages.

    Pure integer arithmetic on numpy values (the engine's count of pages
    a wave visits) and on traced scalars (the kernel's own bounds)
    alike; a page outside [lo, hi) contributes probability 0 and rescale
    1 to the recurrence, so skipping it is exact."""
    if isinstance(start, (int, np.integer, np.ndarray)):
        xp = np
    else:
        import jax.numpy as xp
    lo = 0 * start if window is None else \
        xp.maximum(start - (window - 1), 0) // bs
    hi = xp.maximum(xp.minimum((start + (c - 1)) // bs + 1, nblk), lo)
    return lo, hi


#: VMEM a call asks the compiler for (a v5e core has 128 MiB, the
#: compiler's own default is 16), and what the rule lets a step plan of it
_VMEM_LIMIT = 64 << 20
_VMEM_PLAN = 40 << 20
#: pages a step takes at least and at most (each is one more pipelined
#: operand; 64 of them compile and run on a v5e)
_MIN_PAGES, _MAX_PAGES = 8, 64
#: rows (group members x queries) of one kv-head a query tile holds
_MAX_ROWS = 2048
#: query rows of ALL kv-heads under which a call is a wave (one query a
#: lane, or the verify wave's k + 1): every kv-head shares a step
_WAVE_ROWS = 512

_LANES = 128


def _width(d):
    """Width of the kernel's query, key and value operands at head size
    `d`: D where the stored [K | V] slab can be cut at D (a multiple of
    a vreg's 128 lanes), else the whole slab's 2D."""
    return d if d % _LANES == 0 else 2 * d


def _step_vmem(heads, pages, rows, d, bs, itemsize):
    """Bytes of VMEM one step of the kernel plans for `rows` query rows
    against `pages` pages of `heads` kv-heads: the pipelined operands
    twice (queries, pages, output), the accumulators (a `[rows, 1]`
    float32 column is stored a whole vreg of lanes wide), the pages as
    one slab in the pool's dtype and in float32, and four tiles the
    size of the scores (scores, probabilities, masks)."""
    w = _width(d)
    cols = pages * heads * bs
    return (2 * rows * w * itemsize + 2 * cols * 2 * d * itemsize
            + 3 * rows * w * 4 + 2 * rows * _LANES * 4
            + cols * 2 * d * (itemsize + 4) + 4 * rows * cols * 4)


@functools.lru_cache(maxsize=None)
def _tile(c, rep, hkv, d, bs, nblk, itemsize):
    """(kv-heads a step, pages a step, queries a tile) from the call's
    shapes: what one pass of the recurrence scores. Written from the
    kernel timed alone on a v5e at the four served configurations' wave
    and chunk shapes (scripts/paged_kernel_sweep.py; PERF.md section 6,
    PR 36); every term below is what that table said.

    Pages: a quarter of the table, as a power of two, within
    `_MIN_PAGES` .. `_MAX_PAGES` (16 of gpt2-small's 64, 64 of
    Mistral's 160, 32 of Nemotron's 128, 64 of Granite's 1,088) and
    within the table. A pass costs its latency whatever it scores: a
    visited step of a wave takes about 0.35 us plus 0.04-0.09 us a page
    (Mistral's 64 KB pages arrive at 750 GB/s once a step holds 16),
    so a lane's context in one or two passes beats eight (Granite's
    wave 2.65 ms a layer at 4 pages a step, 1.36 at 32, 1.30 at 64; a
    chunk's layer over 6k keys 3.25 ms at 4, 0.72 at 32, 0.50 at 64).
    Past the contexts the table is there for, a step scores columns no
    lane attends (gpt2-small's wave 0.76 ms at 16 pages, 0.99 at 32,
    1.71 at 64: its lanes hold 14 pages in the mean), and the table's
    length is what a call's shapes say of them.

    Heads and queries: a wave (all kv-heads' rows within `_WAVE_ROWS`)
    takes every kv-head in one step, scored in one product whose
    cross-head entries are masked, because one kv-head a step runs the
    pass once a head (gpt2-small's wave 2.4-2.9 ms a layer with a loop
    over the heads inside the step, 12.6 with the heads in the grid,
    0.76 in one product). A chunk takes one kv-head a step, so nothing
    is masked away, against all its queries and group members up to
    `_MAX_ROWS` rows (Granite's 2,048: 0.50 ms a layer at 64 pages,
    0.63-0.67 in tiles of 1,024 rows, 0.54 in tiles of 512); a longer
    chunk is cut into query tiles that divide it, each with its own
    `attended_pages`. Then pages are halved until `_step_vmem` fits
    `_VMEM_PLAN`."""
    if hkv * rep * c <= _WAVE_ROWS:
        heads, cq = hkv, c
    else:
        heads = 1
        cq = max(t for t in range(1, c + 1)
                 if c % t == 0 and (rep * t <= _MAX_ROWS or t == 1))
    quarter = 1 << max(nblk // 4 - 1, 0).bit_length()
    pages = min(max(quarter, _MIN_PAGES), _MAX_PAGES, nblk)
    while pages > 1 and _step_vmem(heads, pages, heads * rep * cq, d, bs,
                                   itemsize) > _VMEM_PLAN:
        pages //= 2
    return heads, pages, cq


def _step_plan(start, c, bs, nblk, window, pages, cq):
    """The steps of a call whose lanes' first queries sit at `start`
    [B], in query tiles of `cq` and steps of `pages` table entries:
    (first, count, visits, lo, hi), each [B * C // cq], one entry a
    (lane, query tile) in that order, `lo` and `hi` the tile's
    `attended_pages`. A tile's steps are `first .. first + count - 1`
    of its lane's table, from the step that holds the first page it
    attends to the one that holds the last, and nothing else: the grid
    is the tiles' steps laid end to end, so no step walks a page only
    another lane or a later tile attends. A tile that attends nothing
    still gets one step (`count` 1, `visits` 0), so that its output is
    written; `visits` are the steps that fetch and score. numpy or
    traced, like `attended_pages`: the kernel's grid and the engine's
    count of it are this one function."""
    xp = np
    if not isinstance(start, np.ndarray):
        import jax.numpy as xp
    tiles = (start[:, None]
             + cq * np.arange(c // cq, dtype=np.int32)).reshape(-1)
    lo, hi = attended_pages(tiles, cq, bs, nblk, window)
    first = xp.where(hi > lo, lo // pages, 0)
    visits = xp.where(hi > lo, (hi + pages - 1) // pages - first, 0)
    return first, xp.maximum(visits, 1), visits, lo, hi


def count_steps(start, c, rep, hkv, d, bs, nblk, itemsize, window=None):
    """(run, visited): the grid steps one attention layer's kernel runs
    for a call of `c` queries a lane at `start` [B] (numpy), and those
    of them that fetch and score pages (all but the one step of a tile
    that attends nothing). What the engine counts as `paged_steps_run` /
    `paged_steps_visited`."""
    heads, pages, cq = _tile(c, rep, hkv, d, bs, nblk, itemsize)
    start = np.asarray(start, np.int32).reshape(-1)
    _, count, visits, _, _ = _step_plan(start, c, bs, nblk, window, pages,
                                        cq)
    return (int(hkv // heads * count.sum()),
            int(hkv // heads * visits.sum()))


def _paged_attn_kernel(walk_ref, start_ref, tile_ref, step_ref, edge_ref,
                       rows_ref, cols_ref, keys_ref, q_ref, *refs, scale,
                       window, bs, cq, nq, nblk, pages, d):
    """One (kv-head group g, step s) grid step. The steps are those of
    every (lane, query tile) laid end to end (`_step_plan`); step s
    belongs to tile `tile_ref[s]` and is step j = `step_ref[s]` of its
    lane's table: `pages` table entries from `j * pages` on, each the
    [heads, BS, 2D] slab of the group's kv-heads as the pool
    stores it, a key's K row and V row side by side, scored as ONE tile
    against the tile's `cq` queries of every group member of every
    kv-head of the group: rows are (kv-head, group member, query) with
    the query minor, columns (page, kv-head, key in page); an entry
    counts where both name one head (`rows_ref`, `cols_ref`; with one
    kv-head a step, every chunk's form, all do). One product, one max,
    one exp, one product a step: what a step costs is that chain's
    latency, so it is not run once a head.

    At head_dim 128 the slab is cut at D, which is a vreg boundary. At
    64 it is not cut (D is the middle of a vreg's 128 lanes): the
    queries arrive zero-extended to 2D, so `q @ slab.T` is `q @ K.T`,
    the accumulator is `p @ slab`, 2D wide, and its right half, `p @ V`,
    is cut out by the caller; both are free at the MXU's width.

    The pipeline gathered the pages through the index_map, and the
    kernel folds the step into the VMEM accumulators, which persist
    across a tile's steps (`edge_ref` marks its first, which resets
    them, and its last, which writes the output). A step whose every key every row of
    the tile attends (all of a long context but its last step) needs no
    mask by position. In any other visited step a key's position
    follows from where its page stands in the table, so the pages that
    lie outside the bounds (the walk names a page inside them there)
    fall to the masks like any other key no row attends: to the
    window's below `lo`, to the causal one above `hi`, which also stops
    at the table's end; the rows of K and V no row attends are zeroed
    first, so nothing non-finite in them reaches a product.

    Operands meet the MXU in the dtype the queries came in when that is
    the pool's (bfloat16 products summed in float32 are the float32
    products of the same values), in float32 otherwise. The
    probabilities stay float32 into `p @ V`. Every value is 2-D (the
    TPU compiler refuses 1-D iotas and vector loads from SMEM); the
    running max and denominator stay `[rows, 1]`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    page_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    heads, d2 = page_refs[0].shape[1], 2 * d
    kw = _width(d)
    step = pl.program_id(1)
    tile, j, edge = tile_ref[step], step_ref[step], edge_ref[step]
    start = start_ref[tile // nq] + tile % nq * cq     # SMEM scalars
    lo, hi = attended_pages(start, cq, bs, nblk, window)
    k0 = j * (pages * bs)                              # the step's first key
    # the last key a row may attend: a padded chunk tail's rows sit past
    # the table, which ends at hi
    end = hi * bs - 1

    pl.when(edge % 2 == 1)(lambda: _softmax_init(m_ref, l_ref, acc_ref))

    def fold(masked):
        q = q_ref[0, 0]                                # [rows, kw]
        tiles = [r[0].reshape(heads * bs, d2).astype(q.dtype)
                 for r in page_refs]
        keep = None if heads == 1 else \
            cols_ref[1:2, :] == rows_ref[:, 1:2]       # [rows, cols]
        if masked:
            rowpos = start + rows_ref[:, 0:1]          # [rows, 1]
            ks = k0 + cols_ref[0:1, :]                 # [1, cols]
            near = ks <= jnp.minimum(rowpos, end)
            if window is not None:
                near &= ks > rowpos - window
            keep = near if keep is None else keep & near
            # 0 * nan == nan: the rows of K and V no query row keeps are
            # zeroed, so scratch poison cannot reach a product. The rows
            # sit at start .. start + cq - 1, so the keys some row keeps
            # are exactly (start - window, start + cq - 1]
            last = jnp.minimum(start + (cq - 1), end)
            for i, tile in enumerate(tiles):
                kpos = k0 + i * bs + keys_ref[...]     # [heads * bs, 1]
                live = kpos <= last
                if window is not None:
                    live &= kpos > start - window
                tiles[i] = jnp.where(live, tile, jnp.zeros((), tile.dtype))
        kv = tiles[0] if pages == 1 else jnp.concatenate(tiles, axis=0)
        s = jax.lax.dot_general(                       # q @ k.T
            q, kv[:, :kw], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if keep is not None:
            s = jnp.where(keep, s, -jnp.inf)
        _softmax_fold(s, kv[:, d2 - kw:].astype(jnp.float32), m_ref, l_ref,
                      acc_ref)

    whole = k0 + pages * bs - 1 <= jnp.minimum(start, end)
    if window is not None:
        whole &= k0 > start + (cq - 1) - window
    # hi == lo: the one step of a tile that attends nothing
    pl.when((hi > lo) & whole)(lambda: fold(False))
    pl.when((hi > lo) & jnp.logical_not(whole))(lambda: fold(True))

    pl.when(edge >= 2)(
        lambda: _softmax_finish(o_ref.at[0, 0], l_ref, acc_ref))


def _softmax_init(m_ref, l_ref, acc_ref):
    """The online softmax's accumulators before a lane's first step: what
    both kernels (K/V and latent) keep in VMEM scratch across the
    sequential step dimension."""
    import jax.numpy as jnp
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _softmax_fold(s, kv, m_ref, l_ref, acc_ref):
    """Fold one step's masked scores s [rows, cols] (-inf where masked)
    and its value rows kv [cols, width] into the accumulators: running
    max m, denominator l, weighted sum, rescaled by exp(m_old - m_new).
    A row that has seen only -inf keeps m = -inf and shifts by 0. The
    probabilities meet the MXU in the rows' dtype, the sums are
    float32."""
    import jax.numpy as jnp
    m_prev = m_ref[...]                                # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift)
    alpha = jnp.exp(m_prev - shift)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p.astype(kv.dtype), kv, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _softmax_finish(o_ref, l_ref, acc_ref):
    """The rows' output after their last step; a row that attended
    nothing gives 0. == 0 guard (not > 0): nan denominators must
    propagate."""
    import jax.numpy as jnp
    l = l_ref[...]
    o_ref[...] = jnp.where(l == 0, 0.0, acc_ref[...] / l)


def _pallas_call(tiles, hkv, bs, d, nq, tile, rows, total, scale, window,
                 nblk, interpret):
    """Build the pallas_call for one shape family and one `total` of
    steps (a traced scalar: the grid ends with the last tile's last
    step). The pages' BlockSpec index_maps address the pool by table
    VALUE, so the gather happens in the pipeline, page by page, never as
    a materialised [B, Hkv, nblk*BS, D] array. What they read is the
    call's `walk` (`_pallas_core`), a scalar-prefetch operand like the
    lanes' first query positions and the steps' tiles: the page each
    operand of each step takes, so an index_map is a multiplication and
    a load (with 32 operands a step the scalar core runs 32 of them a
    step). A page of a step that lies outside the tile's
    `attended_pages` names the nearest page inside them, which the
    neighbouring operand or step already names: a block index that does
    not change is not fetched again."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, pages, cq = tile
    groups = hkv // heads
    w = _width(d)
    cols = pages * heads * bs
    kernel = functools.partial(_paged_attn_kernel, scale=scale,
                               window=window, bs=bs, cq=cq, nq=nq,
                               nblk=nblk, pages=pages, d=d)

    def page_spec(i):
        def index_map(gg, ss, walk, *_):
            return walk[ss * pages + i], gg, 0, 0
        return pl.BlockSpec((1, heads, bs, 2 * d), index_map)

    def per_tile(gg, ss, walk, st, tile_of, *_):
        return tile_of[ss], gg, 0, 0

    def whole(gg, ss, *_):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(groups, total),
        in_specs=[
            pl.BlockSpec((rows, 2), whole),
            pl.BlockSpec((2, cols), whole),
            pl.BlockSpec((heads * bs, 1), whole),
            pl.BlockSpec((1, 1, rows, w), per_tile),
            *[page_spec(i) for i in range(pages)],
        ],
        out_specs=pl.BlockSpec((1, 1, rows, w), per_tile),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),        # running max m
            pltpu.VMEM((rows, 1), jnp.float32),        # running denom l
            pltpu.VMEM((rows, w), jnp.float32),        # p @ v acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, groups, rows, w),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="paged_attention")


def _pallas_core(q, pool, tables, start, scale, window=None):
    """Pallas path: an online softmax over the lane's pages (running
    max m, denominator l, weighted accumulator, rescaled by
    exp(m_old - m_new) a step), the block gather folded into the kernel
    pipeline. interpret=True off the TPU so tier-1 parity tests execute
    the genuine kernel body. The call goes through one jitted function a
    shape family (`_tiled_core`), so a program that attends in every
    layer traces and lowers the kernel once, not once a layer (half a
    second each at 64 pages a step)."""
    import jax
    (_, h, c, d), hkv = q.shape, pool.shape[1]
    tile = _tile(c, h // hkv, hkv, d, pool.shape[2], tables.shape[1],
                 pool.dtype.itemsize)
    return _tiled_core(tile, float(scale),
                       None if window is None else int(window),
                       jax.default_backend() != "tpu")(q, pool, tables,
                                                       start)


@functools.lru_cache(maxsize=None)
def _tiled_core(tile, scale, window, interpret):
    import jax
    return jax.jit(functools.partial(_attend_tiled, tile=tile, scale=scale,
                                     window=window, interpret=interpret))


def _attend_tiled(q, pool, tables, start, *, tile, scale, window, interpret):
    """`_pallas_core` for one `_tile`: the queries' re-layout, the
    schedule of steps, the kernel's call, the output's re-layout."""
    import jax
    import jax.numpy as jnp

    b, h, c, d = q.shape
    hkv, bs = pool.shape[1], pool.shape[2]
    nblk = tables.shape[1]
    rep = h // hkv
    heads, pages, cq = tile
    groups, nq = hkv // heads, c // cq
    # operands in the dtype both already have, float32 where they differ
    op = pool.dtype if q.dtype == pool.dtype else jnp.dtype(jnp.float32)
    rows = heads * rep * cq
    w = _width(d)
    start = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32),
                                         (-1,)), (b,))
    # [B, H, C, D] -> [B * C/cq, Hkv/heads, heads * rep * cq, w]: a
    # tile's rows are kv-head, then group member, then query; zeros
    # where the uncut slab holds V
    qr = q.astype(op).reshape(b, groups, heads * rep, nq, cq, d)
    qr = jnp.transpose(qr, (0, 3, 1, 2, 4, 5)).reshape(
        b * nq, groups, rows, d)
    if w > d:
        qr = jnp.pad(qr, ((0, 0),) * 3 + ((0, w - d),))
    row = np.arange(rows, dtype=np.int32)
    col = np.arange(pages * heads * bs, dtype=np.int32)
    # per row: its query's offset in the tile, its kv-head; per column:
    # its key's offset from the step's first position, its kv-head
    rowinfo = np.stack([row % cq, row // (rep * cq)], axis=1)
    colinfo = np.stack([col // (heads * bs) * bs + col % bs,
                        col // bs % heads])
    # the schedule: the tiles' steps end to end, and for every step the
    # tile it belongs to, which step of the lane's table it is, whether
    # it is the tile's first (1) and last (2), and the table entry each
    # of its page operands takes (the walk)
    first, count, _, lo, hi = _step_plan(start, c, bs, nblk, window, pages,
                                         cq)
    tiles = b * nq
    ends = jnp.cumsum(count)
    step = np.arange(tiles * -(-nblk // pages), dtype=np.int32)
    of = jnp.minimum(jnp.sum(ends[None, :] <= step[:, None], axis=1,
                             dtype=jnp.int32), tiles - 1)
    base = np.arange(tiles, dtype=np.int32) // nq * nblk
    begins, count, first, lo, hi, base = jnp.stack(
        [ends - count, count, first, lo, hi, base], axis=1)[of].T
    since = step - begins
    edge = (since == 0) + 2 * (since == count - 1)
    walk = jnp.clip((first + since)[:, None] * pages
                    + np.arange(pages, dtype=np.int32),
                    lo[:, None], hi[:, None] - 1)
    walk = tables.astype(jnp.int32).reshape(-1)[
        base[:, None] + jnp.clip(walk, 0, nblk - 1)]
    call = _pallas_call(tiles, hkv, bs, d, nq, tile, rows, ends[-1], scale,
                        window, nblk, interpret)
    # the scope, innermost at the call, is what names the instruction
    # in a device trace ("%paged_attention.1 = ... custom-call")
    with jax.named_scope("paged_attention"):
        out = call(walk.reshape(-1), start, of, first + since,
                   edge.astype(jnp.int32), jnp.asarray(rowinfo),
                   jnp.asarray(colinfo), jnp.asarray(col[:heads * bs, None]
                                                     % bs), qr,
                   *[pool] * pages)
    out = out[..., w - d:].reshape(b, nq, groups, heads * rep, cq, d)
    return jnp.transpose(out, (0, 2, 3, 1, 4, 5)).reshape(
        b, h, c, d).astype(pool.dtype)


# ---------------------------------------------------------------------------
# the latent form: one row a position, shared by every head (MLA)
# ---------------------------------------------------------------------------
# A layer with latent attention caches, for a position, the row
# [c | k_rope]: the `rank` values every head's K and V are expanded from
# (K_nope, V = c W_kv_b) and the one rotary key all heads share. Its pool
# is ONE array a layer, [num_blocks, block_size, W]: no K-beside-V halves
# and no kv-head dimension. W is rank + rope rounded up to whole vregs of
# 128 lanes (576 -> 640; the tail is zeros): the device would pad the
# minor dimension to that anyway, so the stored bytes are counted as
# they are, the parameter keeps its layout through the programs, and the
# kernel's matmuls take whole tiles. Tables, scratch block, page write
# and masking contract are the K/V form's (`_write_pages`, the pool seen
# as [NB, 1, BS, W]).
#
# Two ways to attend it, one rule (`latent_path`), by the queries a lane
# brings. ABSORBED: K and V are never made; a head's query is carried
# into the latent space (q_abs = q_nope W_UK^T), every head scores the
# same row, the output is carried back (o = (P c) W_UV). A (query, row,
# head) costs 2 (rank + rope) + 2 rank operations: 2,176 at 512 + 64.
# EXPANDED: the attended rows are put through W_kv_b once, 2 rank (nope +
# v) operations a (row, head) = 262,144 at 128 + 128, and a (query, row,
# head) then costs 2 (nope + rope) + 2 v = 640. One query a lane (the
# decode wave) is absorbed; a chunk of hundreds is expanded; the two
# cross at rank (nope + v) / (2 rank - nope - v) queries: 170.


def latent_width(rank, rope):
    """Stored width of a latent row: rank + rope in whole 128-lane
    vregs."""
    return -(-(rank + rope) // _LANES) * _LANES


def init_block_latent(num_blocks, block_size, rank, rope, dtype):
    """A layer's empty pool in the latent form (see above)."""
    import jax.numpy as jnp
    return jnp.zeros((num_blocks, block_size, latent_width(rank, rope)),
                     dtype)


def write_block_latent(pool, row, tables, start, valid_len=None):
    """Write C new positions' rows [S, C, rank + rope] into the latent
    pool [NB, BS, W], zero-extended to W: `write_block_kv`'s contract,
    whole pages moved, scratch zeroed."""
    import jax.numpy as jnp
    row = jnp.pad(row, ((0, 0), (0, 0), (0, pool.shape[-1] - row.shape[-1])))
    return _write_pages(pool[:, None], row[:, None], tables, start,
                        valid_len)[:, 0]


def latent_path(c, rank, rope, nope, v):
    """"absorbed" or "expanded" for a call of `c` queries a lane: the
    one place the choice is made (arithmetic above). Absorbed while its
    extra cost a query is under one expansion of the row."""
    extra = 2 * (2 * rank + rope) - 2 * (nope + rope + v)
    return "absorbed" if c * extra < 2 * rank * (nope + v) else "expanded"


#: pages the expanded path gathers, expands and scores at a time
_EXPAND_PAGES = 32


def expanded_rows(start, c, bs, nblk):
    """Rows one lane's chunk of `c` queries at `start` puts through
    W_kv_b on the expanded path: its attended pages, in whole tiles of
    `_EXPAND_PAGES`. numpy or traced, like `attended_pages`."""
    _, hi = attended_pages(start, c, bs, nblk)
    t = min(_EXPAND_PAGES, nblk)
    return (hi + t - 1) // t * t * bs


def paged_attend_latent(q_nope, q_rope, row, w_kv_b, pool, tables, start,
                        valid_len, scale, kernel=None):
    """What a latent-attention layer says to the paged cache: write the
    C new positions' rows [B, C, rank + rope] (`[c | k_rope]`, the key
    already rotated) through the tables, then attend q_nope [B, H, C,
    nope] and q_rope [B, H, C, rope] (rotated) over the pool, each query
    at its own absolute position start + i. w_kv_b [rank, H, nope + v]
    expands a row into every head's K_nope and V. Returns (out [B, H, C,
    v] in pool.dtype, the pool). Absorbed or expanded: `latent_path`."""
    import jax
    import jax.numpy as jnp
    pool = write_block_latent(pool, row, tables, start, valid_len)
    rank, rope, nope = w_kv_b.shape[0], q_rope.shape[-1], q_nope.shape[-1]
    c, v = q_nope.shape[2], w_kv_b.shape[-1] - nope
    if latent_path(c, rank, rope, nope, v) == "expanded":
        with jax.named_scope("mla_expand"):
            out = _expanded_core(q_nope, q_rope, w_kv_b, pool, tables,
                                 start, scale)
        return out.astype(pool.dtype), pool
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bhcn,rhn->bhcr", q_nope, w_kv_b[..., :nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs.astype(pool.dtype),
                             q_rope.astype(pool.dtype)], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pool.shape[-1] - rank - rope),))
    o_lat = attend_latent(q, pool, tables, start, scale, kernel=kernel)
    with jax.named_scope("mla_absorb"):
        out = jnp.einsum("bhcr,rhv->bhcv",
                         o_lat[..., :rank].astype(pool.dtype),
                         w_kv_b[..., nope:],
                         preferred_element_type=jnp.float32)
    return out.astype(pool.dtype), pool


def attend_latent(q, pool, tables, start, scale, kernel=None):
    """Absorbed attention over the latent pool as it stands: q [B, H, C,
    W] (a head's query in the row's own layout, zeros in the tail), pool
    [NB, BS, W], tables [B, nblk]. Every head scores the same rows, and
    the output is the probabilities' mix of the rows themselves, W wide
    (the caller keeps [..., :rank]). Returns float32 [B, H, C, W]."""
    if resolve_kernel(kernel) == "pallas":
        return _latent_pallas_core(q, pool, tables, start, scale)
    return _latent_reference_core(q, pool, tables, start, scale)


def _latent_reference_core(q, pool, tables, start, scale):
    """The oracle of the latent form: gather the lanes' pages into a
    [B, nblk*BS, W] view, then a plain masked softmax (the K/V oracle's
    contract: rows no query attends are zeroed, so scratch garbage
    cannot reach a good row as 0 * nan)."""
    import jax.numpy as jnp
    from .transformer import _masked_softmax
    b, h, c, w = q.shape
    rows = pool[tables].reshape(b, -1, w)                  # [B, L, W]
    length = rows.shape[1]
    scores = jnp.einsum("bhcw,blw->bhcl", q.astype(jnp.float32),
                        rows.astype(jnp.float32)) * scale
    start = jnp.broadcast_to(jnp.reshape(start, (-1,)), (b,))
    qpos = start[:, None, None, None] + jnp.arange(c).reshape(1, 1, c, 1)
    mask = jnp.arange(length).reshape(1, 1, 1, length) <= qpos
    probs = _masked_softmax(scores, mask).astype(rows.dtype)
    attended = jnp.any(mask, axis=2)[:, 0, :, None]        # [B, L, 1]
    rows = jnp.where(attended, rows, jnp.zeros((), rows.dtype))
    return jnp.einsum("bhcl,blw->bhcw", probs, rows,
                      preferred_element_type=jnp.float32)


def _expanded_core(q_nope, q_rope, w_kv_b, pool, tables, start, scale):
    """The expanded path, plain XLA: a loop over tiles of `_EXPAND_PAGES`
    table entries, as far as the furthest lane attends. A tile's rows
    are read through the table, expanded by W_kv_b into every head's
    K_nope and V, scored (nope and rope parts), masked, and folded into
    an online softmax, so neither the expanded prefix nor the scores of
    a whole chunk over a whole context ever exist at once."""
    import jax
    import jax.numpy as jnp
    b, h, c, nope = q_nope.shape
    rank, rope = w_kv_b.shape[0], q_rope.shape[-1]
    v = w_kv_b.shape[-1] - nope
    bs, nblk = pool.shape[1], tables.shape[1]
    t = min(_EXPAND_PAGES, nblk)
    tables = jnp.pad(tables, ((0, 0), (0, -nblk % t)))     # scratch
    start = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32),
                                         (-1,)), (b,))
    _, hi = attended_pages(start, c, bs, nblk)
    # a padded chunk tail's rows sit past the table: it ends at hi
    last = hi * bs - 1
    qpos = jnp.minimum(start[:, None] + jnp.arange(c), last[:, None])
    seen = jnp.minimum(start + (c - 1), last)              # [B]
    f32 = jnp.float32

    def tile(j, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(tables, j * t, t, axis=1)
        rows = pool[pages].reshape(b, t * bs, -1)          # [B, L, W]
        ks = j * (t * bs) + jnp.arange(t * bs)
        # rows no query attends are zeroed (0 * nan, as in the cores)
        rows = jnp.where((ks[None, :] <= seen[:, None])[..., None], rows,
                         jnp.zeros((), rows.dtype))
        kv = jnp.einsum("blr,rhd->bhld", rows[..., :rank], w_kv_b)
        s = (jnp.einsum("bhcn,bhln->bhcl", q_nope.astype(kv.dtype),
                        kv[..., :nope], preferred_element_type=f32)
             + jnp.einsum("bhcr,blr->bhcl", q_rope.astype(rows.dtype),
                          rows[..., rank:rank + rope],
                          preferred_element_type=f32)) * scale
        s = jnp.where(ks[None, None, None, :] <= qpos[:, None, :, None],
                      s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p, alpha = jnp.exp(s - shift), jnp.exp(m - shift)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "bhcl,bhlv->bhcv", p.astype(kv.dtype), kv[..., nope:],
            preferred_element_type=f32)
        return m_new, l, acc

    init = (jnp.full((b, h, c, 1), -jnp.inf, f32),
            jnp.zeros((b, h, c, 1), f32), jnp.zeros((b, h, c, v), f32))
    _, l, acc = jax.lax.fori_loop(0, (jnp.max(hi) + t - 1) // t, tile, init)
    # == 0 guard (not > 0): nan denominators must propagate
    return jnp.where(l == 0, 0.0, acc / l)


#: pages a step of the latent kernel takes (each is one more pipelined
#: operand: a page is BS rows of W, 20 KB at 16 x 640 bfloat16)
_LATENT_PAGES = 32


def _latent_kernel(tables_ref, start_ref, rows_ref, q_ref, *refs, scale,
                   bs, c, nblk, pages):
    """One (lane b, head group g, step j) grid step of the absorbed
    core: `pages` table entries of the lane from `j * pages` on, each a
    [BS, W] page of latent rows that every head of the group scores
    (`q @ rows.T`) and mixes (`p @ rows`): key and value are the same
    bytes, read once. Operands go to the MXU in the pool's dtype, sums
    and the softmax are float32. The recurrence, the bounds and the
    masking contract are `_paged_attn_kernel`'s; with no kv-head
    dimension a column IS a key, so its position is the step's first
    plus an iota."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    page_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    start = start_ref[b]                               # SMEM scalar
    _, hi = attended_pages(start, c, bs, nblk)
    cols = pages * bs

    pl.when(j == 0)(lambda: _softmax_init(m_ref, l_ref, acc_ref))

    @pl.when(j * pages < hi)
    def _visit():
        q = q_ref[0, 0]                                # [rows, W]
        tiles = [r[0] for r in page_refs]
        kv = tiles[0] if pages == 1 else jnp.concatenate(tiles, axis=0)
        first = j * cols                               # the step's first key
        last = hi * bs - 1       # a padded tail's rows end with the table
        # rows no query keeps are zeroed: 0 * nan == nan otherwise
        kcol = first + jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        kv = jnp.where(kcol <= jnp.minimum(start + (c - 1), last), kv,
                       jnp.zeros((), kv.dtype))
        s = jax.lax.dot_general(                       # q @ rows.T
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, cols]
        ks = first + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        rowpos = jnp.minimum(start + rows_ref[...], last)    # [rows, 1]
        _softmax_fold(jnp.where(ks <= rowpos, s, -jnp.inf), kv, m_ref,
                      l_ref, acc_ref)

    pl.when(j == pl.num_programs(2) - 1)(
        lambda: _softmax_finish(o_ref.at[0, 0], l_ref, acc_ref))


@functools.lru_cache(maxsize=None)
def _latent_call(b, h, c, w, bs, nblk, heads, pages, scale, dtype_name,
                 interpret):
    """The pallas_call of the absorbed core for one static shape family:
    grid (lanes, head groups, steps of pages), the table and the lanes'
    positions as scalar-prefetch operands, the pages gathered by the
    BlockSpec index_maps as in `_pallas_call` (a step past the lane's
    last attended page names that page again and moves no bytes)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = heads * c
    kernel = functools.partial(_latent_kernel, scale=scale, bs=bs, c=c,
                               nblk=nblk, pages=pages)

    def page_spec(i):
        def index_map(bb, gg, jj, tab, st):
            _, hi = attended_pages(st[bb], c, bs, nblk)
            page = jnp.minimum(jj * pages + i, hi - 1)
            return tab[bb, jnp.clip(page, 0, nblk - 1)], 0, 0
        return pl.BlockSpec((1, bs, w), index_map)

    def per_group(bb, gg, jj, tab, st):
        return bb, gg, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // heads, pl.cdiv(nblk, pages)),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda bb, gg, jj, tab, st: (0, 0)),
            pl.BlockSpec((1, 1, rows, w), per_group),
            *[page_spec(i) for i in range(pages)],
        ],
        out_specs=pl.BlockSpec((1, 1, rows, w), per_group),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),        # running max m
            pltpu.VMEM((rows, 1), jnp.float32),        # running denom l
            pltpu.VMEM((rows, w), jnp.float32),        # p @ rows acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h // heads, rows, w),
                                       jnp.float32),
        interpret=interpret, name="paged_latent_attention")


def _latent_pallas_core(q, pool, tables, start, scale):
    """Pallas path of the absorbed core. Rows of the query tile are
    (head, query) with the query minor; as many heads share a step as
    keep the tile within `_MAX_ROWS` rows (all of them in the decode
    form). interpret=True off the TPU, as `_pallas_core`."""
    import jax
    import jax.numpy as jnp

    b, h, c, w = q.shape
    bs, nblk = pool.shape[1], tables.shape[1]
    heads = max(g for g in range(1, h + 1)
                if h % g == 0 and (g * c <= _MAX_ROWS or g == 1))
    pages = min(_LATENT_PAGES, nblk)
    rows = heads * c
    start = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32),
                                         (-1,)), (b,))
    call = _latent_call(b, h, c, w, bs, nblk, heads, pages, float(scale),
                        str(pool.dtype), jax.default_backend() != "tpu")
    qoff = np.arange(rows, dtype=np.int32)[:, None] % c
    # the scope, innermost at the call, names the instruction in a
    # device trace ("%paged_latent_attention.1 = ... custom-call")
    with jax.named_scope("paged_latent_attention"):
        out = call(tables.astype(jnp.int32), start, jnp.asarray(qoff),
                   q.astype(pool.dtype).reshape(b, h // heads, rows, w),
                   *[pool] * pages)
    return out.reshape(b, h, c, w)
