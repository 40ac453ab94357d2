"""The paged K/V cache: the block pool's stored form, its one write, and
attention over it through the block tables.

This is the one module that knows how a layer's pool is laid out. A
model file calls `paged_attend` (write the new positions' K/V through
the tables, then attend the new queries over the pool) and names no
shape of the pool; the serving engines allocate it through
`init_block_kv` and pass it through their programs donated.

Two cores attend, behind one dispatch point:

  kernel="reference"  gather the lanes' pages into a `[B, Hkv, nblk*BS,
                      D]` view (`gather_block_kv`), then a plain masked
                      softmax over it: plain XLA, runs anywhere, and the
                      oracle every test holds the kernel to.
  kernel="pallas"     a Pallas TPU kernel that reads K/V straight out of
                      the pool through the table with an online softmax,
                      so the gathered view never exists. Grid over
                      (lanes, groups of kv-heads, steps of pages). A step
                      takes whole pages as the pool stores them (the
                      [heads, BS, 2D] slab of every kv-head of the group)
                      through the BlockSpec index_map over a
                      scalar-prefetch table, and only the pages the lane
                      attends (`attended_pages`, from its position, the
                      chunk and the window): a step outside them names
                      the page it already holds, so it moves no bytes,
                      and skips the arithmetic. Accumulators sit in VMEM
                      scratch across the sequential step dimension. How
                      many kv-heads and pages share a step follows the
                      shapes of the call (`_tile`): all heads and several
                      pages in the decode form, where a pass through the
                      recurrence costs the same however little it scores,
                      so one pass scores them all; a few heads and one
                      page in a 128-token chunk, where the query tile
                      fills VMEM. `interpret=True` off the TPU so tier-1
                      exercises the real kernel body; the body keeps
                      every value 2-D, which is what the TPU compiler
                      accepts (tests/test_tpu_compile.py compiles it for
                      v5e at the cells' real shapes).

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
    valid_len=None writes all C. The one write of every paged program:
    the decode wave (C == 1), a prefill chunk (S == 1; the padded tail of
    the last chunk lies past valid_len), the speculative verify wave
    (every lane, its own start and span).

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
    s, hkv, c, _ = k.shape
    bs, nblk = pool.shape[2], tables.shape[1]
    kv = jnp.concatenate([k, v], axis=-1).astype(pool.dtype)
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
# Pallas kernel: grid (lanes, kv-head groups, page steps), table gather
# in the BlockSpec index_map over the scalar-prefetch block table; only
# the pages a lane attends are fetched and computed
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


#: rows of the query tile (kv-heads x group x chunk) one step may hold:
#: float32 q, output and accumulator of 1024 x 256 are 1 MB each
_MAX_ROWS = 1024
#: pages a step takes at most (each is one more pipelined operand)
_MAX_PAGES = 8


def _tile(c, rep, hkv):
    """(kv-heads a step, pages a step) from the call's shapes. A step
    takes whole pages: the [heads, BS, 2D] slab of as many kv-heads as
    keep the query tile within `_MAX_ROWS` rows (all of them in the
    decode form, where a row is one head of one lane), scored in one
    matmul whose cross-head entries are masked. What a step costs is one
    pass through the recurrence (matmul, max, exp, matmul: about 1.4 us
    on a v5e however little it scores), so the fewer rows a page is
    scored against, the more pages share a pass: as many as keep rows x
    pages within an eighth of `_MAX_ROWS` (8 pages against a decode
    wave's 12 rows, 4 against 32, one against a chunk's hundreds; past
    that the chip's times are flat, PERF.md section 6, PR 26)."""
    rc = rep * c
    heads = max(g for g in range(1, hkv + 1)
                if hkv % g == 0 and (g * rc <= _MAX_ROWS or g == 1))
    pages = max(1, min(_MAX_PAGES, _MAX_ROWS // 8 // (heads * rc)))
    return heads, pages


def _paged_attn_kernel(tables_ref, start_ref, rows_ref, cols_ref, keys_ref,
                       q_ref, *refs, scale, window, bs, c, nblk, pages):
    """One (lane b, kv-head group g, step j) grid step: `pages` table
    entries of the lane from `j * pages` on, each the [heads, BS, 2D]
    slab of the group's kv-heads as the pool stores it, a key's K row
    and V row side by side. The slab is never cut at D (at head_dim 64
    that is the middle of a vreg's 128 lanes): the queries arrive
    zero-extended to 2D, so `q @ slab.T` is `q @ K.T` (at an attended
    key the V row is finite, or non-finite and due to propagate anyway;
    anywhere else the score is masked), the accumulator is `p @ slab`,
    2D wide, and its right half, `p @ V`, is cut out by the caller; both
    are free at the MXU's width. The pipeline
    gathered them through the index_map; if any lies inside the lane's
    `attended_pages` the kernel scores them as one tile, masks it and
    folds it into the VMEM accumulators, which persist across the
    sequential step dimension; else it does nothing. A key's position
    follows from where its page stands in the table, so the pages of a
    visited step that lie outside the bounds (the index_map names a page
    inside them there) fall to the masks like any other key no row
    attends: to the window's below `lo`, to the causal one above `hi`,
    which also stops at the table's end.

    The tile is 2-D (the TPU compiler lays vectors out over sublanes x
    lanes and refuses 1-D iotas, vector loads from SMEM and the `[:, 0]`
    / `[:, None]` casts between the two): rows are (kv-head, group
    member, query) with the query minor, columns are (page, kv-head, key
    in page); an entry counts where both name one head. The running max
    and denominator stay `[rows, 1]`; a row's query position is the
    lane's scalar `start` plus its offset within the chunk, a column's
    key position the step's first position plus its offset from it
    (`rows_ref`, `cols_ref`, and `keys_ref` for the rows of V)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    page_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    start = start_ref[b]                               # SMEM scalar
    lo, hi = attended_pages(start, c, bs, nblk, window)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def slab():
        """[pages * heads * BS, 2D] float32 of the step's pages."""
        heads, _, d2 = page_refs[0].shape[1:]
        tiles = [r[0].astype(jnp.float32).reshape(heads * bs, d2)
                 for r in page_refs]
        return tiles[0] if pages == 1 else jnp.concatenate(tiles, axis=0)

    @pl.when((j * pages < hi) & ((j + 1) * pages > lo))
    def _visit():
        qf = q_ref[0, 0]                               # [rows, 2D]: (q, 0)
        kv = slab()
        s = jax.lax.dot_general(                       # q @ k.T
            qf, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, cols]
        first = j * pages * bs                         # the step's first key
        rowpos = start + rows_ref[:, 0:1]              # [rows, 1]
        ks = first + cols_ref[0:1, :]                  # [1, cols]
        # a padded chunk tail's rows sit past the table: it ends at hi
        keep = (cols_ref[1:2, :] == rows_ref[:, 1:2]) & \
            (ks <= jnp.minimum(rowpos, hi * bs - 1))
        if window is not None:
            keep &= ks > rowpos - window
        s = jnp.where(keep, s, -jnp.inf)
        # fully-unattended keys get probability 0 but 0 * nan == nan:
        # zero the rows no query row keeps so scratch poison cannot
        # leak. The rows sit at start .. start+C-1, so the keys some row
        # keeps are exactly (start - window, start + C - 1]
        kcol = first + keys_ref[...]                   # [cols, 1]
        attended = kcol <= jnp.minimum(start + (c - 1), hi * bs - 1)
        if window is not None:
            attended &= kcol > start - window
        kv = jnp.where(attended, kv, 0.0)
        m_prev = m_ref[...]                            # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - shift)
        alpha = jnp.exp(m_prev - shift)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + \
            jnp.dot(p, kv, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # == 0 guard (not > 0): nan denominators must propagate
        l = l_ref[...]
        o_ref[0, 0] = jnp.where(l == 0, 0.0, acc_ref[...] / l)


@functools.lru_cache(maxsize=None)
def _pallas_call(b, h, c, d2, hkv, bs, nblk, heads, pages, scale, window,
                 dtype_name, interpret):
    """Build (and cache) the pallas_call for one static shape family.
    The block table and each lane's first query position ride as
    scalar-prefetch operands so the pages' BlockSpec index_maps can address
    the pool by table VALUE — the gather happens in the pipeline, page
    by page, never as a materialised [B, Hkv, nblk*BS, D] array. A step
    outside the lane's `attended_pages` names the nearest page inside
    them: a block index that does not change is not fetched again, so
    the pages nobody attends cost neither bytes nor arithmetic."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, cols = heads * (h // hkv) * c, pages * heads * bs
    kernel = functools.partial(_paged_attn_kernel, scale=scale,
                               window=window, bs=bs, c=c, nblk=nblk,
                               pages=pages)

    def page_spec(i):
        def index_map(bb, gg, jj, tab, st):
            lo, hi = attended_pages(st[bb], c, bs, nblk, window)
            page = jnp.minimum(jnp.maximum(jj * pages + i, lo), hi - 1)
            # a lane that attends nothing has hi == lo, anywhere from 0
            # to past the table: stay inside it
            return tab[bb, jnp.clip(page, 0, nblk - 1)], gg, 0, 0
        return pl.BlockSpec((1, heads, bs, d2), index_map)

    def per_group(bb, gg, jj, tab, st):
        return bb, gg, 0, 0

    def whole(bb, gg, jj, tab, st):
        return 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv // heads, pl.cdiv(nblk, pages)),
        in_specs=[
            pl.BlockSpec((rows, 2), whole),
            pl.BlockSpec((2, cols), whole),
            pl.BlockSpec((cols, 1), whole),
            pl.BlockSpec((1, 1, rows, d2), per_group),
            *[page_spec(i) for i in range(pages)],
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d2), per_group),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),        # running max m
            pltpu.VMEM((rows, 1), jnp.float32),        # running denom l
            pltpu.VMEM((rows, d2), jnp.float32),       # p @ (K, V) acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv // heads, rows, d2),
                                       jnp.float32),
        interpret=interpret, name="paged_attention")


def _pallas_core(q, pool, tables, start, scale, window=None):
    """Pallas path: an online softmax over the lane's pages (running
    max m, denominator l, weighted accumulator, rescaled by
    exp(m_old - m_new) a step), the block gather folded into the kernel
    pipeline. interpret=True off the TPU so tier-1 parity tests execute
    the genuine kernel body."""
    import jax
    import jax.numpy as jnp

    b, h, c, d = q.shape
    hkv, bs = pool.shape[1], pool.shape[2]
    nblk = tables.shape[1]
    rep = h // hkv
    heads, pages = _tile(c, rep, hkv)
    rows = heads * rep * c
    start = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32),
                                         (-1,)), (b,))
    # [B, H, C, D] -> [B, Hkv/heads, heads*rep*C, 2D]: kv-head-major,
    # then group member, query-minor rows, zeros where the slab holds V
    qr = jnp.pad(q.astype(jnp.float32).reshape(b, hkv // heads, rows, d),
                 ((0, 0), (0, 0), (0, 0), (0, d)))
    row = np.arange(rows, dtype=np.int32)
    col = np.arange(pages * heads * bs, dtype=np.int32)
    # per row: its query's offset in the chunk, its kv-head; per column:
    # its key's offset from the step's first position, its kv-head
    rowinfo = np.stack([row % c, row // (rep * c)], axis=1)
    keyoff = col // (heads * bs) * bs + col % bs
    colinfo = np.stack([keyoff, col // bs % heads])
    call = _pallas_call(b, h, c, 2 * d, hkv, bs, nblk, heads, pages,
                        float(scale),
                        None if window is None else int(window),
                        str(pool.dtype),
                        jax.default_backend() != "tpu")
    # the scope, innermost at the call, is what names the instruction
    # in a device trace ("%paged_attention.1 = ... custom-call")
    with jax.named_scope("paged_attention"):
        out = call(tables.astype(jnp.int32), start, jnp.asarray(rowinfo),
                   jnp.asarray(colinfo), jnp.asarray(keyoff[:, None]), qr,
                   *[pool] * pages)
    return out[..., d:].reshape(b, h, c, d).astype(pool.dtype)
