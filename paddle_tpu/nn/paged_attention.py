"""Fused paged-attention kernel family: gather + attend over the block
pools in one pass.

The paged serving paths (decode wave, spec draft wave, spec verify,
prefill chunk) historically read the KV cache in two steps:
`gather_block_kv` materialised a `[B, Hkv, nblk*BS, D]` copy of every
lane's blocks, then `cached_decode_attention`/`chunk_attention`
consumed it. That intermediate is a full extra HBM round-trip over the
cache per layer per wave — exactly the memory-intensive op class the
operator-fusion literature (PAPERS.md: "Operator Fusion in XLA",
"FusionStitching") shows XLA's default fusion will not stitch away.

This module replaces the pair with kernels that read K/V *directly out
of the per-layer block pool through the block table* using an online
(streaming) softmax over blocks — the `[B, Hkv, nblk*BS, D]` gathered
view never exists. Three interchangeable implementations sit behind one
dispatch point:

  kernel="reference"  the original gather-then-attend pair, kept as the
                      selectable parity oracle (bitwise the pre-fusion
                      program);
  kernel="lax"        a lax.fori_loop over blocks carrying the
                      flash-attention recurrence (running max m, denom
                      l, weighted accumulator); works on every backend;
  kernel="pallas"     a Pallas TPU kernel — grid over (lanes, groups of
                      kv-heads, steps of pages). A step takes whole
                      pages as the pool stores them (the [heads, BS, 2D]
                      slab of every kv-head of the group) through the
                      BlockSpec index_map over a scalar-prefetch table,
                      and only the pages the lane attends
                      (`attended_pages`, from its position, the chunk
                      and the window): a step outside them names the
                      page it already holds, so it moves no bytes, and
                      skips the arithmetic. Accumulators sit in VMEM
                      scratch across the sequential step dimension.
                      How many kv-heads and pages share a step follows
                      the shapes of the call (`_tile`): all heads and
                      several pages in the decode form, where a pass
                      through the recurrence costs the same however
                      little it scores, so one pass scores them all; a
                      few heads and one page in a 128-token chunk,
                      where the query tile fills VMEM. `interpret=True`
                      on CPU so tier-1 exercises the real kernel body;
                      the body keeps every value 2-D, which is what the
                      TPU compiler accepts (tests/test_tpu_compile.py
                      compiles it for v5e at the cells' real shapes).
  kernel="auto"       "pallas" on TPU, "lax" elsewhere. An explicit
                      "pallas" reaches the compiler as is: nothing here
                      catches a refusal or falls back.

The pool's stored form (every core, every model): one array a layer,
`[NB, Hkv, BS, 2D]`, a position's K row in `[..., :D]` and its V row
beside it in `[..., D:]` (`nn.transformer.write_block_kv` writes it and
says why). A core takes that array as it is stored; the Pallas core
fetches a page's K and V as one slab and never slices it (see
`_paged_attn_kernel`).

Both serving attention shapes are covered: the decode form (one query
per lane; replaces gather+`cached_decode_attention` in the decode and
spec-draft waves) and the chunked form (C queries at per-lane offsets;
replaces gather+`chunk_attention` in `prefill_chunk` and the spec
verify wave). Decode is the C == 1 case of the chunk recurrence, but
keeps its own entry point so the xprof registry can track the two cores
as distinct programs.

Masking contract (the `-1e9` wart fixed): masked/out-of-window scores
are hard-excluded with `-inf` *before* the max/exp, and fully-masked
rows (all-scratch lanes, padded chunk tails) renormalise through a
guarded `where(l == 0, 0, acc / l)` instead of softmaxing over a
uniform `-1e9` row. Scratch-block garbage — which may be non-finite — therefore
cannot reach the engines' isfinite poison sentinel, while a genuine
non-finite value at any *attended* position still propagates to the
logits exactly as before.

Dispatch resolution order for kernel=None: the innermost active
`kernel_scope(...)` (how the serving engines pin the kernel they were
built with at trace time) > the `PT_PAGED_KERNEL` environment variable
> the module default from `set_paged_kernel` > "auto".
"""
import contextlib
import functools
import os

import numpy as np

KERNELS = ("auto", "reference", "lax", "pallas")

_DEFAULT_KERNEL = "auto"
_SCOPE_STACK = []           # innermost kernel_scope override, LIFO


def set_paged_kernel(kernel):
    """Set the process-wide default paged-attention kernel."""
    global _DEFAULT_KERNEL
    _DEFAULT_KERNEL = _check(kernel)


def get_paged_kernel():
    """The unresolved process default (may be "auto")."""
    return _DEFAULT_KERNEL


def _check(kernel):
    if kernel not in KERNELS:
        raise ValueError(f"unknown paged kernel {kernel!r}: "
                         f"expected one of {KERNELS}")
    return kernel


@contextlib.contextmanager
def kernel_scope(kernel):
    """Pin the kernel inside a `with` block. The serving engines trace
    their jitted programs inside this scope, so the engine's configured
    kernel wins over the process default no matter which thread or
    engine traced first (tracing runs the Python body; the compiled
    program keeps whatever the scope resolved)."""
    _SCOPE_STACK.append(_check(kernel))
    try:
        yield
    finally:
        _SCOPE_STACK.pop()


def resolve_kernel(kernel=None):
    """Resolve to a concrete implementation name ("reference" | "lax" |
    "pallas"). Resolution order: explicit argument > innermost
    kernel_scope > PT_PAGED_KERNEL env > set_paged_kernel default; an
    "auto" at any level falls through to backend selection (pallas on
    TPU, lax elsewhere)."""
    choice = None
    if kernel is not None:
        choice = _check(kernel)
    elif _SCOPE_STACK:
        choice = _SCOPE_STACK[-1]
    else:
        env = os.environ.get("PT_PAGED_KERNEL", "").strip().lower()
        if env:
            choice = _check(env)
        else:
            choice = _DEFAULT_KERNEL
    if choice != "auto":
        return choice
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "lax"


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def paged_decode_attention(q, pool, tables, pos, scale, window=None,
                           kernel=None):
    """Fused decode attention over the block pool. q: [B, H, 1, D];
    pool: [NB, Hkv, BS, 2D] (K beside V, the stored form); tables:
    [B, nblk] int32; pos a traced scalar or [B] vector of each lane's
    current position (the query's own absolute position — keys at
    ks <= pos are attended, banded to the last `window` when given).
    Returns [B, H, 1, D] in pool.dtype.

    Equivalent to gather_block_kv + cached_decode_attention without the
    gathered [B, Hkv, nblk*BS, D] intermediate."""
    k = resolve_kernel(kernel)
    if k == "reference":
        from .transformer import cached_decode_attention, gather_block_kv
        # sanitize: the gathered view contains scratch-block positions
        # (masked by construction) whose garbage may be non-finite
        ck, cv = gather_block_kv(pool, tables)
        return cached_decode_attention(q, ck, cv, pos, scale, window=window,
                                       sanitize=True)
    if k == "pallas":
        return _pallas_core(q, pool, tables, pos, scale, window)
    return _lax_core(q, pool, tables, pos, scale, window)


def paged_chunk_attention(q, pool, tables, start, scale, window=None,
                          kernel=None):
    """Fused chunk attention over the block pool: C queries per lane at
    absolute positions start + i (start: traced scalar or [B] vector).
    q: [B, H, C, D]; pool/tables as in paged_decode_attention. Query
    row i masks ks <= start + i (banded to the last `window` keys when
    given). Returns [B, H, C, D] in pool.dtype.

    Equivalent to gather_block_kv + chunk_attention without the
    gathered intermediate; the decode form is the C == 1 case."""
    k = resolve_kernel(kernel)
    if k == "reference":
        from .transformer import chunk_attention, gather_block_kv
        ck, cv = gather_block_kv(pool, tables)
        return chunk_attention(q, ck, cv, start, scale, window=window,
                               sanitize=True)
    if k == "pallas":
        return _pallas_core(q, pool, tables, start, scale, window)
    return _lax_core(q, pool, tables, start, scale, window)


def _query_positions(start, b, c):
    """[B, C] int32 absolute position of every query row from a traced
    scalar or [B] start vector."""
    import jax.numpy as jnp
    qpos = jnp.reshape(jnp.asarray(start), (-1, 1)) + jnp.arange(c)
    return jnp.broadcast_to(qpos, (b, c)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# lax fallback: fori_loop over blocks, flash-attention recurrence
# ---------------------------------------------------------------------------

def _lax_core(q, pool, tables, start, scale, window=None):
    """Online-softmax attention streamed block-by-block out of the pool.

    Carries (m, l, acc) across the nblk sequential steps: per block j
    the lane's j-th pool block is fetched ([B, Hkv, BS, 2D] — the only
    gathered working set that ever exists), scored against the queries,
    masked with -inf at ks > qpos (and outside the window), and folded
    into the running max/denominator/weighted-V with the standard
    rescale alpha = exp(m_old - m_new). Fully-masked rows finish with
    l == 0 and renormalise to exactly 0 via the guarded `where` — never
    an average over scratch garbage."""
    import jax
    import jax.numpy as jnp

    b, h, c, d = q.shape
    hkv, bs = pool.shape[1], pool.shape[2]
    nblk = tables.shape[1]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, c, d)
    qpos = _query_positions(start, b, c)               # [B, C]
    neg_inf = jnp.float32(-jnp.inf)

    def body(j, carry):
        m, l, acc = carry
        blk = tables[:, j]                             # [B]
        page = pool[blk].astype(jnp.float32)           # [B, Hkv, BS, 2D]
        kblk, vblk = page[..., :d], page[..., d:]
        s = jnp.einsum("bkrcd,bksd->bkrcs", qf, kblk) * scale
        ks = j * bs + jnp.arange(bs)                   # absolute keys
        keep = ks[None, None, :] <= qpos[:, :, None]   # [B, C, BS]
        if window is not None:
            keep &= ks[None, None, :] > qpos[:, :, None] - window
        # keys no query of the lane attends contribute with probability
        # exactly 0 — but 0 * nan == nan, so zero those V rows outright
        # (scratch-block poison must not leak; an attended non-finite
        # still propagates, keeping the engines' isfinite sentinel live)
        vblk = jnp.where(jnp.any(keep, axis=1)[:, None, :, None],
                         vblk, 0.0)
        keep = keep[:, None, None, :, :]               # [B,1,1,C,BS]
        s = jnp.where(keep, s, neg_inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # all-masked-so-far rows carry m == -inf; shifting by 0 keeps
        # exp(-inf) == 0 without manufacturing inf - inf NaNs
        shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - shift[..., None])
        alpha = jnp.exp(m - shift)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = alpha[..., None] * acc + \
            jnp.einsum("bkrcs,bksd->bkrcd", p, vblk)
        return m_new, l_new, acc_new

    m0 = jnp.full((b, hkv, rep, c), neg_inf)
    l0 = jnp.zeros((b, hkv, rep, c), jnp.float32)
    acc0 = jnp.zeros((b, hkv, rep, c, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblk, body, (m0, l0, acc0))
    # guard on == 0, not > 0: a nan denominator (genuine attended
    # fault) must divide through and propagate, not silently zero
    out = jnp.where(l[..., None] == 0, 0.0, acc / l[..., None])
    return out.reshape(b, h, c, d).astype(pool.dtype)


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
    """Pallas path: same recurrence as _lax_core, with the block gather
    folded into the kernel pipeline. interpret=True on CPU so tier-1
    parity tests execute the genuine kernel body."""
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
