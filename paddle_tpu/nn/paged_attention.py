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
  kernel="pallas"     a Pallas TPU kernel — grid over (lanes, kv-heads,
                      blocks), the block-table gather done by the
                      BlockSpec index_map over a scalar-prefetch table,
                      accumulators in VMEM scratch across the
                      sequential block dimension. `interpret=True` on
                      CPU so tier-1 exercises the real kernel body; the
                      body keeps every value 2-D, which is what the TPU
                      compiler accepts (tests/test_tpu_compile.py
                      compiles it for v5e at real widths).
  kernel="auto"       "pallas" on TPU, "lax" elsewhere. An explicit
                      "pallas" reaches the compiler as is: nothing here
                      catches a refusal or falls back.

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

def paged_decode_attention(q, pk, pv, tables, pos, scale, window=None,
                           kernel=None):
    """Fused decode attention over the block pool. q: [B, H, 1, D];
    pk/pv: [NB, Hkv, BS, D] pools; tables: [B, nblk] int32; pos a traced
    scalar or [B] vector of each lane's current position (the query's
    own absolute position — keys at ks <= pos are attended, banded to
    the last `window` when given). Returns [B, H, 1, D] in pv.dtype.

    Equivalent to gather_block_kv + cached_decode_attention without the
    gathered [B, Hkv, nblk*BS, D] intermediate."""
    k = resolve_kernel(kernel)
    if k == "reference":
        from .transformer import cached_decode_attention, gather_block_kv
        # sanitize: the gathered view contains scratch-block positions
        # (masked by construction) whose garbage may be non-finite
        return cached_decode_attention(q, gather_block_kv(pk, tables),
                                       gather_block_kv(pv, tables),
                                       pos, scale, window=window,
                                       sanitize=True)
    if k == "pallas":
        return _pallas_core(q, pk, pv, tables, pos, scale, window)
    return _lax_core(q, pk, pv, tables, pos, scale, window)


def paged_chunk_attention(q, pk, pv, tables, start, scale, window=None,
                          kernel=None):
    """Fused chunk attention over the block pool: C queries per lane at
    absolute positions start + i (start: traced scalar or [B] vector).
    q: [B, H, C, D]; pools/tables as in paged_decode_attention. Query
    row i masks ks <= start + i (banded to the last `window` keys when
    given). Returns [B, H, C, D] in pv.dtype.

    Equivalent to gather_block_kv + chunk_attention without the
    gathered intermediate; the decode form is the C == 1 case."""
    k = resolve_kernel(kernel)
    if k == "reference":
        from .transformer import chunk_attention, gather_block_kv
        return chunk_attention(q, gather_block_kv(pk, tables),
                               gather_block_kv(pv, tables),
                               start, scale, window=window,
                               sanitize=True)
    if k == "pallas":
        return _pallas_core(q, pk, pv, tables, start, scale, window)
    return _lax_core(q, pk, pv, tables, start, scale, window)


def _query_positions(start, b, c):
    """[B, C] int32 absolute position of every query row from a traced
    scalar or [B] start vector."""
    import jax.numpy as jnp
    qpos = jnp.reshape(jnp.asarray(start), (-1, 1)) + jnp.arange(c)
    return jnp.broadcast_to(qpos, (b, c)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# lax fallback: fori_loop over blocks, flash-attention recurrence
# ---------------------------------------------------------------------------

def _lax_core(q, pk, pv, tables, start, scale, window=None):
    """Online-softmax attention streamed block-by-block out of the pool.

    Carries (m, l, acc) across the nblk sequential steps: per block j
    the lane's j-th pool block is fetched ([B, Hkv, BS, D] — the only
    gathered working set that ever exists), scored against the queries,
    masked with -inf at ks > qpos (and outside the window), and folded
    into the running max/denominator/weighted-V with the standard
    rescale alpha = exp(m_old - m_new). Fully-masked rows finish with
    l == 0 and renormalise to exactly 0 via the guarded `where` — never
    an average over scratch garbage."""
    import jax
    import jax.numpy as jnp

    b, h, c, d = q.shape
    hkv, bs = pk.shape[1], pk.shape[2]
    nblk = tables.shape[1]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, c, d)
    qpos = _query_positions(start, b, c)               # [B, C]
    neg_inf = jnp.float32(-jnp.inf)

    def body(j, carry):
        m, l, acc = carry
        blk = tables[:, j]                             # [B]
        kblk = pk[blk].astype(jnp.float32)             # [B, Hkv, BS, D]
        vblk = pv[blk].astype(jnp.float32)
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
    return out.reshape(b, h, c, d).astype(pv.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (lanes, kv-heads, blocks), table gather in the
# BlockSpec index_map over the scalar-prefetch block table
# ---------------------------------------------------------------------------

def _paged_attn_kernel(tables_ref, start_ref, off_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_ref, *, scale, window, bs,
                       c):
    """One (lane b, kv-head h, block j) grid step. The pipeline already
    gathered this lane's j-th pool block via the index_map — the kernel
    only scores, masks and folds into the VMEM accumulators, which
    persist across the sequential block dimension.

    Every value is 2-D (the TPU compiler lays vectors out over sublanes
    x lanes and refuses 1-D iotas, vector loads from SMEM and the
    `[:, 0]` / `[:, None]` casts between the two): the running max and
    denominator stay `[rows, 1]`, key positions come from
    `broadcasted_iota`, and a row's query position is the lane's scalar
    `start` plus its `[rows, 1]` offset within the chunk."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(2)
    nblk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qf = q_ref[0, 0].astype(jnp.float32)               # [rep*C, D]
    kb = k_ref[0, 0].astype(jnp.float32)               # [BS, D]
    vb = v_ref[0, 0].astype(jnp.float32)
    rc = qf.shape[0]
    start = start_ref[b]                               # SMEM scalar
    s = jax.lax.dot_general(                           # q @ k.T
        qf, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [rep*C, BS]
    # row i of the [rep*C, D] query tile is (group r, query c) with c
    # minor — its absolute position is start + i % C (off_ref)
    rowpos = start + off_ref[...]                      # [rep*C, 1]
    ks = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rc, bs), 1)
    keep = ks <= rowpos
    if window is not None:
        keep &= ks > rowpos - window
    s = jnp.where(keep, s, -jnp.inf)
    # fully-unattended keys get probability 0 but 0 * nan == nan: zero
    # the V rows no query row keeps so scratch poison cannot leak. The
    # rows sit at start .. start+C-1, so the keys some row keeps are
    # exactly (start - window, start + C - 1]
    kcol = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    attended = kcol <= start + (c - 1)
    if window is not None:
        attended &= kcol > start - window
    vb = jnp.where(attended, vb, 0.0)
    m_prev = m_ref[...]                                # [rep*C, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift)
    alpha = jnp.exp(m_prev - shift)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + \
        jnp.dot(p, vb, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        # == 0 guard (not > 0): nan denominators must propagate
        l = l_ref[...]
        o_ref[0, 0] = jnp.where(l == 0, 0.0, acc_ref[...] / l)


@functools.lru_cache(maxsize=None)
def _pallas_call(b, h, c, d, hkv, bs, nblk, scale, window, dtype_name,
                 interpret):
    """Build (and cache) the pallas_call for one static shape family.
    The block table and each lane's first query position ride as
    scalar-prefetch operands so the K/V BlockSpec index_maps can address
    the pool by table VALUE — the gather happens in the pipeline, block
    by block, never as a materialised [B, Hkv, nblk*BS, D] array."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep = h // hkv
    rc = rep * c
    kernel = functools.partial(_paged_attn_kernel, scale=scale,
                               window=window, bs=bs, c=c)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((rc, 1), lambda bb, hh, jj, tab, st: (0, 0)),
            pl.BlockSpec((1, 1, rc, d),
                         lambda bb, hh, jj, tab, st: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bb, hh, jj, tab, st: (tab[bb, jj], hh,
                                                      0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda bb, hh, jj, tab, st: (tab[bb, jj], hh,
                                                      0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rc, d),
                               lambda bb, hh, jj, tab, st: (bb, hh, 0,
                                                            0)),
        scratch_shapes=[
            pltpu.VMEM((rc, 1), jnp.float32),          # running max m
            pltpu.VMEM((rc, 1), jnp.float32),          # running denom l
            pltpu.VMEM((rc, d), jnp.float32),          # weighted V acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rc, d), jnp.float32),
        interpret=interpret, name="paged_attention")


def _pallas_core(q, pk, pv, tables, start, scale, window=None):
    """Pallas path: same recurrence as _lax_core, with the block gather
    folded into the kernel pipeline. interpret=True on CPU so tier-1
    parity tests execute the genuine kernel body."""
    import jax
    import jax.numpy as jnp

    b, h, c, d = q.shape
    hkv, bs = pk.shape[1], pk.shape[2]
    nblk = tables.shape[1]
    rep = h // hkv
    start = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32),
                                         (-1,)), (b,))
    # [B, H, C, D] -> [B, Hkv, rep*C, D]: group-major, query-minor rows
    qr = q.astype(jnp.float32).reshape(b, hkv, rep * c, d)
    off = (jnp.arange(rep * c, dtype=jnp.int32) % c)[:, None]
    call = _pallas_call(b, h, c, d, hkv, bs, nblk, float(scale),
                        None if window is None else int(window),
                        str(pk.dtype),
                        jax.default_backend() != "tpu")
    # the scope, innermost at the call, is what names the instruction
    # in a device trace ("%paged_attention.1 = ... custom-call")
    with jax.named_scope("paged_attention"):
        out = call(tables.astype(jnp.int32), start, off, qr, pk, pv)
    return out.reshape(b, h, c, d).astype(pv.dtype)
