"""Flash attention for TPU.

Forward: online-softmax tiled kernel (Pallas) — keeps the S x S score matrix
out of HBM, streaming K/V blocks through VMEM with running (max, denom)
rescaling. Backward: Pallas flash kernels too (_bwd_dkv_kernel /
_bwd_dq_kernel below) — two passes that recompute the block's scores in
VMEM from the saved logsumexp, so dQ/dK/dV never materialise S x S in HBM.

Two layouts share the kernels: the default [B, H, S, D] (one head per
program) and the transpose-free [B, S, H, D] path, which views the array
as [B, S, H*D] (free contiguous collapse) and packs heads into 128-lane
groups — d=64 packs head PAIRS per program — so every block satisfies the
Mosaic rule that a block's last two dims be 8/128-divisible or whole.
D is padded to the 128-lane boundary inside the wrapper when needed.
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import os

# Block sizes: bigger tiles amortise per-program overhead and feed the MXU
# larger operands (128x128 tiles left the kernels ~20x off roofline in the
# device trace); bounded by VMEM (~16MB/core). Env-tunable for sweeps.
_BQ = int(os.environ.get("PADDLE_TPU_FLASH_BQ", 512))   # query block
_BK = int(os.environ.get("PADDLE_TPU_FLASH_BK", 512))   # key block


def _blk(pref, n):
    """Largest 128-multiple divisor of n not exceeding pref."""
    b = (min(pref, n) // 128) * 128   # round env-supplied sizes to the grid
    while b > 128 and n % b:
        b -= 128
    return max(b, 128)


def _causal_block_bounds(off, qblk, bq, bk, nblocks, window):
    """KV-block loop bounds for one q block under causal(+window) masking:
    returns (lower, lo_mid, mid, upper) with [lower, lo_mid) window-edge
    blocks (masked), [lo_mid, mid) interior blocks (every (q, k) pair in
    band — no mask chain needed), and [mid, upper) diagonal-edge blocks
    (masked). The kernels are VPU-bound at small head_dim, so skipping
    the 2-iota+compare+select chain on interior blocks matters. Shared
    by _fwd_kernel and _bwd_dq_kernel; _bwd_dkv_kernel iterates the
    transposed direction with its own bounds."""
    qlo = off + qblk * bq                 # first absolute q row
    diag = off + (qblk + 1) * bq
    upper = jnp.minimum(nblocks, (diag + bk - 1) // bk)
    # interior from the right: all k_idx <= min q_idx
    mid = jnp.minimum(jnp.maximum(0, (qlo + 1) // bk), upper)
    lower = 0
    lo_mid = jnp.int32(0)
    if window is not None:
        lower = jnp.maximum(0, (qlo - window + 1) // bk)
        # interior from the left: all k_idx > max q_idx - window
        lo_mid = jnp.minimum(
            jnp.maximum(lower, -(-(diag - window) // bk)), mid)
    return lower, lo_mid, mid, upper


def _sds(shape, dtype, *arrs):
    """ShapeDtypeStruct matching the varying-manual-axes (vma) of the
    inputs: under a vma-checked shard_map (partial-manual hybrid meshes),
    pallas_call outputs must declare how they vary across mesh axes."""
    vma = frozenset()
    for a in arrs:
        vma |= getattr(jax.typeof(a), "vma", frozenset()) or frozenset()
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_dim(d):
    """Kernel head-dim: 64 stays (block == array dim is Mosaic-legal and
    avoids doubling HBM traffic); otherwise round up to the 128 lane
    boundary."""
    return d if d == 64 else max(128, ((d + 127) // 128) * 128)



def _pack(d_pad, h):
    """BSHD head-group packing rule (single source of truth for fwd, bwd
    and eligibility): heads per program, group count, lane width. d=64
    packs head PAIRS into the 128-lane tile; d_pad >= 128 maps 1:1."""
    gsz = 2 if d_pad == 64 else 1
    return gsz, h // gsz, gsz * d_pad


def _band_keep(q_idx, k_idx, window):
    """Causal(+sliding-window) mask — ONE definition for the reference
    path, both kernels' fwd/bwd tiles, and the XLA fallback."""
    keep = k_idx <= q_idx
    if window is not None:
        keep = keep & (k_idx > q_idx - window)
    return keep


def _sdpa_reference(q, k, v, mask, causal, scale, window=None):
    """Fused XLA path — also the recompute body for the backward pass.
    Softmax statistics in f32 regardless of input dtype. window=W keeps
    only the last W keys per query (sliding-window/local attention)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        qi = jnp.arange(qlen)[:, None] + (klen - qlen)
        ki = jnp.arange(klen)[None, :]
        logits = jnp.where(_band_keep(qi, ki, window), logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _q2(ref, g, d):
    """Whole-block 2-D view of head g: refs are [1, BQ, G*D] — the BSHD
    path packs G heads into the lane dim (G*D is a 128 multiple, which is
    what makes the block Mosaic-legal); the BHSD path is the G=1, full-
    lane case of the same layout."""
    return ref[0, :, g * d:(g + 1) * d]


def _kslice(ref, start, size, g, d):
    from jax.experimental import pallas as pl
    return ref[0, pl.ds(start, size), g * d:(g + 1) * d]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                kv_len, q_len, bk, dp, gsz=1, window=None):
    """One (batch*head-group, q-block) program: stream K/V blocks, online
    softmax. Also writes the per-row log-sum-exp (softmax stats) so the
    flash backward kernel can recompute P tiles without re-reducing.
    gsz heads live side-by-side in the lane dim (static unroll)."""
    from jax.experimental import pallas as pl

    bq = q_ref.shape[1]
    nblocks = kv_len // bk
    qblk = pl.program_id(1)
    outs = []
    for g in range(gsz):
        # dots take the INPUT dtype (bf16 on the bench path) with f32
        # accumulation via preferred_element_type — an f32 upcast before
        # the dot runs the MXU at its much slower f32 rate (measured:
        # fwd kernel 0.59 -> ~0.2 ms/layer on gpt2s b=8). Softmax stats
        # (m/l/lse) and the accumulator stay f32; scale applies post-dot.
        q = _q2(q_ref, g, dp)                              # [BQ, D]

        m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((bq, 1), jnp.float32)
        acc0 = jnp.zeros((bq, dp), jnp.float32)

        def make_body(masked):
            def body(j, carry):
                m, l, acc = carry
                kblk = _kslice(k_ref, j * bk, bk, g, dp)
                vblk = _kslice(v_ref, j * bk, bk, g, dp)
                s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32
                                        ) * scale
                if masked:
                    # absolute query position includes the (klen - qlen)
                    # decode offset so semantics match _sdpa_reference
                    # for sq != sk
                    q_idx = ((kv_len - q_len) + qblk * bq
                             + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bq, bk), 0))
                    k_idx = j * bk + jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 1)
                    s = jnp.where(_band_keep(q_idx, k_idx, window), s,
                                  -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                # guard fully-masked rows (m_new = -inf): shift by 0 there
                shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - shift)
                alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - shift,
                                          -jnp.inf))
                l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc_new = acc * alpha + jax.lax.dot_general(
                    p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l_new, acc_new
            return body

        if causal:
            # edge/interior split (_causal_block_bounds): only blocks up
            # to the diagonal are visited; the mask chain runs on EDGE
            # blocks only
            lower, lo_mid, mid, upper = _causal_block_bounds(
                kv_len - q_len, qblk, bq, bk, nblocks, window)
            carry = (m0, l0, acc0)
            if window is not None:
                carry = jax.lax.fori_loop(lower, lo_mid, make_body(True),
                                          carry)
                carry = jax.lax.fori_loop(lo_mid, mid, make_body(False),
                                          carry)
            else:
                carry = jax.lax.fori_loop(lower, mid, make_body(False),
                                          carry)
            m, l, acc = jax.lax.fori_loop(mid, upper, make_body(True),
                                          carry)
        else:
            m, l, acc = jax.lax.fori_loop(0, nblocks, make_body(False),
                                          (m0, l0, acc0))
        outs.append((acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype))
        # lse = m + log l (finite-m guard matches the shift guard above).
        # lse_ref holds FULL [1, gsz, q_len] rows (TPU block constraint:
        # last two dims must be 8/128-divisible or whole); each q-block
        # program writes its slice — grid iterations are sequential so
        # this is race-free.
        lse = (jnp.where(jnp.isfinite(m), m, 0.0)
               + jnp.log(jnp.maximum(l, 1e-30)))
        lse_ref[0, g, pl.ds(qblk * bq, bq)] = lse[:, 0]
    o_ref[0] = outs[0] if gsz == 1 else jnp.concatenate(outs, axis=-1)


def _flash_fwd_pallas(q, k, v, causal, scale, bshd=False,
                      window=None):
    from jax.experimental import pallas as pl

    if bshd:
        # native [B, S, H, D] layout: no q/k/v transposes feed the kernel —
        # the array is viewed as [B, S, H*D] (a FREE reshape: contiguous
        # collapse) and heads are packed into 128-lane groups so the block
        # shape stays Mosaic-legal (a size-1 head-axis block is not: the
        # last two block dims must be 8/128-divisible or whole). Kills the
        # ~10ms/step of bf16 layout transposes the BHSD path pays at the
        # bench config; PERF.md "qkv/attention transposes".
        b, sq, h, d = q.shape
        sk = k.shape[1]
    else:
        b, h, sq, d = q.shape
        sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # head_dim 64 runs unpadded (block dim == array dim satisfies the
    # Mosaic constraint); padding to 128 would double the HBM traffic of
    # every q/k/v copy feeding the kernel
    d_pad = _pad_dim(d)
    if d != d_pad:
        pad = [(0, 0)] * 3 + [(0, d_pad - d)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    bq_ = _blk(_BQ, sq)
    if bshd:
        gsz, ngrp, lane = _pack(d_pad, h)
        qr = q.reshape(b, sq, h * d_pad)
        kr = k.reshape(b, sk, h * d_pad)
        vr = v.reshape(b, sk, h * d_pad)
        q_spec = pl.BlockSpec((1, bq_, lane),
                              lambda bg, i: (bg // ngrp, i, bg % ngrp))
        kv_spec = pl.BlockSpec((1, sk, lane),
                               lambda bg, i: (bg // ngrp, 0, bg % ngrp))
        o_shape = _sds((b, sq, h * d_pad), q.dtype, qr, kr, vr)
        nprog = b * ngrp
    else:
        gsz, ngrp = 1, h
        qr = q.reshape(b * h, sq, d_pad)
        kr = k.reshape(b * h, sk, d_pad)
        vr = v.reshape(b * h, sk, d_pad)
        q_spec = pl.BlockSpec((1, bq_, d_pad), lambda bh, i: (bh, i, 0))
        kv_spec = pl.BlockSpec((1, sk, d_pad), lambda bh, i: (bh, 0, 0))
        o_shape = _sds((b * h, sq, d_pad), q.dtype, qr, kr, vr)
        nprog = b * h

    interpret = jax.default_backend() == "cpu"
    bk_ = _blk(_BK, sk)
    kernel = functools.partial(_fwd_kernel, scale=s, causal=causal,
                               kv_len=sk, q_len=sq, bk=bk_, dp=d_pad,
                               gsz=gsz, window=window)
    # the scope, innermost at the call, names the instruction in a
    # device trace ("%flash_fwd.1 = ... custom-call"), whatever traced
    # this function (jvp, transpose, a model's own scopes)
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(nprog, sq // bq_),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, gsz, sq), lambda bh, i: (bh, 0, 0)),
            ],
            out_shape=[
                o_shape,
                _sds((nprog, gsz, sq), jnp.float32, qr, kr, vr),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr)
    if bshd:
        out = out.reshape(b, sq, h, d_pad)
    else:
        out = out.reshape(b, h, sq, d_pad)
    return (out[..., :d] if d != d_pad else out), lse


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                    dk_ref, dv_ref, *, scale, causal, kv_len, q_len,
                    bq, bk, dp, gsz=1, window=None):
    """One (batch*head-group, k-block) program: accumulate dK/dV over q
    blocks. P tiles are recomputed from saved lse; dd is rowsum(dO * O)."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    nqb = q_len // bq
    dks, dvs = [], []
    for g in range(gsz):
        # same mixed-precision discipline as _fwd_kernel: dots in the
        # input dtype with f32 accumulation; p/ds downcast for the
        # second-stage dots (standard flash practice), stats stay f32
        kblk = _q2(k_ref, g, dp)                         # [BK, D]
        vblk = _q2(v_ref, g, dp)

        dk0 = jnp.zeros((bk, dp), jnp.float32)
        dv0 = jnp.zeros((bk, dp), jnp.float32)

        def make_body(masked):
            def body(i, carry):
                dk, dv = carry
                q = _kslice(q_ref, i * bq, bq, g, dp)
                do = _kslice(do_ref, i * bq, bq, g, dp)
                lse = lse_ref[0, g, pl.ds(i * bq, bq)].reshape(bq, 1)
                dd = dd_ref[0, g, pl.ds(i * bq, bq)].reshape(bq, 1)
                s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32
                                        ) * scale
                p = jnp.exp(s - lse)                    # [BQ, BK]
                if masked:
                    q_idx = ((kv_len - q_len) + i * bq
                             + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bq, bk), 0))
                    k_idx = kb * bk + jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 1)
                    p = jnp.where(_band_keep(q_idx, k_idx, window), p, 0.0)
                dv = dv + jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp_ = jax.lax.dot_general(do, vblk,
                                          (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
                ds = p * (dp_ - dd) * scale             # [BQ, BK]
                dk = dk + jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return dk, dv
            return body

        if causal:
            # edge/interior split (see _fwd_kernel): a q block is
            # INTERIOR to this k block when every (q, k) pair is in the
            # causal band — q >= k for all pairs, and within the window
            # when one is set — so only edge q blocks pay the mask chain
            off = kv_len - q_len
            # first q block whose last row reaches this k block's first row
            start = jnp.maximum(0, (kb * bk - off) // bq)
            end = nqb
            # interior from below: all q_idx >= max k_idx of this k block
            mid = jnp.minimum(jnp.maximum(
                start, -(-(kb * bk + bk - 1 - off) // bq)), end)
            if window is not None:
                # past q_idx >= k_idx + window no query sees this k block
                last = kb * bk + bk - 1 + window - 1 - off
                end = jnp.minimum(nqb, last // bq + 1)
                # interior from above: all q_idx < min k_idx + window
                hi_mid = jnp.minimum(end, (kb * bk + window - off) // bq)
                mid = jnp.minimum(mid, hi_mid)
            else:
                hi_mid = end
            carry = jax.lax.fori_loop(start, mid, make_body(True),
                                      (dk0, dv0))
            carry = jax.lax.fori_loop(mid, hi_mid, make_body(False), carry)
            dk, dv = jax.lax.fori_loop(hi_mid, end, make_body(True), carry)
        else:
            dk, dv = jax.lax.fori_loop(0, nqb, make_body(False),
                                       (dk0, dv0))
        dks.append(dk.astype(dk_ref.dtype))
        dvs.append(dv.astype(dv_ref.dtype))
    dk_ref[0] = dks[0] if gsz == 1 else jnp.concatenate(dks, axis=-1)
    dv_ref[0] = dvs[0] if gsz == 1 else jnp.concatenate(dvs, axis=-1)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, *,
                   scale, causal, kv_len, q_len, bq, bk, dp, gsz=1,
                   window=None):
    """One (batch*head-group, q-block) program: accumulate dQ over k
    blocks."""
    from jax.experimental import pallas as pl

    qblk = pl.program_id(1)
    nkb = kv_len // bk
    dqs = []
    for g in range(gsz):
        q = _q2(q_ref, g, dp)                            # [BQ, D]
        do = _q2(do_ref, g, dp)
        lse = lse_ref[0, g, pl.ds(qblk * bq, bq)].reshape(bq, 1)
        dd = dd_ref[0, g, pl.ds(qblk * bq, bq)].reshape(bq, 1)
        dq0 = jnp.zeros((bq, dp), jnp.float32)

        def make_body(masked):
            def body(j, dq):
                kblk = _kslice(k_ref, j * bk, bk, g, dp)
                vblk = _kslice(v_ref, j * bk, bk, g, dp)
                s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32
                                        ) * scale
                p = jnp.exp(s - lse)
                if masked:
                    q_idx = ((kv_len - q_len) + qblk * bq
                             + jax.lax.broadcasted_iota(jnp.int32,
                                                        (bq, bk), 0))
                    k_idx = j * bk + jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 1)
                    p = jnp.where(_band_keep(q_idx, k_idx, window), p, 0.0)
                dp_ = jax.lax.dot_general(do, vblk,
                                          (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
                ds = p * (dp_ - dd) * scale
                return dq + jax.lax.dot_general(
                    ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return body

        if causal:
            # edge/interior split over k blocks (shared bounds helper)
            lower, lo_mid, mid, upper = _causal_block_bounds(
                kv_len - q_len, qblk, bq, bk, nkb, window)
            dq = dq0
            if window is not None:
                dq = jax.lax.fori_loop(lower, lo_mid, make_body(True), dq)
                dq = jax.lax.fori_loop(lo_mid, mid, make_body(False), dq)
            else:
                dq = jax.lax.fori_loop(lower, mid, make_body(False), dq)
            dq = jax.lax.fori_loop(mid, upper, make_body(True), dq)
        else:
            dq = jax.lax.fori_loop(0, nkb, make_body(False), dq0)
        dqs.append(dq.astype(dq_ref.dtype))
    dq_ref[0] = dqs[0] if gsz == 1 else jnp.concatenate(dqs, axis=-1)


def _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale,
                      bshd=False, window=None):
    """Flash backward: dQ/dK/dV without materialising S x S in HBM."""
    from jax.experimental import pallas as pl

    if bshd:
        b, sq, h, d = q.shape
        sk = k.shape[1]
    else:
        b, h, sq, d = q.shape
        sk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    d_pad = _pad_dim(d)
    if d != d_pad:
        pad = [(0, 0)] * 3 + [(0, d_pad - d)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        out, g = jnp.pad(out, pad), jnp.pad(g, pad)
    if bshd:
        gsz, ngrp, lane = _pack(d_pad, h)
        qr = q.reshape(b, sq, h * d_pad)
        kr = k.reshape(b, sk, h * d_pad)
        vr = v.reshape(b, sk, h * d_pad)
        dor = g.reshape(b, sq, h * d_pad)
        # dd = rowsum(dO * O) in [B*G, gsz, S] layout (tiny f32 transpose)
        dd = jnp.swapaxes(
            jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1), 1, 2).reshape(b * ngrp, gsz, sq)

        def qspec(blk):
            return pl.BlockSpec((1, blk, lane),
                                lambda bg, i: (bg // ngrp, i, bg % ngrp))

        def fullspec(n):
            return pl.BlockSpec((1, n, lane),
                                lambda bg, i: (bg // ngrp, 0, bg % ngrp))

        dkv_shape = [_sds((b, sk, h * d_pad), k.dtype, qr, kr, vr, dor),
                     _sds((b, sk, h * d_pad), v.dtype, qr, kr, vr, dor)]
        dq_shape = _sds((b, sq, h * d_pad), q.dtype, qr, kr, vr, dor)
        nprog = b * ngrp
    else:
        gsz, ngrp = 1, h
        qr = q.reshape(b * h, sq, d_pad)
        kr = k.reshape(b * h, sk, d_pad)
        vr = v.reshape(b * h, sk, d_pad)
        dor = g.reshape(b * h, sq, d_pad)
        # dd = rowsum(dO * O): cheap elementwise reduce, XLA fuses it
        dd = jnp.sum(dor.astype(jnp.float32)
                     * out.reshape(b * h, sq, d_pad).astype(jnp.float32),
                     axis=-1).reshape(b * h, 1, sq)

        def qspec(blk):
            return pl.BlockSpec((1, blk, d_pad), lambda bh, i: (bh, i, 0))

        def fullspec(n):
            return pl.BlockSpec((1, n, d_pad), lambda bh, i: (bh, 0, 0))

        dkv_shape = [_sds((b * h, sk, d_pad), k.dtype, qr, kr, vr, dor),
                     _sds((b * h, sk, d_pad), v.dtype, qr, kr, vr, dor)]
        dq_shape = _sds((b * h, sq, d_pad), q.dtype, qr, kr, vr, dor)
        nprog = b * h

    lse_spec = pl.BlockSpec((1, gsz, sq), lambda bh, i: (bh, 0, 0))
    interpret = jax.default_backend() == "cpu"
    bq_, bk_ = _blk(_BQ, sq), _blk(_BK, sk)
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=s, causal=causal,
                              kv_len=sk, q_len=sq, bq=bq_, bk=bk_,
                              dp=d_pad, gsz=gsz, window=window),
            grid=(nprog, sk // bk_),
            in_specs=[fullspec(sq), qspec(bk_), qspec(bk_), fullspec(sq),
                      lse_spec, lse_spec],
            out_specs=[qspec(bk_), qspec(bk_)],
            out_shape=dkv_shape,
            interpret=interpret,
            name="flash_bwd_dkv",
        )(qr, kr, vr, dor, lse, dd)

    with jax.named_scope("flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=s, causal=causal,
                              kv_len=sk, q_len=sq, bq=bq_, bk=bk_,
                              dp=d_pad, gsz=gsz, window=window),
            grid=(nprog, sq // bq_),
            in_specs=[qspec(bq_), fullspec(sk), fullspec(sk), qspec(bq_),
                      lse_spec, lse_spec],
            out_specs=qspec(bq_),
            out_shape=dq_shape,
            interpret=interpret,
            name="flash_bwd_dq",
        )(qr, kr, vr, dor, lse, dd)

    if bshd:
        dq = dq.reshape(b, sq, h, d_pad)
        dk = dk.reshape(b, sk, h, d_pad)
        dv = dv.reshape(b, sk, h, d_pad)
    else:
        dq = dq.reshape(b, h, sq, d_pad)
        dk = dk.reshape(b, h, sk, d_pad)
        dv = dv.reshape(b, h, sk, d_pad)
    if d != d_pad:
        dq, dk, dv = dq[..., :d], dk[..., :d], dv[..., :d]
    return dq, dk, dv


def _kernel_eligible(q, k, mask, dropout_p, bshd=False):
    if mask is not None or dropout_p:
        return False
    if jax.default_backend() == "cpu":
        # interpret-mode pallas cannot evaluate kernels whose inputs carry
        # varying-manual-axes types (vma-checked hybrid shard_map): the
        # HLO interpreter's block dynamic_slices mix invariant indices with
        # varying operands. Real Mosaic lowering is unaffected; on CPU use
        # the XLA softmax path for those call sites.
        vma = frozenset()
        for a in (q, k):
            vma |= getattr(jax.typeof(a), "vma", frozenset()) or frozenset()
        if vma:
            return False
    seq_ax = 1 if bshd else 2
    if bshd and q.shape[2] % _pack(_pad_dim(q.shape[-1]), q.shape[2])[0]:
        # head-pair lane packing needs an even head count; odd-H models
        # take the transpose fallback (rare)
        return False
    sq, sk = q.shape[seq_ax], k.shape[seq_ax]
    return (sq % 128 == 0 and sk % 128 == 0
            and sq >= 128 and sk >= 128)


def _per_shard_spec(q, bshd):
    """(mesh, PartitionSpec) when the kernel has to be called per shard,
    else None. Traced for a program over the installed multi-device mesh
    (`make_mesh`, as ShardedTrainStep runs under), a Mosaic kernel is
    refused at lowering — "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — so it is wrapped
    here: batch over 'dp' and heads over 'mp' where they divide (nothing
    in the kernel mixes batches or heads), whole otherwise. Interpret
    mode (CPU) lowers to plain HLO that the partitioner handles itself,
    and inside someone else's shard_map (ring, pipeline) the call is
    already per shard."""
    if jax.default_backend() == "cpu":
        return None
    from ...distributed import mesh as mesh_mod
    mesh = mesh_mod.get_mesh()
    if mesh is None or mesh.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None

    def divides(name, n):
        return name in mesh.axis_names and n % int(mesh.shape[name]) == 0

    h = q.shape[2 if bshd else 1]
    b_ax = mesh_mod.DP_AXIS if divides(mesh_mod.DP_AXIS, q.shape[0]) else None
    h_ax = mesh_mod.MP_AXIS if divides(mesh_mod.MP_AXIS, h) else None
    if h_ax and bshd:
        # BSHD packs heads into 128-lane groups: a shard keeps whole groups
        local = h // int(mesh.shape[h_ax])
        if local % _pack(_pad_dim(q.shape[-1]), local)[0]:
            h_ax = None
    return mesh, (P(b_ax, None, h_ax, None) if bshd
                  else P(b_ax, h_ax, None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, causal, scale, bshd=False, window=None):
    out, _ = _flash_fwd_pallas(q, k, v, causal, scale, bshd, window)
    return out


def _flash_core_fwd(q, k, v, causal, scale, bshd=False, window=None):
    out, lse = _flash_fwd_pallas(q, k, v, causal, scale, bshd, window)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, bshd, window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal, scale, bshd,
                             window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_array(q, k, v, mask=None, causal=False, dropout_p=0.0, scale=None,
                 rng_key=None, layout="bhsd", window=None):
    """Array-level flash attention (pure; usable inside any jax transform).
    layout="bshd" takes/returns [B, S, H, D] natively — no transposes feed
    the kernel (the model keeps the matmul-natural layout end to end).
    window=W (requires causal) keeps only the last W keys per query —
    sliding-window/local attention; the kernels skip KV blocks entirely
    outside the band, so compute is O(S*W) instead of O(S^2/2)."""
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding-window "
                             "attention is a causal mask refinement)")
        window = int(window)
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
    bshd = layout == "bshd"
    if _kernel_eligible(q, k, mask, dropout_p, bshd):
        sharded = _per_shard_spec(q, bshd)
        if sharded is None:
            return _flash_core(q, k, v, causal, scale, bshd, window)
        mesh, spec = sharded
        return jax.shard_map(
            lambda q_, k_, v_: _flash_core(q_, k_, v_, causal, scale, bshd,
                                           window),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
    if bshd:
        # fallback reference path works in BHSD: transpose around it
        # (ineligible shapes are the rare/small case)
        o = _flash_array(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                         jnp.swapaxes(v, 1, 2), mask=mask, causal=causal,
                         dropout_p=dropout_p, scale=scale, rng_key=rng_key,
                         window=window)
        return jnp.swapaxes(o, 1, 2)
    out = None
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        qi = jnp.arange(qlen)[:, None] + (klen - qlen)
        ki = jnp.arange(klen)[None, :]
        logits = jnp.where(_band_keep(qi, ki, window), logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_p and rng_key is not None:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _flash_attention_raw(q, k, v, *maybe_mask, causal=False, scale=None,
                         layout="bhsd", window=None):
    """Registered (desc-serializable) dropout-free form — captured
    transformer programs stay portable across processes."""
    m = maybe_mask[0] if maybe_mask else None
    return _flash_array(q, k, v, mask=m, causal=causal, dropout_p=0.0,
                        scale=scale, layout=layout, window=window)


from ..dispatch import register_op as _register_op

_register_op("flash_attention", _flash_attention_raw)


def flash_attention(q, k, v, attn_mask=None, causal=False, dropout_p=0.0,
                    scale=None, layout="bhsd", window=None):
    """Tensor-level op (dispatcher-integrated: eager tape or functional).
    layout="bshd" takes [B, S, H, D] straight from the qkv projection —
    no layout transposes between the matmul and the kernel. window=W is
    causal sliding-window attention (last W keys per query)."""
    from ..dispatch import apply
    from ...framework import state

    args = (q, k, v) if attn_mask is None else (q, k, v, attn_mask)
    if not dropout_p:
        return apply(_flash_attention_raw, args,
                     {"causal": bool(causal),
                      "scale": None if scale is None else float(scale),
                      "layout": str(layout),
                      "window": None if window is None else int(window)},
                     name="flash_attention")

    # attention dropout draws a key: stays an in-process closure op (a
    # desc-portable rng form would thread the key input like dropout)
    rng_key = state.next_rng_key()

    def f(q_, k_, v_, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        return _flash_array(q_, k_, v_, mask=m, causal=causal,
                            dropout_p=dropout_p, scale=scale,
                            rng_key=rng_key, layout=layout, window=window)

    return apply(f, args, name="flash_attention")


def flash_attention_xla(q, k, v, attn_mask=None, causal=False, scale=None,
                        window=None):
    """Force the XLA path (debug/fallback) — same band semantics as the
    kernel path so windowed models compare apples to apples."""
    from ..dispatch import apply

    def f(q_, k_, v_, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        return _sdpa_reference(q_, k_, v_, m, causal, scale, window)

    args = (q, k, v) if attn_mask is None else (q, k, v, attn_mask)
    return apply(f, args, name="flash_attention")
