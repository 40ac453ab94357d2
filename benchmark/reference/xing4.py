"""Xing4.0-29B-A4B (`model_type: xing4_0`), forward pass in plain float32
jax.numpy, written from the equations.

Independent of the code under test: no kernels, no cache, no absorbed
attention, no grouped matmul, no split rotary tables; streams are
`[B, S, n, C]` here (the program keeps them stream-major).

  residual state: n = `hc_mult` streams a token, x [B, S, n, C]; every
     stream starts as the token's embedding row; after the last layer
     h = sum_j x[j], then RMSNorm and the untied head (Hyper-Connections,
     arXiv:2409.19606, section 3).
  every sub-layer F (attention, then the MLP), with its own parameters
  (mHC, arXiv:2512.24880, section 4):
     xt      = RMSNorm(vec(x)) over the n C values, no learned scale
     [p|q|r] = xt Phi                                  Phi [n C, 2n + n^2]
                                        (stored transposed: [out, in])
     H_pre   = sigmoid(a_pre p + b_pre)                [n]
     H_post  = 2 sigmoid(a_post q + b_post)            [n]
     M       = exp(clip(a_res mat(r) + b_res, clamp_min, clamp_max))
     `hc_sinkhorn_iters` times: M = M / (rowsum(M) + hc_eps);
                                M = M / (colsum(M) + hc_eps)
     H_res   = M                                       [n, n]
     u       = sum_j H_pre[j] x[j]
     y       = F(RMSNorm_F(u))
     x'[i]   = sum_j H_res[i, j] x[j] + H_post[i] y
  attention (MLA), all positions at once, expanded:
     q = RMSNorm(u W_qa) W_qb, a head's values [q_nope | q_rope];
     a = u W_kv_a; c = RMSNorm(a[:rank]); k_rope = rope(a[rank:]), one
     rotary key for every head; q_rope = rope(q_rope);
     a head's [k_nope | v] = c W_kv_b;
     scores = (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-0.5
     m(mscale_all_dim)^2, causal softmax, o = P v, y = o W_o. Computed in
     blocks of `QUERY_BLOCK` query rows, so that the scores of a
     20k-token sequence are never whole.
  rope: YaRN as the DeepSeek-V3 modelling code has it: f_i = base^(-2i/d);
     corr(b) = d ln(L0 / (2 pi b)) / (2 ln base); low = floor(corr(
     beta_fast)), high = ceil(corr(beta_slow)); ramp_i = clip((i - low) /
     (high - low), 0, 1); inv_freq_i = (f_i / s) ramp_i + f_i (1 -
     ramp_i); m(a) = 0.1 a ln s + 1; cos and sin times m(mscale) /
     m(mscale_all_dim). The angles are made in float64 on the host.
  layer < first_k_dense_replace: SwiGLU, down(silu(gate x) * up x).
  later layers: router logits in float32, s = sigmoid(logits); the top k
     of s + e_score_correction_bias; weights the chosen s over their sum
     times routed_scaling_factor; expert = down(silu(gate x) * up x), a
     loop over all experts with a mask, one expert cast to float32 at a
     time; one shared SwiGLU of n_shared_experts times the expert width.

Matmuls run at "highest" precision. A layer runs as two jitted halves
(attention, MLP), each handed the streams to overwrite, so one
sub-layer's float32 copies exist at a time beside the 1.2 GB of streams.

Departures from the published description (also in the configuration
file under `assumed`): the stream norm has no learned scale and uses
`rms_norm_eps`; the maps are float32; row sums before column sums in an
iteration, `hc_eps` in both; the clamp is applied before `exp`; scalars
`a_*` and offsets `b_*` belong to each sub-layer; rotary pairs are
interleaved, (x[2i], x[2i+1]), as stored; `n_group` = `topk_group` = 1;
the multi-token-prediction module is not part of the next-token forward
and is left out.

`forward(.., lower=<dtype>)` is the control a referee's limit has to call
wrong: both operands of every matmul rounded to `<dtype>` first (an 8-bit
type scaled tensor by tensor to its largest magnitude); the arithmetic
stays float32. The router and the maps are float32 in every forward:
their input is what a lower precision rounds. `state=` is taken for the
kinds that pass it and changes nothing: no recurrent state to round.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ._control import mm as _mm, rounded as _rounded

QUERY_BLOCK = 128


def from_state_dict(state, n_layer):
    def blk(i):
        p = f"layers.{i}."
        return {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
    return {"embed": state["embeddings"],
            "layers": [blk(i) for i in range(n_layer)],
            "norm": state["norm_weight"], "head": state["lm_head"]}


def _f32(x):
    return x.astype(jnp.float32)


def _unit(x, eps):
    """x over the root of its mean square along the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rms_norm(x, w, eps):
    return _unit(x, eps) * _f32(w)


# ---------------------------------------------------------------- positions
def yarn(cfg):
    """(inv_freq [d/2] float64, factor on cos and sin, softmax scale) from
    the configuration's keys; plain rotary tables without `rope_scaling`."""
    d, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    scale = (int(cfg["qk_nope_head_dim"]) + d) ** -0.5
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return f, 1.0, scale
    s, l0 = float(sc["factor"]), float(sc["original_max_position_embeddings"])

    def corr(b):
        return d * math.log(l0 / (2 * math.pi * b)) / (2 * math.log(base))

    low = max(math.floor(corr(float(sc.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(sc.get("beta_slow", 1)))), d - 1)
    width = high - low if high != low else 0.001
    ramp = np.clip((np.arange(d // 2) - low) / width, 0.0, 1.0)

    def m(a):
        return 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0

    m_all = m(float(sc.get("mscale_all_dim", 0)))
    return ((f / s) * ramp + f * (1 - ramp),
            m(float(sc.get("mscale", 1))) / m_all, scale * m_all * m_all)


def _rope(x, cos, sin):
    """x [B, S, H, D], interleaved pairs, cos and sin [S, D/2]."""
    c, sn = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c],
                     axis=-1).reshape(x.shape)


# ---------------------------------------------------------- hyper-connection
def _maps(x, hw, hc, eps, lower):
    """x [B, S, n, C] -> H_pre [B, S, n], H_post [B, S, n], H_res
    [B, S, n, n]."""
    n, iters, hc_eps, lo, hi = hc
    b, s = x.shape[:2]
    xt = _unit(x.reshape(b, s, -1), eps)
    pqr = _rounded(xt, lower) @ _f32(hw["phi"]).T    # stored [out, in]
    h_pre = jax.nn.sigmoid(_f32(hw["pre_scale"]) * pqr[..., :n]
                           + _f32(hw["pre_bias"]))
    h_post = 2.0 * jax.nn.sigmoid(_f32(hw["post_scale"]) * pqr[..., n:2 * n]
                                  + _f32(hw["post_bias"]))
    r = (_f32(hw["res_scale"]) * pqr[..., 2 * n:].reshape(b, s, n, n)
         + _f32(hw["res_offset"]))

    def knopp(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)

    return h_pre, h_post, jax.lax.fori_loop(
        0, iters, knopp, jnp.exp(jnp.clip(r, lo, hi)))


def _wrapped(x, hw, hc, eps, lower, f):
    """The streams after sub-layer `f`; with one stream (`hc` None) the
    plain residual `x + f(x)`, x [B, S, C]."""
    if hc is None:
        return x + f(x)
    h_pre, h_post, h_res = _maps(x, hw, hc, eps, lower)
    y = f(jnp.einsum("bsj,bsjc->bsc", h_pre, x))
    return (jnp.einsum("bsij,bsjc->bsic", h_res, x)
            + h_post[..., None] * y[:, :, None, :])


def _sub(lw, prefix):
    return {k[len(prefix):]: v for k, v in lw.items()
            if k.startswith(prefix)}


# ------------------------------------------------------------------ attention
def _causal_attention(q, k, v, scale, lower):
    """q, k [B, S, H, D], v [B, S, H, Dv] -> [B, S, H, Dv], in blocks of
    query rows (a `lax.map` over them)."""
    b, s, h, d = q.shape
    q, k, v = (_rounded(t, lower) for t in (q, k, v))
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, blk, h, d)
    keys = jnp.arange(s)

    def one(args):
        qi, i = args                                    # [B, blk, H, D]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * scale
        rows = i * blk + jnp.arange(blk)
        sc = jnp.where(keys[None, :] <= rows[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _rounded(jax.nn.softmax(sc, axis=-1), lower), v)

    out = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(qb.shape[1])))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, h, v.shape[-1])[:, :s]


def _attention(a, lw, cos, sin, dims):
    heads, rank, nope, rope, vd, scale, eps, lower = dims
    b, s, _ = a.shape
    if "self_attn.q_a_proj" in lw:
        q = _mm(_rms_norm(_mm(a, lw["self_attn.q_a_proj"], lower),
                          lw["self_attn.q_a_norm_weight"], eps),
                lw["self_attn.q_b_proj"], lower)
    else:
        q = _mm(a, lw["self_attn.q_proj"], lower)
    q = q.reshape(b, s, heads, nope + rope)
    lat = _mm(a, lw["self_attn.kv_a_proj"], lower)
    c = _rms_norm(lat[..., :rank], lw["self_attn.kv_a_norm_weight"], eps)
    k_rope = _rope(lat[..., rank:][:, :, None, :], cos, sin)
    kv = _mm(c, lw["self_attn.kv_b_proj"], lower).reshape(b, s, heads,
                                                          nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)
    o = _causal_attention(q, k, kv[..., nope:], scale, lower)
    return _mm(o.reshape(b, s, heads * vd), lw["self_attn.o_proj"], lower)


# ------------------------------------------------------------------------ MLP
def _swiglu(x, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


def _experts(a, lw, top_k, scale, lower=None):
    b, s, hid = a.shape
    x = a.reshape(b * s, hid)
    n_exp = lw["mlp.experts_down"].shape[0]
    score = jax.nn.sigmoid(_rounded(x, lower)
                           @ _f32(lw["mlp.router_weight"]))
    _, idx = jax.lax.top_k(
        score + _f32(lw["mlp.e_score_correction_bias"]), top_k)
    w = jnp.take_along_axis(score, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale

    def one(e, acc):
        # stored [experts, width, hidden]: `up` and `gate` as [out, in],
        # `down` as [in, out]
        up, gate, down = (
            jax.lax.dynamic_index_in_dim(lw["mlp.experts_" + n], e, 0, False)
            for n in ("up", "gate", "down"))
        y = _swiglu(x, _f32(gate).T, _f32(up).T, down, lower)
        return acc + y * jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1,
                                 keepdims=True)

    y = jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(x))
    y = y + _swiglu(x, lw["mlp.shared_gate"], lw["mlp.shared_up"],
                    lw["mlp.shared_down"], lower)
    return y.reshape(b, s, hid)


# ---------------------------------------------------------------------- layer
@functools.partial(jax.jit, static_argnames=("hc", "dims"),
                   donate_argnums=(0,))
def _attention_half(x, lw, cos, sin, hc, dims):
    eps, lower = dims[-2:]
    with jax.default_matmul_precision("highest"):
        return _wrapped(
            x, _sub(lw, "hc_attn."), hc, eps, lower,
            lambda u: _attention(_rms_norm(u, lw["input_norm_weight"], eps),
                                 lw, cos, sin, dims))


@functools.partial(jax.jit, static_argnames=("hc", "dense", "dims"),
                   donate_argnums=(0,))
def _mlp_half(x, lw, hc, dense, dims):
    top_k, scale, eps, lower = dims

    def mlp(u):
        a = _rms_norm(u, lw["post_norm_weight"], eps)
        if dense:
            return _swiglu(a, lw["mlp.gate_proj.weight"],
                           lw["mlp.up_proj.weight"],
                           lw["mlp.down_proj.weight"], lower)
        return _experts(a, lw, top_k, scale, lower)

    with jax.default_matmul_precision("highest"):
        return _wrapped(x, _sub(lw, "hc_mlp."), hc, eps, lower, mlp)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, eps, lower):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms_norm(x, norm, eps), head, lower)


def forward(w, ids, cfg, rows=None, lower=None, state=None):
    """ids [B, S] int -> float32 logits [B, S, V], or [B, len(rows), V]
    for the sequence positions in `rows`. cfg: the configuration file.
    `lower`: the control forward (this module's docstring); `state`:
    taken and unused."""
    del state
    ids = jnp.asarray(ids, jnp.int32)
    n = int(cfg.get("hc_mult", 1))
    eps = float(cfg["rms_norm_eps"])
    hc = None if n == 1 else (
        n, int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]),
        float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"]))
    inv_freq, on_tables, scale = yarn(cfg)
    ang = np.outer(np.arange(ids.shape[1], dtype=np.float64), inv_freq)
    cos = jnp.asarray(np.cos(ang) * on_tables, jnp.float32)
    sin = jnp.asarray(np.sin(ang) * on_tables, jnp.float32)
    attn_dims = (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
                 int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
                 int(cfg["v_head_dim"]), float(scale), eps, lower)
    mlp_dims = (int(cfg["num_experts_per_tok"]),
                float(cfg["routed_scaling_factor"]), eps, lower)
    x = _f32(w["embed"][ids])
    if hc:
        x = jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n,
                                                x.shape[-1]))
    for i, lw in enumerate(w["layers"]):
        x = _attention_half(x, lw, cos, sin, hc, attn_dims)
        x = _mlp_half(x, lw, hc, i < int(cfg["first_k_dense_replace"]),
                      mlp_dims)
    if hc:
        x = jnp.sum(x, axis=2)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["norm"], w["head"], eps, lower)
