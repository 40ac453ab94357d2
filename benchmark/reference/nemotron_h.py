"""Nemotron-H (NVIDIA Nemotron 3 Nano, `model_type: nemotron_h`), forward
pass in plain float32 jax.numpy, written from the equations.

Independent of the code under test: no kernels, no cache, no chunked scan,
no grouped matmul. Every block is `h + mixer(RMSNorm(h))`; the pattern
string of the configuration chooses each block's mixer:

  M  Mamba-2. in_proj -> z, xBC, dt; xBC = silu(conv(xBC)) with a causal
     depthwise conv of `conv_kernel` taps and bias; dt = softplus(dt +
     dt_bias); A = -exp(A_log), one scalar a head; per head, position by
     position (a `lax.scan` over t),
         S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t
     with B and C shared by the heads of a group; then
     y = RMSNorm_group(y * silu(z)) * w (gate first, the norm over each
     group's channels) and out_proj.
  *  causal grouped-query attention as a full softmax; no bias, no window,
     no rotary embedding.
  E  router logits in float32, s = sigmoid(logits); the top k of
     s + e_score_correction_bias are chosen; their weights are the chosen
     s (without the bias) over their sum, times routed_scaling_factor;
     expert = down(relu(up(x))^2), computed as a loop over all experts
     with a mask, one expert cast to float32 at a time; one shared expert
     of the same form is added for every token.

Matmuls run at "highest" precision (a float32 matmul on a TPU is otherwise
bfloat16 passes). Departures from the published modelling code are listed
in the configuration file under `assumed`.

Two things beside the plain forward, both for the referee of a cell
(`kinds/serve_closed_routed.py`). `forward(.., margins=True)` also gives,
for every position, how far apart the last chosen and the first unchosen
expert's scores lie, the least over the expert layers: where that is
about nothing, a forward in another precision chooses another expert and
its logits move by a whole expert's output. `forward(.., lower=<dtype>)`
is the control: the same equations with both operands of every matmul
rounded to `<dtype>` first (an 8-bit type scaled tensor by tensor to its
largest magnitude, as an 8-bit forward scales them) and `state=<dtype>`
with the Mamba state rounded after every step; the arithmetic stays
float32. A referee's limit has to call that forward wrong.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them; an expert's `up`
matrix is stored [width, hidden], its `down` matrix [width, hidden]).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ._control import mm as _mm, rounded as _rounded


def from_state_dict(state, n_layer):
    def blk(i):
        p = f"layers.{i}.mixer."
        lw = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        lw["norm"] = state[f"layers.{i}.norm_weight"]
        return lw
    return {"embed": state["embeddings"],
            "layers": [blk(i) for i in range(n_layer)],
            "norm": state["norm_f_weight"], "head": state["lm_head"]}


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _mamba(a, lw, heads, hd, groups, n, taps, eps, lower, state_dtype):
    b, s, _ = a.shape
    d_inner, gn = heads * hd, groups * n
    zxd = _mm(a, lw["in_proj"], lower)
    z, xbc, dt = jnp.split(zxd, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    padded = jnp.pad(xbc, [(0, 0), (taps - 1, 0), (0, 0)])
    conv = _f32(lw["conv_bias"]) + sum(
        _f32(lw["conv_weight"])[j] * padded[:, j:j + s] for j in range(taps))
    x, bm, cm = jnp.split(jax.nn.silu(conv), [d_inner, d_inner + gn],
                          axis=-1)
    x = x.reshape(b, s, heads, hd)
    rep = heads // groups
    bm = jnp.repeat(bm.reshape(b, s, groups, n), rep, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, groups, n), rep, axis=2)
    dt = jax.nn.softplus(dt + _f32(lw["dt_bias"]))          # [B, S, H]
    decay = jnp.exp(dt * -jnp.exp(_f32(lw["A_log"])))

    def step(state, t):
        x_t, b_t, c_t, dt_t, decay_t = t
        state = _rounded(
            state * decay_t[:, :, None, None]
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :],
            state_dtype)
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((b, heads, hd, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + _f32(lw["D"])[:, None] * x
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    g = y.reshape(b, s, groups, d_inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return _mm(g.reshape(b, s, d_inner) * _f32(lw["norm_weight"]),
               lw["out_proj"], lower)


def _attention(a, lw, heads, kv_heads, d, lower):
    b, s, _ = a.shape
    q, k, v = jnp.split(_mm(a, lw["qkv_proj"], lower),
                        [heads * d, (heads + kv_heads) * d], axis=-1)
    rep = heads // kv_heads
    q = q.reshape(b, s, heads, d)
    k = jnp.repeat(k.reshape(b, s, kv_heads, d), rep, axis=2)
    v = jnp.repeat(v.reshape(b, s, kv_heads, d), rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", _rounded(q, lower),
                    _rounded(k, lower)) / math.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   _rounded(jax.nn.softmax(sc, axis=-1), lower),
                   _rounded(v, lower))
    return _mm(o.reshape(b, s, heads * d), lw["o_proj"], lower)


def _experts(a, lw, top_k, scale, lower=None):
    """-> (output [B, S, hidden], margin [B, S]: the last chosen biased
    score less the first unchosen one)."""
    b, s, hid = a.shape
    x = a.reshape(b * s, hid)
    n_exp = lw["experts_down"].shape[0]
    # the router is float32 in every forward (`assumed`): its input is
    # what a lower precision rounds
    score = jax.nn.sigmoid(_rounded(x, lower) @ _f32(lw["router_weight"]))
    best, idx = jax.lax.top_k(
        score + _f32(lw["e_score_correction_bias"]), top_k + 1)
    margin, idx = best[:, top_k - 1] - best[:, top_k], idx[:, :top_k]
    w = jnp.take_along_axis(score, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale

    def one(e, acc):
        # stored [experts, width, hidden]: `up` as [out, in], `down` as
        # [in, out]
        up = jax.lax.dynamic_index_in_dim(lw["experts_up"], e, 0, False)
        down = jax.lax.dynamic_index_in_dim(lw["experts_down"], e, 0, False)
        y = _mm(jnp.square(jax.nn.relu(_mm(x, _f32(up).T, lower))), down,
                lower)
        return acc + y * jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1,
                                 keepdims=True)

    y = jax.lax.fori_loop(0, n_exp, one, jnp.zeros_like(x))
    y = y + _mm(jnp.square(jax.nn.relu(_mm(x, lw["shared_up"], lower))),
                lw["shared_down"], lower)
    return y.reshape(b, s, hid), margin.reshape(b, s)


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _block(x, lw, kind, dims):
    """-> (x after the block, the experts' margin [B, S] or None)."""
    (heads, kv_heads, d, m_heads, m_hd, groups, n, taps, top_k, scale,
     eps, lower, state_dtype) = dims
    with jax.default_matmul_precision("highest"):
        a = _rms_norm(x, lw["norm"], eps)
        if kind == "M":
            return x + _mamba(a, lw, m_heads, m_hd, groups, n, taps, eps,
                              lower, state_dtype), None
        if kind == "*":
            return x + _attention(a, lw, heads, kv_heads, d, lower), None
        y, margin = _experts(a, lw, top_k, scale, lower)
        return x + y, margin


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, eps, lower):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms_norm(x, norm, eps), head, lower)


def forward(w, ids, cfg, rows=None, margins=False, lower=None, state=None):
    """ids [B, S] int -> float32 logits [B, S, V], or [B, len(rows), V]
    for the sequence positions in `rows`. cfg: the configuration file.
    `margins`: also the experts' least margin at every position [B, S].
    `lower`, `state`: the control forward (this module's docstring)."""
    ids = jnp.asarray(ids, jnp.int32)
    x = _f32(w["embed"][ids])
    eps = float(cfg["layer_norm_epsilon"])
    dims = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["mamba_num_heads"]),
            int(cfg["mamba_head_dim"]), int(cfg["n_groups"]),
            int(cfg["ssm_state_size"]), int(cfg["conv_kernel"]),
            int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]), eps, lower, state)
    least = jnp.full(ids.shape, jnp.inf)
    for kind, lw in zip(cfg["hybrid_override_pattern"], w["layers"]):
        x, margin = _block(x, lw, kind, dims)
        if margin is not None:
            least = jnp.minimum(least, margin)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    logits = _head(x, w["norm"], w["head"], eps, lower)
    return (logits, least) if margins else logits
