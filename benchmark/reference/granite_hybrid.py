"""Granite 4.0-H without routed experts (`model_type: granitemoehybrid`,
`num_local_experts: 0`), forward pass in plain float32 jax.numpy, written
from the equations.

Independent of the code under test: no kernels, no cache, no chunked scan.
For x = E[ids] * embedding_multiplier, every layer is

    r = x;  x = RMSNorm(x; w_in);  x = mixer(x);  x = r + residual_multiplier x
    r = x;  x = RMSNorm(x; w_post)
    g, u = split(x W_in);  x = (silu(g) * u) W_out;  x = r + residual_multiplier x

and logits = RMSNorm(x; w_f) E^T / logits_scaling (the head is the
embedding). `layer_types` chooses each layer's mixer:

  mamba      Mamba-2. in_proj -> z, xBC, dt (no bias); xBC = silu(conv(xBC))
             with a causal depthwise conv of `mamba_d_conv` taps and bias;
             dt = softplus(dt + dt_bias); A = -exp(A_log), one scalar a
             head; per head, position by position (a `lax.scan` over t),
                 S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
             with B and C shared by the heads of a group (one group: by
             all); then RMSNorm(y * silu(z)) over each group's channels
             (one group: all of them), times w, and out_proj.
  attention  causal grouped-query attention as a full softmax over
             attention_multiplier * q k^T (NOT 1/sqrt(head_dim)); no bias,
             no rotary embedding (`position_embedding_type: nope`).
             Computed in blocks of query rows, each against all keys, so
             that the [heads, rows, keys] scores of a 17k-token context
             fit; a block's softmax is whole, nothing is accumulated.

Matmuls run at "highest" precision (a float32 matmul on a TPU is otherwise
bfloat16 passes). One layer's weights are cast to float32 at a time.

Departures from the published modelling code, each also under `assumed`
in the configuration file:
  - dt is not clamped after softplus (`time_step_limit` (0, inf), the
    published default);
  - the MLP is the family's shared MLP (`shared_intermediate_size`); with
    no routed experts nothing is added to it;
  - state, dt, decay and softmax are float32, as is everything else here.

`forward(.., lower=<dtype>)` is the referee's control: the same equations
with both operands of every matmul (and of q k^T and p v) rounded to
`<dtype>` first (`_control.py`); `state=<dtype>` rounds the Mamba state
after every step. The arithmetic stays float32. A referee's limit has to
call the 8-bit forward wrong.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them).
"""
import functools

import jax
import jax.numpy as jnp

from ._control import mm as _mm, rounded as _rounded

#: query rows one attention block scores against every key
ROW_BLOCK = 256


def from_state_dict(state, n_layer):
    def layer(i):
        p = f"layers.{i}."
        lw = {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
        return {"norm_in": lw.pop("input_norm_weight"),
                "norm_post": lw.pop("post_norm_weight"),
                "mlp_in": lw.pop("mlp.input_linear"),
                "mlp_out": lw.pop("mlp.output_linear"),
                **{k[len("mixer."):]: v for k, v in lw.items()}}
    return {"embed": state["embeddings"],
            "layers": [layer(i) for i in range(n_layer)],
            "norm": state["norm_f_weight"]}


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
    rep = heads // groups
    # [.., groups, heads of the group, ..]: a group's B and C broadcast
    # over its heads, never repeated
    x = x.reshape(b, s, groups, rep, hd)
    bm, cm = bm.reshape(b, s, groups, n), cm.reshape(b, s, groups, n)
    dt = jax.nn.softplus(dt + _f32(lw["dt_bias"])).reshape(b, s, groups, rep)
    decay = jnp.exp(dt * -jnp.exp(_f32(lw["A_log"])).reshape(groups, rep))

    def step(state, t):
        x_t, b_t, c_t, dt_t, decay_t = t
        state = _rounded(
            state * decay_t[..., None, None]
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :],
            state_dtype)
        return state, jnp.sum(state * c_t[:, :, None, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((b, groups, rep, hd, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + _f32(lw["D"]).reshape(groups, rep, 1) * x
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    g = y.reshape(b, s, groups, d_inner // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return _mm(g.reshape(b, s, d_inner) * _f32(lw["norm_weight"]),
               lw["out_proj"], lower)


def _attention(a, lw, heads, kv_heads, scale, lower):
    b, s, hidden = a.shape
    d = hidden // heads
    q, k, v = jnp.split(_mm(a, lw["qkv_proj"], lower),
                        [heads * d, (heads + kv_heads) * d], axis=-1)
    rep = heads // kv_heads
    q = _rounded(q.reshape(b, s, kv_heads, rep, d), lower)
    k = _rounded(k.reshape(b, s, kv_heads, d), lower)
    v = _rounded(v.reshape(b, s, kv_heads, d), lower)
    rows = min(ROW_BLOCK, s)
    pad = -s % rows
    q = jnp.pad(q, [(0, 0), (0, pad)] + [(0, 0)] * 3)
    keys = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * scale
        qpos = i * rows + jnp.arange(rows)
        sc = jnp.where(keys[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          _rounded(jax.nn.softmax(sc, axis=-1), lower), v)

    o = jax.lax.map(block, jnp.arange((s + pad) // rows))   # [n, B, rows, ..]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s + pad, heads * d)[:, :s]
    return _mm(o, lw["o_proj"], lower)


def _mlp(a, lw, lower):
    g, u = jnp.split(_mm(a, lw["mlp_in"], lower), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, lw["mlp_out"], lower)


@functools.partial(jax.jit, static_argnames=("kind", "dims"))
def _layer(x, lw, kind, dims):
    (heads, kv_heads, m_heads, m_hd, groups, n, taps, scale, res, eps,
     lower, state_dtype) = dims
    with jax.default_matmul_precision("highest"):
        a = _rms_norm(x, lw["norm_in"], eps)
        if kind == "mamba":
            y = _mamba(a, lw, m_heads, m_hd, groups, n, taps, eps, lower,
                       state_dtype)
        else:
            y = _attention(a, lw, heads, kv_heads, scale, lower)
        x = x + res * y
        return x + res * _mlp(_rms_norm(x, lw["norm_post"], eps), lw, lower)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "lower"))
def _head(x, norm, embed, eps, scaling, lower):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms_norm(x, norm, eps), _f32(embed).T, lower) / scaling


def forward(w, ids, cfg, rows=None, lower=None, state=None):
    """ids [B, S] int -> float32 logits [B, S, V], or [B, len(rows), V]
    for the sequence positions in `rows`. cfg: the configuration file.
    `lower`, `state`: the control forward (this module's docstring)."""
    if int(cfg["num_local_experts"]):
        raise ValueError("num_local_experts: this reference has no routed "
                         "experts")
    ids = jnp.asarray(ids, jnp.int32)
    x = _f32(w["embed"][ids]) * float(cfg["embedding_multiplier"])
    eps = float(cfg["rms_norm_eps"])
    dims = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
            int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"]),
            int(cfg["mamba_d_conv"]), float(cfg["attention_multiplier"]),
            float(cfg["residual_multiplier"]), eps, lower, state)
    for kind, lw in zip(cfg["layer_types"], w["layers"]):
        x = _layer(x, lw, kind, dims)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["norm"], w["embed"], eps,
                 float(cfg["logits_scaling"]), lower)
