"""Laguna (`model_type: laguna`), forward pass in plain float32 jax.numpy,
written from the equations.

Independent of the code under test: no kernels, no cache, no pools, no
split rotary tables, no grouped matmul, no sort of picks.

  layer i: x += attn_i(RMSNorm(x)); x += mlp_i(RMSNorm(x)); then RMSNorm
     and the untied head. Every RMSNorm has a learned scale and
     `rms_norm_eps`.
  attention of kind `layer_types[i]`, `num_attention_heads_per_layer[i]`
     query heads over `num_key_value_heads` kv-heads of `head_dim` (query
     head h reads kv-head h // (heads / kv_heads)):
     [q | k | v] = a W_qkv; the first r = `partial_rotary_factor` x
     head_dim channels of every head of q and k rotated at the token's
     position by the kind's `rope_parameters` group; scores q.k /
     sqrt(head_dim); a query at position p sees the keys k <= p, and in a
     "sliding_attention" layer only those with k > p - `sliding_window`;
     softmax, o = P v; o_h *= sigmoid(a W_g)[h] with `gating`; y = o W_o.
     Computed in blocks of `QUERY_BLOCK` query rows; a window layer's
     block reads only the keys its window can reach.
  rotary: pairs (x[2i], x[2i+1]) of the rotated channels, angle p f_i with
     f_i = theta^(-2i / r). "yarn" (as Hugging Face's
     `_compute_yarn_parameters` has it): corr(b) = r ln(L0 / (2 pi b)) /
     (2 ln theta), low = floor(corr(beta_fast)), high = ceil(corr(
     beta_slow)), ramp_i = clip((i - low) / (high - low), 0, 1), inv_i =
     (f_i / factor) ramp_i + f_i (1 - ramp_i), cos and sin times
     `attention_factor`. Angles are made in float64 on the host.
  mlp "dense": down(silu(gate x) * up x). "sparse": router logits in
     float32, p = softmax; the top k of p, over their sum, times
     `moe_routed_scaling_factor`; an expert is down(silu(gate x) * up x),
     a loop over all experts with a mask, one expert cast to float32 at a
     time; plus one shared SwiGLU of `shared_expert_intermediate_size`,
     added once.

Matmuls run at "highest" precision. A layer runs as two jitted halves
(attention, MLP), each handed the residual to overwrite.

Departures from the published description (also in the configuration
file under `assumed`): the gate is per head from the attention's normed
input, no bias; the router is a softmax with no correction bias; rotary
pairs are interleaved, as the program stores them (Hugging Face rotates
the two halves of the rotated channels: one fixed permutation of q and k
columns apart with weights from a seed).

`forward(.., lower=<dtype>)` is the control a referee's limit has to call
wrong: both operands of every matmul rounded to `<dtype>` first (an 8-bit
type scaled tensor by tensor to its largest magnitude); the arithmetic
stays float32. The router's and the gate's logits are float32 in every
forward, their input rounded. `state=` is taken for the kinds that pass it
and changes nothing: no recurrent state to round.

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
SLIDING = "sliding_attention"


def from_state_dict(state, n_layer):
    def blk(i):
        p = f"layers.{i}."
        return {k[len(p):]: v for k, v in state.items() if k.startswith(p)}
    return {"embed": state["embeddings"],
            "layers": [blk(i) for i in range(n_layer)],
            "norm": state["norm_weight"], "head": state["lm_head"]}


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(w)


# ---------------------------------------------------------------- positions
def rotary(cfg, kind):
    """(rotated channels, inverse frequencies float64, factor on cos and
    sin) of a layer kind, from the configuration's keys."""
    group = dict(cfg["rope_parameters"][kind])
    share = group.get("partial_rotary_factor",
                      cfg.get("partial_rotary_factor", 1.0))
    r = int(int(cfg["head_dim"]) * float(share))
    base = float(group["rope_theta"])
    f = 1.0 / base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if group.get("rope_type", "default") == "default":
        return r, f, 1.0
    factor = float(group["factor"])
    l0 = float(group["original_max_position_embeddings"])

    def corr(b):
        return r * math.log(l0 / (b * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(group["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(group["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    att = group.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return r, f / factor * ramp + f * (1.0 - ramp), float(att)


def _rope(x, cos, sin, r):
    """x [B, S, H, D]: the first r channels rotated, pairs interleaved;
    cos and sin [S, r/2]."""
    c, sn = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., 0:r:2], x[..., 1:r:2]
    rot = jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c],
                    axis=-1).reshape(*x.shape[:-1], r)
    return jnp.concatenate([rot, x[..., r:]], axis=-1)


# ------------------------------------------------------------------ attention
def _attention_core(q, k, v, window, lower):
    """q [B, S, H, D], k and v [B, S, Hkv, D] -> [B, S, H, D], causal,
    banded to the last `window` keys when given; blocks of QUERY_BLOCK
    query rows (a `lax.map` over them)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    q, k, v = (_rounded(t, lower) for t in (q, k, v))
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, blk, hkv, h // hkv, d)
    if window is None:
        span, lead = s, 0
    else:
        # a block's keys: the `window` before its first row, and its own
        span, lead = window + blk, window
        k, v = (jnp.pad(t, ((0, 0), (window, pad), (0, 0), (0, 0)))
                for t in (k, v))

    def one(args):
        qi, i = args                                  # [B, blk, G, R, D]
        first = i * blk
        if window is None:
            ki, vi, keys = k, v, jnp.arange(s)
        else:
            ki, vi = (jax.lax.dynamic_slice_in_dim(t, first, span, axis=1)
                      for t in (k, v))
            keys = first - lead + jnp.arange(span)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", qi, ki) / math.sqrt(d)
        rows = (first + jnp.arange(blk))[:, None]
        see = (keys[None, :] <= rows) & (keys[None, :] >= 0)
        if window is not None:
            see &= keys[None, :] > rows - window
        sc = jnp.where(see, sc, -jnp.inf)
        p = _rounded(jax.nn.softmax(sc, axis=-1), lower)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, vi)

    out = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(qb.shape[1])))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, h, d)[:, :s]


def _attention(a, lw, cos, sin, dims, lower):
    heads, kv_heads, hd, r, window, gating = dims
    b, s, _ = a.shape
    qkv = _mm(a, lw["self_attn.qkv_proj"], lower)
    q = qkv[..., :heads * hd].reshape(b, s, heads, hd)
    k = qkv[..., heads * hd:(heads + kv_heads) * hd].reshape(
        b, s, kv_heads, hd)
    v = qkv[..., (heads + kv_heads) * hd:].reshape(b, s, kv_heads, hd)
    o = _attention_core(_rope(q, cos, sin, r), _rope(k, cos, sin, r), v,
                        window, lower)
    if gating:
        g = jax.nn.sigmoid(_rounded(a, lower)
                           @ _f32(lw["self_attn.gate_proj"]))
        o = o * g[..., None]
    return _mm(o.reshape(b, s, heads * hd), lw["self_attn.o_proj"], lower)


# ------------------------------------------------------------------------ MLP
def _swiglu(x, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


def _experts(a, lw, top_k, scale, lower):
    b, s, hid = a.shape
    x = a.reshape(b * s, hid)
    n_exp = lw["mlp.experts_down"].shape[0]
    p = jax.nn.softmax(_rounded(x, lower) @ _f32(lw["mlp.router_weight"]),
                       axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
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
@functools.partial(jax.jit, static_argnames=("dims", "eps", "lower"),
                   donate_argnums=(0,))
def _attention_half(x, lw, cos, sin, dims, eps, lower):
    with jax.default_matmul_precision("highest"):
        return x + _attention(_rms_norm(x, lw["input_norm_weight"], eps), lw,
                              cos, sin, dims, lower)


@functools.partial(jax.jit, static_argnames=("dense", "dims", "eps",
                                             "lower"),
                   donate_argnums=(0,))
def _mlp_half(x, lw, dense, dims, eps, lower):
    top_k, scale = dims
    with jax.default_matmul_precision("highest"):
        a = _rms_norm(x, lw["post_norm_weight"], eps)
        if dense:
            return x + _swiglu(a, lw["mlp.gate_proj.weight"],
                               lw["mlp.up_proj.weight"],
                               lw["mlp.down_proj.weight"], lower)
        return x + _experts(a, lw, top_k, scale, lower)


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
    eps = float(cfg["rms_norm_eps"])
    positions = np.arange(ids.shape[1], dtype=np.float64)
    tables = {}
    for kind in set(cfg["layer_types"]):
        r, inv, att = rotary(cfg, kind)
        ang = np.outer(positions, inv)
        tables[kind] = (r, jnp.asarray(np.cos(ang) * att, jnp.float32),
                        jnp.asarray(np.sin(ang) * att, jnp.float32))
    mlp_dims = (int(cfg["num_experts_per_tok"]),
                float(cfg["moe_routed_scaling_factor"]))
    x = _f32(w["embed"][ids])
    for i, lw in enumerate(w["layers"]):
        kind = cfg["layer_types"][i]
        r, cos, sin = tables[kind]
        dims = (int(cfg["num_attention_heads_per_layer"][i]),
                int(cfg["num_key_value_heads"]), int(cfg["head_dim"]), r,
                int(cfg["sliding_window"]) if kind == SLIDING else None,
                bool(cfg["gating"]))
        x = _attention_half(x, lw, cos, sin, dims, eps, lower)
        x = _mlp_half(x, lw, cfg["mlp_layer_types"][i] == "dense", mlp_dims,
                      eps, lower)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["norm"], w["head"], eps, lower)
