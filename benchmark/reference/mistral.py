"""Mistral-7B (Jiang et al. 2023, arXiv:2310.06825), forward pass in plain
float32 jax.numpy.

Independent of the code under test: no kernels, no cache, no fused
projections beyond the layout of the weights it is handed. The block:
RMSNorm, rotary positions, grouped-query attention (each key/value head
serves heads/kv_heads query heads), causal sliding window, SwiGLU, untied
head. Matmuls run at "highest" precision (a float32 matmul on a TPU is
otherwise bfloat16 passes). One layer's weights are cast to float32 at a
time, so that 2 B parameters in bfloat16 need 1 GB of float32 scratch and
not 8.

Departures from the source, both noted in the configuration file:
  * rotary pairs are interleaved, (x[2i], x[2i+1]), as the program lays
    them out; Hugging Face rotates the two halves. With weights from a
    seed the two are one fixed permutation of q/k columns apart.
  * query i attends keys j with i - window < j <= i (`window` keys, itself
    included). No cell reaches past the window yet.

`forward(.., lower=<dtype>)` is the control a referee's limit has to
call wrong: the same equations with both operands of every matmul rounded
to `<dtype>` first (an 8-bit type scaled tensor by tensor to its largest
magnitude, as an 8-bit forward scales them); the arithmetic stays float32.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them); q, k and v come
fused as one [hidden, (heads + 2 kv_heads) * head_dim] matrix.
"""
import functools
import math

import jax
import jax.numpy as jnp

from ._control import mm as _mm, rounded as _rounded

QUERY_BLOCK = 512


def from_state_dict(state, n_layer):
    def blk(i):
        p = f"model.layers.{i}."
        return {
            "in_norm": state[p + "input_layernorm.weight"],
            "wqkv": state[p + "self_attn.qkv_proj.weight"],
            "wo": state[p + "self_attn.o_proj.weight"],
            "post_norm": state[p + "post_attention_layernorm.weight"],
            "gate": state[p + "mlp.gate_proj.weight"],
            "up": state[p + "mlp.up_proj.weight"],
            "down": state[p + "mlp.down_proj.weight"],
        }
    return {
        "embed": state["model.embed_tokens.weight"],
        "layers": [blk(i) for i in range(n_layer)],
        "norm": state["model.norm.weight"],
        "head": state["lm_head.weight"],
    }


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, interleaved pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c],
                     axis=-1).reshape(x.shape)


def _window_attention(q, k, v, window, lower=None):
    """q [B, S, H, D], k/v [B, S, Hkv, D] -> [B, S, H, D]."""
    b, s, h, d = q.shape
    q, k, v = (_rounded(t, lower) for t in (q, k, v))
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    keys = jnp.arange(s)
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        qpos = q0 + jnp.arange(qb.shape[1])
        keep = keys[None, :] <= qpos[:, None]
        if window is not None:
            keep &= keys[None, :] > qpos[:, None] - window
        sc = jnp.where(keep, sc, -jnp.inf)
        outs.append(jnp.einsum(
            "bhqk,bkhd->bqhd",
            _rounded(jax.nn.softmax(sc, axis=-1), lower), v))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "window", "lower"))
def _block(x, lw, heads, kv_heads, theta, eps, window, lower=None):
    with jax.default_matmul_precision("highest"):
        b, s, hid = x.shape
        d = lw["wo"].shape[0] // heads
        a = _rms_norm(x, lw["in_norm"], eps)
        qkv = _mm(a, lw["wqkv"], lower)
        q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d],
                            axis=-1)
        q = _rope(q.reshape(b, s, heads, d), theta)
        k = _rope(k.reshape(b, s, kv_heads, d), theta)
        v = v.reshape(b, s, kv_heads, d)
        o = _window_attention(q, k, v, window, lower).reshape(
            b, s, heads * d)
        x = x + _mm(o, lw["wo"], lower)
        m = _rms_norm(x, lw["post_norm"], eps)
        m = (jax.nn.silu(_mm(m, lw["gate"], lower))
             * _mm(m, lw["up"], lower))
        return x + _mm(m, lw["down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms_norm(x, norm, eps), head, lower)


def forward(w, ids, cfg, rows=None, lower=None):
    """ids [B, S] int -> float32 logits [B, S, V], or [B, len(rows), V]
    for the sequence positions in `rows`. cfg: the configuration file.
    `lower`: the control forward (this module's docstring)."""
    ids = jnp.asarray(ids, jnp.int32)
    x = w["embed"][ids].astype(jnp.float32)
    window = cfg.get("sliding_window")
    for lw in w["layers"]:
        x = _block(x, lw, int(cfg["num_attention_heads"]),
                   int(cfg["num_key_value_heads"]),
                   float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
                   None if window is None else int(window), lower)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["norm"], w["head"], float(cfg["rms_norm_eps"]),
                 lower)
