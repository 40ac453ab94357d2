"""GPT-2 (Radford et al. 2019), forward pass in plain float32 jax.numpy.

Independent of the code under test: no kernels, no cache, no batching
tricks. Follows the published block: learned positions, pre-LayerNorm,
fused c_attn, tanh GELU ("gelu_new"), head tied to the token embedding.
Matmuls run at "highest" precision, because on a TPU a float32 matmul is
otherwise computed in bfloat16 passes. Departures from the source: none
(dropout is off, as in the configuration file).

`forward(.., lower=<dtype>)` is the control a referee's limit has to
call wrong: the same equations with both operands of every matmul rounded
to `<dtype>` first (an 8-bit type scaled tensor by tensor to its largest
magnitude, as an 8-bit forward scales them); the arithmetic stays float32.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ._control import mm as _mm, rounded as _rounded

QUERY_BLOCK = 512


def from_state_dict(state, n_layer):
    """Program names -> this file's structure. Arrays are left in the
    dtype they come in; every use casts to float32."""
    def blk(i):
        p = f"gpt.blocks.{i}."
        return {
            "ln_1": (state[p + "ln_1.weight"], state[p + "ln_1.bias"]),
            "c_attn": (state[p + "attn.qkv_proj.weight"],
                       state[p + "attn.qkv_proj.bias"]),
            "c_proj": (state[p + "attn.out_proj.weight"],
                       state[p + "attn.out_proj.bias"]),
            "ln_2": (state[p + "ln_2.weight"], state[p + "ln_2.bias"]),
            "c_fc": (state[p + "mlp.fc_in.weight"],
                     state[p + "mlp.fc_in.bias"]),
            "mlp_proj": (state[p + "mlp.fc_out.weight"],
                         state[p + "mlp.fc_out.bias"]),
        }
    return {
        "wte": state["gpt.embeddings.word_embeddings.weight"],
        "wpe": state["gpt.embeddings.position_embeddings.weight"],
        "layers": [blk(i) for i in range(n_layer)],
        "ln_f": (state["gpt.ln_f.weight"], state["gpt.ln_f.bias"]),
    }


def _f32(t):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)


def _layer_norm(x, wb, eps):
    w, b = wb
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _causal_attention(q, k, v, lower=None):
    """q, k, v: [B, S, H, D] -> [B, S, H, D]; query blocks bound the
    [H, block, S] score matrix."""
    s, d = q.shape[1], q.shape[-1]
    q, k, v = (_rounded(t, lower) for t in (q, k, v))
    keys = jnp.arange(s)
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        qpos = q0 + jnp.arange(qb.shape[1])
        sc = jnp.where(keys[None, :] <= qpos[:, None], sc, -jnp.inf)
        outs.append(jnp.einsum(
            "bhqk,bkhd->bqhd",
            _rounded(jax.nn.softmax(sc, axis=-1), lower), v))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "lower"))
def _block(x, lw, n_head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        lw = _f32(lw)
        b, s, h = x.shape
        a = _layer_norm(x, lw["ln_1"], eps)
        qkv = _mm(a, lw["c_attn"][0], lower) + lw["c_attn"][1]
        q, k, v = (t.reshape(b, s, n_head, h // n_head)
                   for t in jnp.split(qkv, 3, axis=-1))
        o = _causal_attention(q, k, v, lower).reshape(b, s, h)
        x = x + _mm(o, lw["c_proj"][0], lower) + lw["c_proj"][1]
        m = _layer_norm(x, lw["ln_2"], eps)
        m = _gelu_new(_mm(m, lw["c_fc"][0], lower) + lw["c_fc"][1])
        return x + _mm(m, lw["mlp_proj"][0], lower) + lw["mlp_proj"][1]


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, ln_f, wte, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return _mm(_layer_norm(x, _f32(ln_f), eps), wte.T, lower)


def forward(w, ids, cfg, rows=None, lower=None):
    """ids [B, S] int -> float32 logits [B, S, V], or [B, len(rows), V]
    for the sequence positions in `rows`. cfg: the configuration file.
    `lower`: the control forward (this module's docstring)."""
    ids = jnp.asarray(ids, jnp.int32)
    s = ids.shape[1]
    eps = float(cfg["layer_norm_epsilon"])
    x = (w["wte"][ids].astype(jnp.float32)
         + w["wpe"][:s].astype(jnp.float32)[None])
    for lw in w["layers"]:
        x = _block(x, lw, int(cfg["n_head"]), eps, lower)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["ln_f"], w["wte"], eps, lower)
