"""DeepSeek-V3 family (`model_type: deepseek_v3`; here Kanana-2-30B-A3B),
forward pass in plain float32 jax.numpy, written from the equations.

Independent of the code under test: no kernels, no cache, no absorbed
attention, no grouped matmul. Every layer is pre-norm residual with
RMSNorm: `h + attn(norm(h))`, then `h + mlp(norm(h))`.

  attention (MLA), all positions at once, expanded:
     q = x W_q, a head's values [q_nope | q_rope];
     a = x W_kv_a; c = RMSNorm(a[:rank]); k_rope = rope(a[rank:]), one
     rotary key for every head; q_rope = rope(q_rope);
     a head's [k_nope | v] = c W_kv_b;
     scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope),
     causal softmax, o = P v, y = o W_o. Computed in blocks of
     `QUERY_BLOCK` query rows, so that the scores of an 8.7k-token
     sequence are never whole.
  layer < first_k_dense_replace: SwiGLU, down(silu(gate x) * up x).
  later layers: router logits in float32, s = sigmoid(logits); the top k
     of s + e_score_correction_bias are chosen; their weights are the
     chosen s (without the bias) over their sum, times
     routed_scaling_factor; expert = down(silu(gate x) * up x), computed
     as a loop over all experts with a mask, one expert cast to float32
     at a time; the shared experts, one SwiGLU of n_shared_experts times
     the expert width, are added for every token.

Matmuls run at "highest" precision (a float32 matmul on a TPU is otherwise
bfloat16 passes); one layer runs at a time, so one layer's float32 copies
exist at a time.

Departures from the published modelling code (also in the configuration
file under `assumed`):
  * rotary pairs are interleaved, (x[2i], x[2i+1]), as the source stores
    them (`rope_interleave: true`); the source permutes each to the
    half-split order before rotating, the same permutation of q_rope and
    k_rope, which no score sees.
  * `n_group` = `topk_group` = 1: plain top-k over all experts, no group
    step.
  * no rotary scaling (`rope_scaling: null`), so no softmax-scale
    correction.

`forward(.., lower=<dtype>)` is the control a referee's limit has to call
wrong: the same equations with both operands of every matmul rounded to
`<dtype>` first (an 8-bit type scaled tensor by tensor to its largest
magnitude, as an 8-bit forward scales them); the arithmetic stays
float32. `state=` is taken for the kinds that pass it
(`kinds/serve_closed_routed.py`) and changes nothing: this model carries
no recurrent state to round.

Weights are given under the names of the program's `state_dict`
(`from_state_dict` is the one place that knows them; an expert's `up` and
`gate` matrices are stored [width, hidden], its `down` matrix [width,
hidden]).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ._control import mm as _mm, rounded as _rounded

QUERY_BLOCK = 256


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


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, interleaved pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c],
                     axis=-1).reshape(x.shape)


def _causal_attention(q, k, v, lower):
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
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        sc = jnp.where(keys[None, :] <= rows[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _rounded(jax.nn.softmax(sc, axis=-1), lower), v)

    out = jax.lax.map(one, (jnp.moveaxis(qb, 1, 0),
                            jnp.arange(qb.shape[1])))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, h, v.shape[-1])[:, :s]


def _attention(a, lw, heads, rank, nope, rope, vd, theta, eps, lower):
    b, s, _ = a.shape
    q = _mm(a, lw["self_attn.q_proj"], lower).reshape(b, s, heads,
                                                      nope + rope)
    lat = _mm(a, lw["self_attn.kv_a_proj"], lower)
    c = _rms_norm(lat[..., :rank], lw["self_attn.kv_a_norm_weight"], eps)
    k_rope = _rope(lat[..., rank:][:, :, None, :], theta)
    kv = _mm(c, lw["self_attn.kv_b_proj"], lower).reshape(b, s, heads,
                                                          nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)
    o = _causal_attention(q, k, kv[..., nope:], lower)
    return _mm(o.reshape(b, s, heads * vd), lw["self_attn.o_proj"], lower)


def _swiglu(x, gate, up, down, lower):
    return _mm(jax.nn.silu(_mm(x, gate, lower)) * _mm(x, up, lower), down,
               lower)


def _experts(a, lw, top_k, scale, lower=None):
    b, s, hid = a.shape
    x = a.reshape(b * s, hid)
    n_exp = lw["mlp.experts_down"].shape[0]
    # the router is float32 in every forward (`assumed`): its input is
    # what a lower precision rounds
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


@functools.partial(jax.jit, static_argnames=("dense", "dims"))
def _layer(x, lw, dense, dims):
    heads, rank, nope, rope, vd, theta, top_k, scale, eps, lower = dims
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_rms_norm(x, lw["input_norm_weight"], eps), lw,
                           heads, rank, nope, rope, vd, theta, eps, lower)
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
    x = _f32(w["embed"][ids])
    eps = float(cfg["rms_norm_eps"])
    dims = (int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"]),
            int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]),
            int(cfg["v_head_dim"]), float(cfg["rope_theta"]),
            int(cfg["num_experts_per_tok"]),
            float(cfg["routed_scaling_factor"]), eps, lower)
    for i, lw in enumerate(w["layers"]):
        x = _layer(x, lw, i < int(cfg["first_k_dense_replace"]), dims)
    if rows is not None:
        x = x[:, jnp.asarray(rows, jnp.int32)]
    return _head(x, w["norm"], w["head"], eps, lower)
