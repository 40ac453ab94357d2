"""LLaMA-family decoder LM — RMSNorm + RoPE + SwiGLU + grouped-query
attention. Third NLP model family next to GPT/BERT (the reference era
predates LLaMA; this is the modern-LLM surface a switching user expects,
built on the same TPU-native kernel/parallelism substrate).

TPU-first choices:
  - fused QKV projection sized for GQA (q heads + 2 * kv heads in one
    MXU matmul); KV heads are repeated with a reshape-broadcast (free
    under XLA) to feed the shared flash kernel
  - RoPE applied in f32 with precomputed cos/sin tables (static shapes)
  - causal Pallas flash attention (ops/pallas) for the [B,H,S,D] core
  - Megatron TP hints: QKV column-parallel, out row-parallel, SwiGLU
    gate/up column-parallel, down row-parallel (over 'mp')
"""
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..distributed import mesh as mesh_mod
from ..nn.paged_layout import AttentionGroup, PagedLayout


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=768,
                 intermediate_size=None, num_layers=12, num_heads=12,
                 num_kv_heads=None, max_seq_len=2048, rope_theta=10000.0,
                 rms_eps=1e-6, initializer_range=0.02,
                 use_recompute=False, tie_embeddings=True,
                 attn_layout=None, fused_head_loss=None,
                 attn_window=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # LLaMA sizing: 2/3 * 4h rounded; callers may pass exact values
        self.intermediate_size = intermediate_size or int(8 * hidden_size / 3)
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads   # GQA when smaller
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        # attention kernel layout (same knob as GPTConfig): "bshd"
        # (default) keeps [B,S,H,D] end to end — no layout transposes
        import os as _os
        self.attn_layout = (attn_layout
                            or _os.environ.get("PT_ATTN_LAYOUT", "bshd"))
        # vocab-chunked fused LM-head+CE, same AUTO semantics as
        # GPTConfig.fused_head_loss (None = by logits size)
        self.fused_head_loss = (None if fused_head_loss is None
                                else bool(fused_head_loss))
        # causal sliding-window attention (LLaMA + GQA + window = the
        # Mistral recipe); the banded flash kernel skips out-of-band KV
        # blocks in training and the decode band matches (see
        # cached_decode_attention)
        self.attn_window = None if attn_window is None else int(attn_window)
        self.tie_embeddings = tie_embeddings
        if num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {self.num_kv_heads}")


def _rms_norm_raw(x_, w, eps=1e-6):
    xf = x_.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x_.dtype)


from ..ops.dispatch import register_op as _register_op  # noqa: E402
_register_op("rms_norm", _rms_norm_raw)


class RMSNorm(nn.Layer):
    """Root-mean-square norm (no mean subtraction, no bias): stats in f32."""

    def __init__(self, dim, eps=1e-6):
        super().__init__()
        self.eps = eps
        self.weight = self.create_parameter(
            [dim], default_initializer=I.Constant(1.0))

    def forward(self, x):
        from ..ops.dispatch import apply
        return apply(_rms_norm_raw, (x, self.weight),
                     {"eps": float(self.eps)}, name="rms_norm")


@functools.lru_cache(maxsize=8)
def rope_tables(seq_len, head_dim, theta=10000.0):
    """cos/sin tables [S, D/2] in f32. lru-cached so every attention
    layer of a model shares ONE table (not per-layer copies baked into
    the traced program)."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(seq_len)
    freqs = np.outer(t, inv)                       # [S, D/2]
    return (jnp.asarray(np.cos(freqs), jnp.float32),
            jnp.asarray(np.sin(freqs), jnp.float32))


def _rotate_pairs(x, c, sn):
    """Rotate interleaved pairs (x[2i], x[2i+1]) of x's last dim by
    cos/sin rows c/sn (broadcastable to [..., D/2]) in f32, cast back —
    the one place the pair-layout convention lives."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    y1 = x1 * c - x2 * sn
    y2 = x1 * sn + x2 * c
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_rotate(x, cos, sin, pos_offset, head_axis):
    """Shared RoPE core over a contiguous position range.
    head_axis selects the layout — 1 for [B,H,S,D], 2 for [B,S,H,D]; the
    sequence axis is the other one. A static pos_offset is range-checked
    (a traced offset can't be; dynamic_slice would clamp silently)."""
    d = x.shape[-1]
    seq_axis = 3 - head_axis            # the non-head middle axis
    s_len = x.shape[seq_axis]
    if isinstance(pos_offset, int) and pos_offset + s_len > cos.shape[0]:
        raise ValueError(
            f"RoPE positions [{pos_offset}, {pos_offset + s_len}) exceed "
            f"the table length {cos.shape[0]} (raise max_seq_len)")
    c = jax.lax.dynamic_slice_in_dim(cos, pos_offset, s_len, axis=0)
    sn = jax.lax.dynamic_slice_in_dim(sin, pos_offset, s_len, axis=0)
    bshape = [1, 1, 1, d // 2]
    bshape[seq_axis] = s_len
    return _rotate_pairs(x, c.reshape(bshape), sn.reshape(bshape))


def apply_rope_bshd(x, cos, sin, pos_offset=0):
    """x: [B, S, H, D] (transpose-free layout)."""
    return _rope_rotate(x, cos, sin, pos_offset, head_axis=2)


def apply_rope(x, cos, sin, pos_offset=0):
    """x: [B, H, S, D] array (default layout)."""
    return _rope_rotate(x, cos, sin, pos_offset, head_axis=1)


def apply_rope_positions(x, cos, sin, positions):
    """x: [B, H, C, D] rotated at traced absolute positions [B, C] (or
    [1, C], every row alike): each lane's span starts at its own depth —
    one row a lane in a decode wave, a chunk of one lane's prompt, every
    lane's k+1-token span in the speculative verify wave. GATHERED per
    element, not dynamic-sliced: a final padded chunk can run past the
    table end, where a dynamic_slice clamps its START and silently
    shifts the rotation of VALID rows; the gather clamps only the
    out-of-range pad rows themselves (whose K/V is never written or
    read). One gather cos[idx] keeps the whole batch one fused
    program."""
    idx = jnp.minimum(positions, cos.shape[0] - 1)
    return _rotate_pairs(x, cos[idx][:, None, :, :],    # [B, 1, C, D/2]
                         sin[idx][:, None, :, :])


@functools.lru_cache(maxsize=8)
def _rope_tensor_tables(seq_len, head_dim, theta):
    """Tensor wrappers for the rope tables, cached so EVERY layer of a
    captured model dedupes onto one shared const pair in the desc."""
    from ..framework.tensor import Tensor
    cos, sin = rope_tables(seq_len, head_dim, theta)
    t_cos, t_sin = Tensor(cos), Tensor(sin)
    t_cos.stop_gradient = True
    t_sin.stop_gradient = True
    return t_cos, t_sin


def _split_rope_bshd(a, cos, sin, nh, nkv, hd):
    """Split a fused qkv projection [B, S, (nh+2*nkv)*hd] and apply RoPE
    to q/k in the transpose-free bshd layout (v reshape only). One home
    for the split/rope convention — shared by the training forward
    (_llama_attention_raw) and the serving prefill path."""
    b, s = a.shape[0], a.shape[1]
    q, k, v = jnp.split(a, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = apply_rope_bshd(q.reshape(b, s, nh, hd), cos, sin)
    k = apply_rope_bshd(k.reshape(b, s, nkv, hd), cos, sin)
    return q, k, v.reshape(b, s, nkv, hd)


def _gqa_flash_bshd(q, k, v, nh, nkv, window, scale=None):
    """GQA kv-head repeat (free reshape-broadcast under XLA) + causal
    flash attention, bshd layout; `scale` multiplies q k^T
    (None: 1/sqrt(head_dim))."""
    if nkv != nh:
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    from ..ops.pallas.flash_attention import _flash_array
    return _flash_array(q, k, v, causal=True, layout="bshd", window=window,
                        scale=scale)


def _llama_attention_raw(x, wqkv, cos, sin, num_heads=1, num_kv_heads=1,
                         head_dim=1, attn_layout="bhsd", window=None):
    """Registered (desc-serializable) GQA attention: fused qkv matmul,
    RoPE from the cos/sin table inputs, kv-head repeat, causal flash.
    The rope tables ride as const inputs so captured LLaMA programs
    replay in fresh processes. attn_layout="bshd" keeps [B,S,H,D]
    end-to-end (RoPE + kv-repeat + packed-lane kernel) — zero layout
    transposes in the whole attention block."""
    nh, nkv, hd = num_heads, num_kv_heads, head_dim
    cos = jax.lax.stop_gradient(cos)
    sin = jax.lax.stop_gradient(sin)
    b, s, _ = x.shape
    qkv = x @ wqkv                                   # [B,S,(nh+2kv)*hd]
    from ..ops.pallas.flash_attention import _flash_array
    if attn_layout == "bshd":
        q, k, v = _split_rope_bshd(qkv, cos, sin, nh, nkv, hd)
        o = _gqa_flash_bshd(q, k, v, nh, nkv, window)
        return o.reshape(b, s, nh * hd)
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = apply_rope(q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3), cos, sin)
    k = apply_rope(k.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3), cos, sin)
    v = v.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    if nkv != nh:                                    # GQA: repeat KV
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    o = _flash_array(q, k, v, causal=True, window=window)
    return o.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)


_register_op("llama_attention", _llama_attention_raw)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = h // cfg.num_heads
        self.attn_layout = getattr(cfg, "attn_layout", "bshd")
        self.attn_window = getattr(cfg, "attn_window", None)
        init = I.Normal(0.0, cfg.initializer_range)
        qkv_out = (cfg.num_heads + 2 * cfg.num_kv_heads) * self.head_dim
        self.qkv_proj = nn.Linear(h, qkv_out, bias_attr=False,
                                  weight_attr=nn.ParamAttr(initializer=init))
        self.o_proj = nn.Linear(cfg.num_heads * self.head_dim, h,
                                bias_attr=False,
                                weight_attr=nn.ParamAttr(
                                    initializer=I.Normal(
                                        0.0, cfg.initializer_range
                                        / math.sqrt(2 * cfg.num_layers))))
        self.qkv_proj.weight.sharding = P(None, mesh_mod.MP_AXIS)
        self.o_proj.weight.sharding = P(mesh_mod.MP_AXIS, None)
        self._rope_args = (cfg.max_seq_len, self.head_dim,
                           cfg.rope_theta)
        self._cos, self._sin = rope_tables(cfg.max_seq_len, self.head_dim,
                                           cfg.rope_theta)

    def forward(self, x):
        from ..ops.dispatch import apply
        t_cos, t_sin = _rope_tensor_tables(self._rope_args[0],
                                           self._rope_args[1],
                                           self._rope_args[2])
        out = apply(_llama_attention_raw,
                    (x, self.qkv_proj.weight, t_cos, t_sin),
                    {"num_heads": self.num_heads,
                     "num_kv_heads": self.num_kv_heads,
                     "head_dim": self.head_dim,
                     "attn_layout": self.attn_layout,
                     "window": (None if self.attn_window is None
                                else int(self.attn_window))},
                    name="llama_attention")
        return self.o_proj(out)

    # -------------------------------------------------- incremental decode
    def init_cache(self, batch, max_len, dtype=jnp.float32):
        """KV cache [B, kv_heads, L, head_dim] x2 — GQA caches only the
        kv heads (the memory win that motivates GQA at decode time).
        max_len is validated against the RoPE table here because inside
        the decode loop `pos` is traced and apply_rope's static range
        check cannot fire (dynamic_slice would clamp silently)."""
        if max_len > self._cos.shape[0]:
            raise ValueError(
                f"decode length {max_len} exceeds the RoPE table "
                f"({self._cos.shape[0]}); raise max_seq_len")
        shape = (batch, self.num_kv_heads, max_len, self.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def init_paged_cache(self, num_blocks, block_size, dtype=jnp.float32):
        """A layer's block pool (nn.paged_attention owns its form) — GQA
        pools cache only the kv heads, and requests claim blocks through
        a host-managed table (serving/paged)."""
        from ..nn.paged_attention import init_block_kv
        return init_block_kv(num_blocks, self.num_kv_heads, block_size,
                             self.head_dim, dtype)

    def _qkv_heads(self, x):
        """x [B, S, H] Tensor -> q [B, nh, S, D], k and v [B, nkv, S, D]
        arrays, before rotation."""
        from ..framework.tensor import Tensor
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        a = qkv._data if isinstance(qkv, Tensor) else qkv
        q, k, v = jnp.split(a, [nh * hd, (nh + nkv) * hd], axis=-1)
        return (q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3),
                k.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3),
                v.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3))

    def _merge_heads(self, out, x):
        """out [B, nh, S, D] -> the output projection of [B, S, nh * D],
        in x's dtype."""
        from ..framework.tensor import Tensor
        b, _, s, _ = out.shape
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, -1)
        return self.o_proj(Tensor(out.astype(x._data.dtype)))

    def decode(self, x_t, cache, pos):
        """One-token step on the dense cache: RoPE at `pos` (traced),
        write K/V, attend over cache[:pos]. x_t: [B, 1, H] Tensor. `pos`
        is a scalar (lockstep batch) or a [B] vector — slot-wise serving
        decode where each row is at its own depth; the vector path
        scatters per-row cache writes and masks per-row, same fixed
        shapes, one program."""
        q, k_t, v_t = self._qkv_heads(x_t)
        from ..nn.transformer import cached_decode_attention, scatter_kv_at
        ck, cv = cache
        if jnp.ndim(pos):
            positions = pos[:, None]
            q = apply_rope_positions(q, self._cos, self._sin, positions)
            k_t = apply_rope_positions(k_t, self._cos, self._sin, positions)
            ck = scatter_kv_at(ck, k_t, pos)
            cv = scatter_kv_at(cv, v_t, pos)
        else:
            q = apply_rope(q, self._cos, self._sin, pos_offset=pos)
            k_t = apply_rope(k_t, self._cos, self._sin, pos_offset=pos)
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k_t.astype(ck.dtype), pos, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v_t.astype(cv.dtype), pos, axis=2)
        out = cached_decode_attention(q, ck, cv, pos,
                                      1.0 / math.sqrt(self.head_dim),
                                      window=self.attn_window)
        return self._merge_heads(out, x_t), (ck, cv)

    def paged_step(self, x, cache, block_tables, start, valid_len=None):
        """C positions a lane against the block pool, x: [B, C, H]: RoPE
        at the absolute positions start + arange(C), the span's K/V
        written through the tables [B, nblk] (nothing at i >= valid_len:
        a padded tail, a horizon or spec_len clamp), then the C queries
        attend the pool — what the lane cached before plus the span's own
        causal prefix. `start` and `valid_len` are scalars or [B]
        vectors. One body at every width: the decode wave is C == 1, the
        prefill chunk B == 1, the speculative verify wave [S, k + 1]."""
        q, k, v = self._qkv_heads(x)
        positions = jnp.reshape(start, (-1, 1)) + jnp.arange(x.shape[1])
        q = apply_rope_positions(q, self._cos, self._sin, positions)
        k = apply_rope_positions(k, self._cos, self._sin, positions)
        from ..nn.paged_attention import paged_attend
        out, cache = paged_attend(q, k, v, cache, block_tables, start,
                                  valid_len,
                                  1.0 / math.sqrt(self.head_dim),
                                  window=self.attn_window)
        return self._merge_heads(out, x), cache

    def prefill(self, x, cache):
        """Prompt-phase step: the training forward's attention math over
        x [B, P, H], additionally writing the prompt's K/V into
        cache[:, :, :P] so decode can continue at pos=P. Positions past
        the true prompt length hold garbage until the decode frontier
        overwrites them — cached_decode_attention masks ks<=pos, so a
        not-yet-rewritten cell is never attended. P is static (the engine
        pads prompts to one bucket) => one compiled prefill program."""
        from ..framework.tensor import Tensor
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        a = qkv._data if isinstance(qkv, Tensor) else qkv
        q, k, v = _split_rope_bshd(a, self._cos, self._sin, nh, nkv, hd)
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.transpose(k, (0, 2, 1, 3)).astype(ck.dtype),
            (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.transpose(v, (0, 2, 1, 3)).astype(cv.dtype),
            (0, 0, 0, 0))
        o = _gqa_flash_bshd(q, k, v, nh, nkv, self.attn_window)
        out = self.o_proj(Tensor(
            o.reshape(b, s, nh * hd).astype(x._data.dtype)))
        return out, (ck, cv)


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = nn.Linear(h, m, bias_attr=False,
                                   weight_attr=nn.ParamAttr(initializer=init))
        self.up_proj = nn.Linear(h, m, bias_attr=False,
                                 weight_attr=nn.ParamAttr(initializer=init))
        self.down_proj = nn.Linear(m, h, bias_attr=False,
                                   weight_attr=nn.ParamAttr(
                                       initializer=I.Normal(
                                           0.0, cfg.initializer_range
                                           / math.sqrt(2 * cfg.num_layers))))
        self.gate_proj.weight.sharding = P(None, mesh_mod.MP_AXIS)
        self.up_proj.weight.sharding = P(None, mesh_mod.MP_AXIS)
        self.down_proj.weight.sharding = P(mesh_mod.MP_AXIS, None)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x

    def decode(self, x, cache, pos):
        a, cache = self.self_attn.decode(self.input_layernorm(x), cache,
                                         pos)
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, cache

    def prefill(self, x, cache):
        a, cache = self.self_attn.prefill(self.input_layernorm(x), cache)
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, cache

    def paged_step(self, x, cache, block_tables, start, valid_len=None):
        a, cache = self.self_attn.paged_step(
            self.input_layernorm(x), cache, block_tables, start, valid_len)
        x = x + a
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x, cache


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.embed_tokens.weight.sharding = P(mesh_mod.MP_AXIS, None)
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.cfg.use_recompute:
            from ..incubate.recompute import recompute
            for blk in self.layers:
                x = recompute(blk, x)
        else:
            for blk in self.layers:
                x = blk(x)
        return self.norm(x)

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return [blk.self_attn.init_cache(batch, max_len, dtype)
                for blk in self.layers]

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32):
        """Per-layer block pools [num_blocks, kv_heads, block_size,
        2 * hd]. max_len (= nblk * block_size, the per-request horizon) is
        validated against the RoPE table here because positions are
        traced inside the programs (dynamic_slice would clamp
        silently)."""
        first = self.layers[0].self_attn
        if max_len > first._cos.shape[0]:
            raise ValueError(
                f"decode length {max_len} exceeds the RoPE table "
                f"({first._cos.shape[0]}); raise max_seq_len")
        return [blk.self_attn.init_paged_cache(num_blocks, block_size,
                                               dtype)
                for blk in self.layers]

    def decode_step(self, tok, caches, pos):
        """One token a row on the dense caches. tok: [B, 1] ids; pos:
        traced position — a scalar, or a [B] vector for slot-wise
        serving decode. Returns (h, caches)."""
        from ..framework.tensor import Tensor
        pos = pos._data if isinstance(pos, Tensor) else pos
        x = self.embed_tokens(tok)
        new_caches = []
        for blk, cache in zip(self.layers, caches):
            x, cache = blk.decode(x, cache, pos)
            new_caches.append(cache)
        return self.norm(x), new_caches

    def paged_step(self, tok, caches, block_tables, start, valid_len=None):
        """[B, C] ids at absolute positions start + arange(C) against the
        block pools (see LlamaAttention.paged_step for the three widths
        the paged engines run it at). Returns (h [B, C, H], caches)."""
        from ..framework.tensor import Tensor
        start = start._data if isinstance(start, Tensor) else start
        x = self.embed_tokens(tok)
        new_caches = []
        for blk, cache in zip(self.layers, caches):
            x, cache = blk.paged_step(x, cache, block_tables, start,
                                      valid_len)
            new_caches.append(cache)
        return self.norm(x), new_caches

    def prefill(self, input_ids, max_len, dtype=jnp.float32):
        """Prompt-phase forward over [B, P] ids that also populates fresh
        [B, kv_heads, max_len, head_dim] KV caches for positions [0, P).
        Returns (hidden, caches) — decode continues at pos=P."""
        x = self.embed_tokens(input_ids)
        caches = self.init_cache(input_ids.shape[0], max_len, dtype)
        new_caches = []
        for blk, cache in zip(self.layers, caches):
            x, cache = blk.prefill(x, cache)
            new_caches.append(cache)
        return self.norm(x), new_caches


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(
                cfg.hidden_size, cfg.vocab_size, bias_attr=False,
                weight_attr=nn.ParamAttr(
                    initializer=I.Normal(0.0, cfg.initializer_range)))
            self.lm_head.weight.sharding = P(None, mesh_mod.MP_AXIS)

    def _logits(self, hidden, frontier=None):
        """Logits of hidden [B, S, H]; frontier (traced index): of that
        one position only, [B, 1, V] — the serving engines want ONE
        next-token row, and indexing before the LM head keeps the vocab
        matmul [1, V] instead of [S, V] (S = padded bucket or chunk)."""
        if frontier is not None:
            from ..framework.tensor import Tensor
            hr = hidden._data if isinstance(hidden, Tensor) else hidden
            hidden = Tensor(jax.lax.dynamic_slice_in_dim(hr, frontier, 1,
                                                         axis=1))
        if self.cfg.tie_embeddings:
            w = self.model.embed_tokens.weight
            from ..ops.math import matmul
            return matmul(hidden, w, transpose_y=True)
        return self.lm_head(hidden)

    def forward(self, input_ids):
        hidden = self.model(input_ids)
        logits = self._logits(hidden)
        from .gpt import _use_fused_head
        if (self.cfg.tie_embeddings
                and _use_fused_head(self.cfg, logits.shape)):
            # hand the loss the pre-head pieces so llama_pretrain_loss
            # (-> gpt_pretrain_loss) takes the vocab-chunked fused CE and
            # the dense head matmul above DCEs under jit. ARRAY snapshot
            # of w for the same functional_call reason as GPT (gpt.py):
            # the Tensor's _data is restored after tracing.
            w = self.model.embed_tokens.weight
            logits._fused_head = (hidden, w, w._data)
        return logits

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return self.model.init_cache(batch, max_len, dtype)

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32, num_slots=1):
        return self.model.init_paged_cache(num_blocks, block_size,
                                           max_len, dtype)

    def paged_layout(self):
        """K/V pages of the kv-heads, layers alike (nn/paged_layout.py)."""
        cfg = self.cfg
        return PagedLayout(groups=(AttentionGroup(
            cfg.num_layers, "kv", cfg.num_heads, cfg.num_kv_heads,
            cfg.hidden_size // cfg.num_heads, window=cfg.attn_window),))

    def decode_step(self, tok, caches, pos, block_tables=None, active=None):
        """One token a row: tok [B, 1] at pos (a scalar, or [B]). On the
        dense caches; given block_tables [B, nblk] the caches are block
        POOLS and the step is a chunk of one (the paged decode wave).
        The one place that chooses between the two; `active` unused."""
        if block_tables is not None:
            return self.prefill_chunk(tok, caches, block_tables, pos, None)
        h, caches = self.model.decode_step(tok, caches, pos)
        return self._logits(h), caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier=None, slot=None):
        """[B, C] ids a lane against the block pools, at absolute
        positions chunk_start + arange(C) (chunk_start and valid_len:
        scalars or [B]): a prompt chunk of one lane, or every lane's
        k + 1 drafted tokens in the speculative verify wave, whose
        logits for ALL C positions ([S, C, V]) are the cost that program
        pays on purpose. frontier: see _logits; only the final chunk's
        frontier row is consumed by the serving engine; `slot` unused."""
        h, caches = self.model.paged_step(tok_chunk, caches, block_tables,
                                          chunk_start, valid_len)
        return self._logits(h, frontier), caches

    def prefill(self, input_ids, max_len, dtype=jnp.float32,
                frontier=None):
        h, caches = self.model.prefill(input_ids, max_len, dtype)
        return self._logits(h, frontier), caches


def llama_pretrain_loss(logits, labels):
    """Same label-shift CE as GPT (see gpt.gpt_pretrain_loss)."""
    from .gpt import gpt_pretrain_loss
    return gpt_pretrain_loss(logits, labels)
