"""DeepSeek-V3-family decoder LM (`model_type: deepseek_v3`): multi-head
latent attention (MLA), `first_k_dense_replace` leading SwiGLU layers,
then routed-expert layers of gated experts with a gated shared expert.
Every block is pre-norm residual: `h + attn(RMSNorm(h))`, then
`h + mlp(RMSNorm(h))`.

MLA. `q = x W_q`, a head's 192 values `[q_nope 128 | q_rope 64]`;
`a = x W_kv_a` (rank + rope wide): `c = RMSNorm(a[:rank])`, `k_rope =
rope(a[rank:])`, one rotary key shared by every head; a head's
`[k_nope | v] = c W_kv_b`; scores `(q_nope . k_nope + q_rope . k_rope)
/ sqrt(nope + rope)`, causal softmax, `o = P v`, `y = o W_o`. Rotary
pairs are interleaved, (x[2i], x[2i+1]), as the source stores them
(`rope_interleave`); `nlp/llama.py` rotates them.

What is cached for a position is the row `[c | k_rope]` and nothing
else. The layer names no shape of the pool: it hands queries, the new
rows and `W_kv_b` to `nn.paged_attention.paged_attend_latent`, which
writes the rows and attends them absorbed (the decode wave: no K or V is
made) or expanded (a prompt chunk), by the queries a lane brings. The
sequence forward (training, the tests' oracle of the cached paths)
expands the sequence's own rows round the flash kernel.

The router and the dispatch are `nlp/moe.py`'s (shared with
`nlp/nemotron_h.py`), in the gated form; RMSNorm, rotary and the dense
SwiGLU are `nlp/llama.py`'s. Router arithmetic, the norms' statistics
and the softmax are float32; everything else runs in the parameters'
dtype.
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn import initializer as I
from .llama import (LlamaMLP, _rms_norm_raw, apply_rope_bshd,
                    apply_rope_positions, rope_tables)
from .moe import ParamBlock, RoutedExperts


class DeepseekV3Config:
    """Sizes under the names of the published `config.json`.
    `param_dtype` is the dtype parameters are created in;
    `init_weights=False` creates the matrices as zeros for a caller that
    installs its own (drawing billions of values on the host takes
    minutes). What the program does not compute is refused by name: a
    low-rank query (`q_lora_rank`), grouped routing (`n_group`,
    `topk_group` over 1), scaled rotary tables (`rope_scaling`)."""

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=8, num_attention_heads=32,
                 kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=128,
                 n_shared_experts=2, num_experts_per_tok=6,
                 routed_scaling_factor=2.448, first_k_dense_replace=1,
                 n_group=1, topk_group=1, rms_norm_eps=1e-6,
                 rope_theta=1e6, rope_scaling=None,
                 max_position_embeddings=32768, initializer_range=0.02,
                 param_dtype="float32", init_weights=True):
        for name, value, only in (("q_lora_rank", q_lora_rank, None),
                                  ("rope_scaling", rope_scaling, None),
                                  ("n_group", n_group, 1),
                                  ("topk_group", topk_group, 1)):
            if value != only:
                raise ValueError(f"{name}={value!r} is not computed by "
                                 f"this model (only {only!r})")
        if num_experts_per_tok > n_routed_experts:
            raise ValueError(f"num_experts_per_tok {num_experts_per_tok} > "
                             f"n_routed_experts {n_routed_experts}")
        if qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even, got "
                             f"{qk_rope_head_dim}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.param_dtype = jnp.dtype(param_dtype).name
        self.init_weights = bool(init_weights)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class MLAttention(ParamBlock):
    """Multi-head latent attention (the module's docstring)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        h, self.nh = cfg.hidden_size, cfg.num_attention_heads
        self.rank, self.nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        self.rope, self.vd = cfg.qk_rope_head_dim, cfg.v_head_dim
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        self.q_proj = self._matrix(h, self.nh * (self.nope + self.rope))
        self.kv_a_proj = self._matrix(h, self.rank + self.rope)
        self.kv_a_norm_weight = self._vector(self.rank, 1.0)
        self.kv_b_proj = self._matrix(self.rank,
                                      self.nh * (self.nope + self.vd))
        self.o_proj = self._matrix(self.nh * self.vd, h)
        self._cos, self._sin = rope_tables(cfg.max_position_embeddings,
                                           self.rope, cfg.rope_theta)

    def init_paged_cache(self, num_blocks, block_size, dtype):
        from ..nn.paged_attention import init_block_latent
        return init_block_latent(num_blocks, block_size, self.rank,
                                 self.rope, dtype)

    def _project(self, x):
        """x [B, L, hidden] -> q [B, L, H, nope + rope], c [B, L, rank]
        after its norm, k_rope [B, L, rope]; nothing rotated yet."""
        b, length = x.shape[:2]
        q = (x @ self.q_proj._data).reshape(b, length, self.nh, -1)
        a = x @ self.kv_a_proj._data
        c = _rms_norm_raw(a[..., :self.rank], self.kv_a_norm_weight._data,
                          self.cfg.rms_norm_eps)
        return q, c, a[..., self.rank:]

    def forward(self, x):
        """The sequence's own rows, expanded, round the flash kernel
        (whose value rows are as wide as its keys: v is zero-extended
        and cut back)."""
        from ..ops.pallas.flash_attention import _flash_array
        b, length = x.shape[:2]
        q, c, k_rope = self._project(x)
        q = jnp.concatenate(
            [q[..., :self.nope],
             apply_rope_bshd(q[..., self.nope:], self._cos, self._sin)],
            axis=-1)
        k_rope = apply_rope_bshd(k_rope[:, :, None], self._cos, self._sin)
        kv = (c @ self.kv_b_proj._data).reshape(b, length, self.nh, -1)
        k = jnp.concatenate(
            [kv[..., :self.nope],
             jnp.broadcast_to(k_rope, (b, length, self.nh, self.rope))],
            axis=-1)
        v = jnp.pad(kv[..., self.nope:],
                    ((0, 0),) * 3 + ((0, self.nope + self.rope - self.vd),))
        o = _flash_array(q, k, v, causal=True, layout="bshd",
                         scale=self.scale)[..., :self.vd]
        return o.reshape(b, length, -1).astype(x.dtype) @ self.o_proj._data

    def paged_step(self, x, cache, tables, start, valid_len=None):
        """x [B, C, hidden] at positions start + arange(C) against the
        latent pool: one token a lane in the wave (C == 1), a chunk of
        one lane's prompt (B == 1; nothing written past valid_len)."""
        from ..nn.paged_attention import paged_attend_latent
        b, c = x.shape[:2]
        positions = jnp.reshape(start, (-1, 1)) + jnp.arange(c)
        q, lat, k_rope = self._project(x)
        q = jnp.swapaxes(q, 1, 2)                       # [B, H, C, 192]
        q_rope = apply_rope_positions(q[..., self.nope:], self._cos,
                                      self._sin, positions)
        k_rope = apply_rope_positions(k_rope[:, None], self._cos, self._sin,
                                      positions)[:, 0]
        o, cache = paged_attend_latent(
            q[..., :self.nope], q_rope,
            jnp.concatenate([lat, k_rope], axis=-1),
            self.kv_b_proj._data.reshape(self.rank, self.nh, -1), cache,
            tables, start, valid_len, self.scale)
        o = jnp.swapaxes(o, 1, 2).reshape(b, c, -1)
        return o.astype(x.dtype) @ self.o_proj._data, cache


class DeepseekV3MoE(RoutedExperts):
    """Gated routed experts `down(silu(gate x) * up x)` and the shared
    experts as one SwiGLU of `n_shared_experts` times their width."""

    #: three matrices of 768 x 2048 are 19 MB double-buffered where the
    #: ungated model's two of 1856 x 2688 are 40: the kernel's VMEM holds
    #: twice the rows, and a chunk of 512 tokens (3,072 picks) reads the
    #: experts in two segments and not three
    MAX_ROWS = 2048

    def __init__(self, cfg):
        super().__init__(cfg,
                         cfg.n_shared_experts * cfg.moe_intermediate_size,
                         gated=True)


class DeepseekV3Block(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        self.dense = index < cfg.first_k_dense_replace

        def scale():
            return self.create_parameter(
                [cfg.hidden_size], dtype=cfg.param_dtype,
                default_initializer=I.Constant(1.0))

        self.input_norm_weight = scale()
        self.self_attn = MLAttention(cfg)
        self.post_norm_weight = scale()
        if self.dense:
            self.mlp = LlamaMLP(cfg)
            self.mlp.to(dtype=jnp.dtype(cfg.param_dtype))
        else:
            self.mlp = DeepseekV3MoE(cfg)

    def run(self, x, cache, attend):
        """x [B, L, hidden] raw; `attend(attention layer, its cache,
        normed x)` -> (attention output, new cache). Returns (x, the new
        cache)."""
        a, cache = attend(self.self_attn, cache, _rms_norm_raw(
            x, self.input_norm_weight._data, self.eps))
        x = x + a
        y = _rms_norm_raw(x, self.post_norm_weight._data, self.eps)
        return x + (_raw(self.mlp(Tensor(y))) if self.dense
                    else self.mlp(y)), cache


class DeepseekV3ForCausalLM(nn.Layer):
    """The stack, its embedding, final norm and untied head. The methods
    are the ones the trainer's forward and the paged engine call:
    `forward`, `init_paged_cache`, `decode_step`, `prefill_chunk`."""

    #: the paged cache holds latent rows (the paged engine reads this:
    #: it counts the rows a wave attends and a chunk expands, and
    #: refuses what moves K/V pages alone)
    latent_cache = True

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        init = (I.Normal(0.0, cfg.initializer_range) if cfg.init_weights
                else I.Constant(0.0))
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=init)
        self.layers = nn.LayerList([DeepseekV3Block(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm_weight = self.create_parameter(
            [cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.param_dtype,
            default_initializer=init)
        #: (token, expert) pairs one token makes on its way down the stack
        self.moe_picks_per_token = (
            sum(not blk.dense for blk in self.layers)
            * cfg.num_experts_per_tok)

    def _run(self, ids, caches, attend):
        """The residual stack over ids [B, L]; `attend(attention layer,
        its cache, normed x)` -> (output, new cache). Returns (final
        hidden, caches)."""
        x = self.embeddings._data[_raw(ids)]
        caches = list(caches)
        for i, blk in enumerate(self.layers):
            x, caches[i] = blk.run(x, caches[i], attend)
        return _rms_norm_raw(x, self.norm_weight._data,
                             self.cfg.rms_norm_eps), caches

    def forward(self, input_ids):
        """Logits [B, L, V] of a whole sequence."""
        x, _ = self._run(input_ids, [None] * len(self.layers),
                         lambda attn, cache, a: (attn(a), cache))
        return Tensor(x @ self.lm_head._data)

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32):
        """A latent pool a layer. max_len is held to the rotary table
        here: positions are traced inside the programs."""
        if max_len > self.cfg.max_position_embeddings:
            raise ValueError(
                f"decode length {max_len} exceeds the rotary table "
                f"({self.cfg.max_position_embeddings}); raise "
                "max_position_embeddings")
        return [blk.self_attn.init_paged_cache(num_blocks, block_size,
                                               dtype)
                for blk in self.layers]

    def _paged(self, ids, caches, tables, start, valid_len):
        tables, start = _raw(tables), _raw(start)
        return self._run(ids, caches, lambda attn, cache, a: attn.paged_step(
            a, cache, tables, start, valid_len))

    def decode_step(self, tok, caches, pos, block_tables):
        """One token a lane: tok [B, 1], pos [B], tables [B, nblk].
        Returns (logits [B, 1, V], caches)."""
        x, caches = self._paged(tok, caches, block_tables, pos, None)
        return x @ self.lm_head._data, caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier):
        """One prompt chunk [1, C] at absolute positions chunk_start +
        arange(C). Returns (logits [1, 1, V] at the chunk's `frontier`
        row, caches)."""
        x, caches = self._paged(tok_chunk, caches, block_tables,
                                chunk_start, _raw(valid_len))
        x = jax.lax.dynamic_slice_in_dim(x, frontier, 1, axis=1)
        return x @ self.lm_head._data, caches
