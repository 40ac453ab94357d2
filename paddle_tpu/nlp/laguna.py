"""Laguna-family decoder LM (`model_type: laguna`): layers of two kinds of
attention in one stack, full and sliding-window, each with its own head
count and rotary table, a per-head sigmoid gate on the attention output,
then a dense SwiGLU (`mlp_layer_types` "dense") or routed gated experts
with a shared expert ("sparse"). Every block is pre-norm residual:
`h + attn(RMSNorm(h))`, then `h + mlp(RMSNorm(h))`.

Attention of layer i. `layer_types[i]` is "full_attention" (every key up
to the query) or "sliding_attention" (the keys `k > q - sliding_window`);
`num_attention_heads_per_layer[i]` query heads over `num_key_value_heads`
kv-heads of `head_dim` (grouped-query attention). q, k and v are one
fused projection; the first `partial_rotary_factor x head_dim` channels
of a head's q and k are rotated by the kind's `rope_parameters` group
(pairs (x[2i], x[2i+1]) as `nlp/llama.py` rotates them; "yarn" stretches
the table as `nlp/deepseek_v3.yarn_frequencies` does and its
`attention_factor` multiplies cos and sin), the rest pass; scores
`q.k / sqrt(head_dim)`, softmax, `o = P v`. With `gating`, a head's output
is multiplied by `sigmoid(x W_g)[h]` (W_g [hidden, heads], x the normed
input that q is made from) before `o_proj`.

Experts: the router is a softmax over `num_experts` logits, the top
`num_experts_per_tok` renormalised and scaled by
`moe_routed_scaling_factor` (`nlp/moe.route_softmax`); the dispatch is
`nlp/moe.py`'s gated form. The shared expert is one SwiGLU of
`shared_expert_intermediate_size`, added once.

The paged cache. A layer keeps K/V pages in `nn/paged_attention.py`'s
form. Where window layers stand beside full ones, the window layers keep
theirs in a pool of their own, which the serving engine sizes to what a
window attends and whose pages behind the window it gives back: the
model's `paged_layout()` names it, and a step's table row holds the full
pool's entries, then the window pool's.

Router arithmetic, the norms' statistics, the gate and the softmax are
float32; everything else runs in the parameters' dtype.
"""
import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Tensor
from ..nn import initializer as I
from ..nn.paged_layout import AttentionGroup, PagedLayout
from .deepseek_v3 import (_sequence_tables, _split_rows, _split_tables,
                          yarn_frequencies)
from .llama import LlamaMLP, _gqa_flash_bshd, _rms_norm_raw, _rotate_pairs
from .moe import ParamBlock, RoutedExperts, routed_layout

FULL, SLIDING = "full_attention", "sliding_attention"


class LagunaConfig:
    """Sizes under the names of the published `config.json`.
    `param_dtype` and `init_weights` as in `DeepseekV3Config`. What the
    program does not compute is refused by name: a tied head, attention
    biases, a layer kind or rotary type other than the two above, a
    router logit soft cap, routing weights applied to the experts'
    input."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 intermediate_size=8192, num_hidden_layers=40,
                 num_attention_heads=48, num_key_value_heads=8, head_dim=128,
                 num_attention_heads_per_layer=None, layer_types=None,
                 mlp_layer_types=None, sliding_window=512,
                 rope_parameters=None, partial_rotary_factor=None,
                 rms_norm_eps=1e-6, num_experts=256, num_experts_per_tok=8,
                 moe_intermediate_size=512,
                 shared_expert_intermediate_size=512,
                 moe_routed_scaling_factor=2.5, gating=True,
                 moe_router_logit_softcapping=0,
                 max_position_embeddings=262144, initializer_range=0.02,
                 tie_word_embeddings=False, attention_bias=False,
                 moe_apply_router_weight_on_input=False,
                 param_dtype="float32", init_weights=True):
        n = int(num_hidden_layers)
        layer_types = list(layer_types or [FULL] * n)
        mlp_layer_types = list(mlp_layer_types or ["sparse"] * n)
        heads = list(num_attention_heads_per_layer
                     or [num_attention_heads] * n)
        for name, value in (("layer_types", layer_types),
                            ("mlp_layer_types", mlp_layer_types),
                            ("num_attention_heads_per_layer", heads)):
            if len(value) != n:
                raise ValueError(f"{name} has {len(value)} entries for "
                                 f"{n} layers")
        for name, value, only in (
                ("tie_word_embeddings", bool(tie_word_embeddings), False),
                ("attention_bias", bool(attention_bias), False),
                ("moe_apply_router_weight_on_input",
                 bool(moe_apply_router_weight_on_input), False),
                ("moe_router_logit_softcapping",
                 float(moe_router_logit_softcapping or 0), 0.0)):
            if value != only:
                raise ValueError(f"{name}={value!r} is not computed by "
                                 f"this model (only {only!r})")
        for kinds, known in ((layer_types, (FULL, SLIDING)),
                             (mlp_layer_types, ("dense", "sparse"))):
            bad = sorted(set(kinds) - set(known))
            if bad:
                raise ValueError(f"layer kinds {bad} are not computed by "
                                 f"this model (only {list(known)})")
        for h in heads:
            if h % int(num_key_value_heads):
                raise ValueError(f"{h} query heads do not group over "
                                 f"{num_key_value_heads} kv-heads")
        if num_experts_per_tok > num_experts:
            raise ValueError(f"num_experts_per_tok {num_experts_per_tok} > "
                             f"num_experts {num_experts}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_layers = n
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.num_attention_heads_per_layer = [int(h) for h in heads]
        self.layer_types = layer_types
        self.mlp_layer_types = mlp_layer_types
        self.sliding_window = int(sliding_window)
        self.rms_norm_eps = float(rms_norm_eps)
        # the names nlp/moe.py reads
        self.n_routed_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.shared_expert_intermediate_size = int(
            shared_expert_intermediate_size)
        self.routed_scaling_factor = float(moe_routed_scaling_factor)
        self.gating = bool(gating)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.param_dtype = jnp.dtype(param_dtype).name
        self.init_weights = bool(init_weights)
        self.rotary = {kind: _rotary(dict((rope_parameters or {}).get(
            kind) or {}), self.head_dim, partial_rotary_factor)
            for kind in (FULL, SLIDING)}


def _rotary(group, head_dim, partial):
    """(rotated channels, inverse frequencies, factor on cos and sin) of
    one kind's `rope_parameters` group: "default" is the plain table,
    "yarn" the stretched one with its `attention_factor`."""
    kind = group.get("rope_type", "default")
    share = group.get("partial_rotary_factor", partial)
    dim = int(head_dim * float(1.0 if share is None else share))
    theta = float(group.get("rope_theta", 10000.0))
    if kind == "default":
        return dim, theta ** -(np.arange(0, dim, 2, dtype=np.float64)
                               / dim), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not computed by this "
                         "model (only 'default' or 'yarn')")
    inv, mult, _ = yarn_frequencies(dim, theta, {
        **group, "mscale": 1, "mscale_all_dim": 0})
    return dim, inv, float(group.get("attention_factor", mult))


class LagunaAttention(ParamBlock):
    """Full or sliding-window GQA with its own heads and rotary table and
    a per-head output gate (the module's docstring)."""

    def __init__(self, cfg, index):
        super().__init__(cfg)
        h, hd = cfg.hidden_size, cfg.head_dim
        self.nh = cfg.num_attention_heads_per_layer[index]
        self.nkv, self.hd = cfg.num_key_value_heads, hd
        self.kind = cfg.layer_types[index]
        self.window = cfg.sliding_window if self.kind == SLIDING else None
        self.qkv_proj = self._matrix(h, (self.nh + 2 * self.nkv) * hd)
        if cfg.gating:
            self.gate_proj = self._matrix(h, self.nh)
        self.o_proj = self._matrix(self.nh * hd, h)
        self.rot, inv, self._mult = cfg.rotary[self.kind]
        self._inv = tuple(inv)
        self._tables = _split_tables(self._inv, cfg.max_position_embeddings,
                                     self._mult)

    def init_paged_cache(self, num_blocks, block_size, dtype):
        from ..nn.paged_attention import init_block_kv
        return init_block_kv(num_blocks, self.nkv, block_size, self.hd,
                             dtype)

    def _heads(self, x):
        """x [B, L, hidden] -> q [B, L, nh, hd], k and v [B, L, nkv, hd],
        nothing rotated yet."""
        b, length = x.shape[:2]
        q, k, v = jnp.split(x @ self.qkv_proj._data,
                            [self.nh * self.hd, (self.nh + self.nkv) * self.hd],
                            axis=-1)
        return (q.reshape(b, length, self.nh, self.hd),
                k.reshape(b, length, self.nkv, self.hd),
                v.reshape(b, length, self.nkv, self.hd))

    def _rotate(self, t, cos, sin):
        """The first `rot` channels of t's heads rotated by cos and sin
        (broadcastable to [..., rot / 2]); the rest as they are."""
        if self.rot == self.hd:
            return _rotate_pairs(t, cos, sin)
        return jnp.concatenate([_rotate_pairs(t[..., :self.rot], cos, sin),
                                t[..., self.rot:]], axis=-1)

    def _out(self, o, x):
        """o [B, L, nh, hd] -> gated, merged and projected [B, L,
        hidden] in x's dtype."""
        b, length = x.shape[:2]
        if self.cfg.gating:
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(jnp.matmul(
                    x, self.gate_proj._data,
                    preferred_element_type=jnp.float32))
                o = (o.astype(jnp.float32) * g[..., None]).astype(x.dtype)
        return o.reshape(b, length, -1).astype(x.dtype) @ self.o_proj._data

    def forward(self, x):
        """The whole sequence through the flash kernel (banded for a
        window layer)."""
        length = x.shape[1]
        q, k, v = self._heads(x)
        cos, sin = (t[None, :, None, :] for t in _sequence_tables(
            self._inv, length, self._mult))
        q, k = self._rotate(q, cos, sin), self._rotate(k, cos, sin)
        o = _gqa_flash_bshd(q, k, v, self.nh, self.nkv, self.window,
                            scale=1.0 / math.sqrt(self.hd))
        return self._out(o, x)

    def paged_step(self, x, cache, tables, start, valid_len=None):
        """x [B, C, hidden] at positions start + arange(C): write the span's
        K/V through `tables` and attend the pool (`nn.paged_attention
        .paged_attend`, banded to the layer's window)."""
        from ..nn.paged_attention import paged_attend
        c = x.shape[1]
        positions = jnp.reshape(start, (-1, 1)) + jnp.arange(c)
        cos, sin = (t[:, None] for t in _split_rows(self._tables, positions))
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in self._heads(x))
        o, cache = paged_attend(self._rotate(q, cos, sin),
                                self._rotate(k, cos, sin), v, cache, tables,
                                start, valid_len, 1.0 / math.sqrt(self.hd),
                                window=self.window)
        return self._out(jnp.swapaxes(o, 1, 2), x), cache


class LagunaMoE(RoutedExperts):
    """Softmax-routed gated experts and one gated shared expert."""

    def __init__(self, cfg):
        super().__init__(cfg, cfg.shared_expert_intermediate_size,
                         gated=True, scoring="softmax")


class LagunaBlock(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        self.dense = cfg.mlp_layer_types[index] == "dense"

        def scale():
            return self.create_parameter(
                [cfg.hidden_size], dtype=cfg.param_dtype,
                default_initializer=I.Constant(1.0))

        self.input_norm_weight = scale()
        self.self_attn = LagunaAttention(cfg, index)
        self.post_norm_weight = scale()
        if self.dense:
            self.mlp = LlamaMLP(cfg)
            self.mlp.to(dtype=jnp.dtype(cfg.param_dtype))
        else:
            self.mlp = LagunaMoE(cfg)

    def run(self, x, attend):
        """x [B, L, hidden]; `attend(attention layer, normed x)` ->
        attention output."""
        x = x + attend(self.self_attn, _rms_norm_raw(
            x, self.input_norm_weight._data, self.eps))
        y = _rms_norm_raw(x, self.post_norm_weight._data, self.eps)
        return x + (self.mlp(Tensor(y))._data if self.dense
                    else self.mlp(y))


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class LagunaForCausalLM(nn.Layer):
    """The stack, its embedding, final norm and untied head. The methods
    are the ones the trainer's forward and the paged engine call:
    `forward`, `init_paged_cache`, `decode_step`, `prefill_chunk`."""

    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        init = (I.Normal(0.0, cfg.initializer_range) if cfg.init_weights
                else I.Constant(0.0))
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=init)
        self.layers = nn.LayerList([LagunaBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm_weight = self.create_parameter(
            [cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.param_dtype,
            default_initializer=init)
        windows = [blk.self_attn.window for blk in self.layers]
        # window layers beside full ones keep a pool of their own
        self._window_pool = any(windows) and None in windows

    def paged_layout(self):
        """A group a (heads, window), window layers beside full ones on a
        pool of their own, and the routed picks (nn/paged_layout.py)."""
        cfg = self.cfg
        kinds = collections.Counter((blk.self_attn.nh, blk.self_attn.window)
                                    for blk in self.layers)
        return PagedLayout(groups=tuple(
            AttentionGroup(n, "kv", h, cfg.num_key_value_heads, cfg.head_dim,
                           window=w, pool="window" if w and self._window_pool
                           else "full")
            for (h, w), n in sorted(kinds.items(), key=str)),
            **routed_layout(self))

    def _run(self, ids, attend):
        x = self.embeddings._data[_raw(ids)]
        for blk in self.layers:
            x = blk.run(x, attend)
        return _rms_norm_raw(x, self.norm_weight._data, self.cfg.rms_norm_eps)

    def forward(self, input_ids):
        """Logits [B, L, V] of a whole sequence."""
        return Tensor(self._run(input_ids, lambda attn, a: attn(a))
                      @ self.lm_head._data)

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32, num_slots=1, window_blocks=None):
        """A K/V pool a layer: `num_blocks` for a full layer, and
        `window_blocks` (default `num_blocks`) for a window layer. max_len
        is held to the rotary tables here: positions are traced inside the
        programs."""
        if max_len > self.cfg.max_position_embeddings:
            raise ValueError(
                f"decode length {max_len} exceeds the rotary table "
                f"({self.cfg.max_position_embeddings}); raise "
                "max_position_embeddings")
        return [blk.self_attn.init_paged_cache(
            num_blocks if blk.self_attn.window is None
            else window_blocks or num_blocks, block_size, dtype)
            for blk in self.layers]

    def _paged(self, ids, caches, tables, start, valid_len):
        tables, start = _raw(tables), _raw(start)
        window_tables = tables
        if self._window_pool:
            # a row: the full pool's entries, then the window pool's
            nblk = tables.shape[-1] // 2
            tables, window_tables = tables[..., :nblk], tables[..., nblk:]
        caches, i = list(caches), iter(range(len(caches)))

        def attend(attn, a):
            j = next(i)
            out, caches[j] = attn.paged_step(
                a, caches[j], tables if attn.window is None
                else window_tables, start, valid_len)
            return out

        return self._run(ids, attend), caches

    def decode_step(self, tok, caches, pos, block_tables, active=None):
        """One token a lane: tok [B, 1], pos [B], table rows as the layout
        packs them (`active` unused). Returns (logits [B, 1, V], caches)."""
        x, caches = self._paged(tok, caches, block_tables, pos, None)
        return x @ self.lm_head._data, caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier, slot=None):
        """One prompt chunk [1, C] at absolute positions chunk_start +
        arange(C); `slot` unused. Returns (logits [1, 1, V] at the
        chunk's `frontier` row, caches)."""
        x, caches = self._paged(tok_chunk, caches, block_tables,
                                chunk_start, _raw(valid_len))
        x = jax.lax.dynamic_slice_in_dim(x, frontier, 1, axis=1)
        return x @ self.lm_head._data, caches
