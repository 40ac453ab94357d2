"""Transformer stack (ref python/paddle/nn/layer/transformer.py:115-1094:
MultiHeadAttention, TransformerEncoder/DecoderLayer, TransformerEncoder/Decoder,
Transformer).

TPU-first: the attention core is scaled_dot_product_attention (below), which
routes to the Pallas flash-attention kernel when eligible (ops/pallas/) and
otherwise to an XLA-fused softmax(QK^T)V, in layout [batch, heads, seq, head_dim].
"""
import collections

import numpy as np
import jax.numpy as jnp

from ..framework.tensor import Tensor
from ..ops.dispatch import apply
from . import functional as F
from .layer import Layer, LayerList
from .layers_common import Linear, Dropout
from .norm import LayerNorm


def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 training=True, causal=False, scale=None):
    """q,k,v: [B, H, S, D]. Routes to pallas flash attention on TPU when
    shapes allow; XLA path otherwise."""
    from ..ops.pallas import flash_attention
    return flash_attention(q, k, v, attn_mask=attn_mask, causal=causal,
                           dropout_p=dropout_p if training else 0.0,
                           scale=scale)


class MultiHeadAttention(Layer):
    """ref transformer.py:115. Weight layouts match the reference's Linear
    projections (q/k/v/out proj over embed_dim)."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None,
                 attn_layout=None):
        super().__init__()
        import os as _os
        # "bshd" (default): the flash kernel reads [B,S,H,D] straight
        # off the projections — no layout transposes (same knob as
        # GPTConfig.attn_layout, measured faster on-chip for both GPT
        # and BERT topologies; PT_ATTN_LAYOUT lets benches A/B it)
        self.attn_layout = (attn_layout
                            or _os.environ.get("PT_ATTN_LAYOUT", "bshd"))
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim

        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _reshape_heads(self, x):
        # [B, S, E] -> [B, H, S, D]
        b, s = x.shape[0], x.shape[1]
        return x.reshape([b, s, self.num_heads, self.head_dim]) \
                .transpose([0, 2, 1, 3])

    def gen_cache(self, key, value=None, type=Cache):
        if type == MultiHeadAttention.StaticCache:
            k = self._reshape_heads(self.k_proj(key))
            v = self._reshape_heads(self.v_proj(value if value is not None
                                                else key))
            return self.StaticCache(k, v)
        if value is None:
            # incremental decode cache seeded empty
            import paddle_tpu as pt
            b = key.shape[0]
            k = pt.zeros([b, self.num_heads, 0, self.head_dim])
            v = pt.zeros([b, self.num_heads, 0, self.head_dim])
            return self.Cache(k, v)
        return self.Cache(key, value)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        if (self.attn_layout == "bshd" and cache is None
                and not self.need_weights and attn_mask is None):
            # transpose-free path: [B,S,E] -> [B,S,H,D] views feed the
            # packed-lane flash kernel natively
            from ..ops.pallas.flash_attention import flash_attention
            b, s = query.shape[0], query.shape[1]
            hd = (self.num_heads, self.head_dim)
            q = self.q_proj(query).reshape([b, s, *hd])
            k = self.k_proj(key).reshape([b, key.shape[1], *hd])
            v = self.v_proj(value).reshape([b, value.shape[1], *hd])
            out = flash_attention(
                q, k, v, causal=False,
                dropout_p=self.dropout if self.training else 0.0,
                layout="bshd")
            out = out.reshape([b, s, self.embed_dim])
            return self.out_proj(out)
        q = self._reshape_heads(self.q_proj(query))
        if isinstance(cache, MultiHeadAttention.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._reshape_heads(self.k_proj(key))
            v = self._reshape_heads(self.v_proj(value))
            if isinstance(cache, MultiHeadAttention.Cache):
                from ..ops.manipulation import concat
                k = concat([cache.k, k], axis=2)
                v = concat([cache.v, v], axis=2)
                cache = self.Cache(k, v)

        weights = None
        if self.need_weights:
            # weights require materialising S x S — use the explicit path
            from ..ops.dispatch import apply
            import math as _math
            d = q.shape[-1]
            sc = 1.0 / _math.sqrt(d)

            def attn_w(q_, k_):
                import jax
                logits = jnp.einsum("bhqd,bhkd->bhqk", q_, k_,
                                    preferred_element_type=jnp.float32) * sc
                return jax.nn.softmax(logits, axis=-1)
            weights = apply(attn_w, (q, k), name="attn_weights")
        out = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
            training=self.training)
        # [B, H, S, D] -> [B, S, E]
        b, s = out.shape[0], out.shape[2]
        out = out.transpose([0, 2, 1, 3]).reshape([b, s, self.embed_dim])
        out = self.out_proj(out)
        outs = [out]
        if self.need_weights:
            outs.append(weights)
        if cache is not None and isinstance(cache, MultiHeadAttention.Cache):
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)


class TransformerEncoderLayer(Layer):
    """ref transformer.py TransformerEncoderLayer (act_dropout, normalize_before)."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self._config = dict(
            d_model=d_model, nhead=nhead, dim_feedforward=dim_feedforward,
            dropout=dropout, activation=activation, attn_dropout=attn_dropout,
            act_dropout=act_dropout, normalize_before=normalize_before,
            weight_attr=weight_attr, bias_attr=bias_attr)
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, incremental_cache = self.self_attn(src, src, src, src_mask,
                                                    cache)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self.linear2(self.dropout(self.activation(self.linear1(src))))
        src = residual + self.dropout2(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src if cache is None else (src, incremental_cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        # replicate with fresh params (ref _get_clones deep-copies; rebuild
        # from config to get independent initialisations)
        self.layers = LayerList([encoder_layer] + [
            _clone_layer(encoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask=src_mask)
            else:
                output, new_cache = mod(output, src_mask=src_mask,
                                        cache=cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    """ref transformer.py TransformerDecoderLayer: self-attn + cross-attn + FFN."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self._config = dict(
            d_model=d_model, nhead=nhead, dim_feedforward=dim_feedforward,
            dropout=dropout, activation=activation, attn_dropout=attn_dropout,
            act_dropout=act_dropout, normalize_before=normalize_before,
            weight_attr=weight_attr, bias_attr=bias_attr)
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            if isinstance(tgt, tuple):
                tgt = tgt[0]
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incremental_cache, cache[1]))

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory,
                                           MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        self.layers = LayerList([decoder_layer] + [
            _clone_layer(decoder_layer) for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask=tgt_mask,
                             memory_mask=memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask=tgt_mask,
                                        memory_mask=memory_mask, cache=cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


class Transformer(Layer):
    """ref transformer.py:886 full encoder-decoder Transformer."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        self.d_model = d_model
        self.nhead = nhead
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask=src_mask)
        return self.decoder(tgt, memory, tgt_mask=tgt_mask,
                            memory_mask=memory_mask)

    def generate_square_subsequent_mask(self, length):
        import paddle_tpu as pt
        mask = pt.triu(pt.full([length, length], float("-inf")), diagonal=1)
        return mask


def _clone_layer(layer):
    """Fresh layer with the same config but independent initialisation
    (the reference rebuilds per-layer from config, transformer.py ~_config)."""
    return type(layer)(**layer._config)


def cached_decode_attention(q, ck, cv, pos, scale, window=None):
    """Single-token cached attention core shared by the GPT and LLaMA
    dense decoders. q: [B, H, 1, D]; ck/cv: [B, Hkv, L, D] with
    H % Hkv == 0 — grouped (GQA) when H > Hkv, WITHOUT materialising the
    repeated cache: q is reshaped to [B, Hkv, rep, D] and contracted
    against the un-repeated KV buffers. window=W restricts to the last W
    cache positions (sliding-window decode matching the training band).
    `pos` is a traced scalar (lockstep batch) or a [B] vector — the
    slot-wise serving case where every row sits at its own depth; the
    causal mask broadcasts per-row. Returns [B, H, 1, D] in cv.dtype.
    (The paged cache's cores are in nn/paged_attention.py.)"""
    import jax
    import jax.numpy as jnp

    b, h, _, d = q.shape
    hkv, L = ck.shape[1], ck.shape[2]
    rep = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, d)
    scores = jnp.einsum("bkrd,bkld->bkrl", qf,
                        ck.astype(jnp.float32)) * scale
    if jnp.ndim(pos):
        pos = jnp.reshape(pos, (b, 1, 1, 1))
    ks = jnp.arange(L)[None, None, None, :]
    mask = ks <= pos
    if window is not None:
        mask = mask & (ks > pos - window)
    probs = _masked_softmax(scores, mask).astype(cv.dtype)
    out = jnp.einsum("bkrl,bkld->bkrd", probs, cv)
    return out.reshape(b, h, 1, d)


def _masked_softmax(scores, mask):
    """Softmax with HARD exclusion of masked positions: -inf (not the
    old -1e9 additive sentinel) before the max/exp, and fully-masked
    rows (all-scratch lanes, padded chunk tails) renormalise to exactly
    0 through the guarded `where` instead of averaging over a uniform
    -1e9 row. Masked-position garbage — scratch blocks hold arbitrary
    bytes, possibly non-finite — therefore can never reach the serving
    engines' isfinite poison sentinel, while a non-finite value at an
    ATTENDED position still propagates (exp(nan) is nan). For any row
    with at least one unmasked position this is bitwise identical to
    softmax over the -1e9-masked scores: exp(-1e9 - m) and
    exp(-inf - m) both round to exactly 0.0 in f32 for finite m."""
    import jax.numpy as jnp
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0))
    denom = jnp.sum(e, axis=-1, keepdims=True)
    # the guard must key on == 0 (fully-masked), NOT > 0: a non-finite
    # denom from a genuine fault fails `> 0` and would silently zero
    # the row; `== 0` lets nan fall through to the division instead
    return jnp.where(denom == 0, 0.0, e / denom)


def scatter_kv_at(cache, kv_t, pos):
    """Write the step's K or V [B, Hkv, 1, D] into cache [B, Hkv, L, D]
    at a per-row position vector pos [B] (slot-wise decode: each serving
    slot is at its own depth). vmap over the batch axis lowers to one
    scatter — no per-slot unrolling in the compiled program. The scalar
    lockstep path keeps using dynamic_update_slice_in_dim directly."""
    import jax
    return jax.vmap(
        lambda c, t, p: jax.lax.dynamic_update_slice_in_dim(
            c, t, p, axis=1))(cache, kv_t.astype(cache.dtype), pos)
