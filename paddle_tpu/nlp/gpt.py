"""GPT decoder-only LM (BASELINE config 3: GPT-2 medium with pipeline + tensor
parallel + recompute).

TPU-first design decisions:
  - fused QKV projection (one MXU matmul instead of three)
  - causal flash attention (Pallas kernel, ops/pallas)
  - pre-norm blocks, gelu MLP
  - every Linear weight carries a PartitionSpec hint so pjit shards
    Megatron-style over the 'mp' axis with zero code changes
    (attention QKV column-parallel, attn-out row-parallel; MLP in
    column-parallel, MLP out row-parallel; embeddings vocab-parallel)
  - layers are homogeneous -> pipeline engine can split evenly over 'pp'
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..distributed import mesh as mesh_mod
from ..nn.paged_layout import AttentionGroup, PagedLayout
from ..nn.transformer import scaled_dot_product_attention


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_hidden_size=None, max_seq_len=1024,
                 dropout=0.1, attn_dropout=0.1, initializer_range=0.02,
                 use_recompute=False, sequence_parallel=False,
                 moe_experts=0, moe_k=2, moe_capacity_factor=1.25,
                 fused_head_loss=None, attn_layout=None,
                 attn_window=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.use_recompute = use_recompute
        # long-context sequence parallelism over the 'sp' mesh axis — new
        # capability vs the reference. False | True/"ring" (ring attention,
        # distributed/ring_attention.py) | "ulysses" (all-to-all head
        # redistribution, distributed/ulysses.py)
        self.sequence_parallel = sequence_parallel
        # MoE FFN: >0 replaces every block's MLP with an expert-parallel
        # MoELayer over the 'ep' mesh axis (incubate/moe.py)
        self.moe_experts = int(moe_experts)
        self.moe_k = moe_k
        self.moe_capacity_factor = moe_capacity_factor
        # vocab-chunked fused LM-head + CE (ops/chunked_ce.py): the [B,S,V]
        # logits never hit HBM in training (XLA DCEs the unfused head
        # matmul when only the loss is consumed). None = AUTO by logits
        # size at forward time: chunking pays one extra matmul pass plus
        # per-chunk [N,C] intermediates, which only wins once the dense
        # logits are too big to ride HBM comfortably (measured on-chip:
        # gpt2s b=8 s=1024 v=32k runs ~20ms/step FASTER dense)
        self.fused_head_loss = (None if fused_head_loss is None
                                else bool(fused_head_loss))
        # attention kernel layout: "bshd" (default — kernel reads the
        # [B,S,H,D] qkv projection natively via packed 128-lane head
        # groups, no layout transposes) or "bhsd". Measured on-chip
        # (v5e, 2026-08-01): gpt2s b=8 64.2 vs 66.4 ms/step, BERT-base
        # b=16 63.9 vs 67.7 — bshd wins both, so it is the default; env
        # PT_ATTN_LAYOUT lets the bench A/B it without code changes.
        import os as _os
        self.attn_layout = (attn_layout
                            or _os.environ.get("PT_ATTN_LAYOUT", "bshd"))
        # causal sliding-window attention (last W keys per query); the
        # flash kernels skip KV blocks outside the band — O(S*W) attention
        # for long context. None = full causal.
        self.attn_window = None if attn_window is None else int(attn_window)


def gpt2_small(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt2_medium(**kw):
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv_proj = nn.Linear(h, 3 * h, weight_attr=nn.ParamAttr(
            initializer=init))
        self.out_proj = nn.Linear(h, h, weight_attr=nn.ParamAttr(
            initializer=I.Normal(0.0, cfg.initializer_range
                                 / math.sqrt(2 * cfg.num_layers))))
        self.attn_dropout_p = cfg.attn_dropout
        self.attn_layout = getattr(cfg, "attn_layout", "bshd")
        self.attn_window = getattr(cfg, "attn_window", None)
        self.sequence_parallel = cfg.sequence_parallel
        if self.attn_window is not None and cfg.sequence_parallel:
            raise ValueError(
                "attn_window with sequence_parallel is not implemented: "
                "the ring/ulysses paths compute full causal attention "
                "(a silent full-attention fallback would train a "
                "different model than configured)")
        if cfg.sequence_parallel and cfg.attn_dropout:
            import warnings
            warnings.warn(
                "sequence_parallel ring attention does not apply "
                "attention-prob dropout; attn_dropout is ignored "
                "(residual dropout still applies)")
        self.resid_dropout = nn.Dropout(cfg.dropout)
        # Megatron shardings: QKV column-parallel, out row-parallel
        self.qkv_proj.weight.sharding = P(None, mesh_mod.MP_AXIS)
        self.qkv_proj.bias.sharding = P(mesh_mod.MP_AXIS)
        self.out_proj.weight.sharding = P(mesh_mod.MP_AXIS, None)

    def forward(self, x):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)                       # [B,S,3H]
        if self.attn_layout == "bshd" and not self.sequence_parallel \
                and not (self.attn_dropout_p and self.training):
            # BSHD fast path: the kernel reads [B,S,H,D] natively, so the
            # only layout op is the free reshape off the qkv matmul —
            # kills the bf16 [B,H,S,D] transposes (PERF.md hotspot #1).
            # q/k/v split indexes the UNSHARDED size-3 axis: the head axis
            # carries the Megatron mp sharding and slicing across it would
            # make GSPMD insert collectives inside per-stage control flow
            qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
            q = qkv[:, :, 0]
            k = qkv[:, :, 1]
            v = qkv[:, :, 2]
            from ..ops.pallas import flash_attention as _fa
            out = _fa(q, k, v, causal=True, layout="bshd",
                      window=self.attn_window)
            out = out.reshape([b, s, h])
            return self.resid_dropout(self.out_proj(out))
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        qkv = qkv.transpose([2, 0, 3, 1, 4])          # [3,B,Hd,S,D]
        q, k, v = qkv[0], qkv[1], qkv[2]
        if self.sequence_parallel:
            # sequence parallelism over 'sp'; attention-prob dropout is
            # skipped on this path (scores are never materialised globally).
            # "ring" (default) streams K/V around the ICI ring; "ulysses"
            # all-to-alls to head-sharded full-sequence attention.
            if self.sequence_parallel == "ulysses":
                from ..distributed.ulysses import ulysses_flash_attention
                out = ulysses_flash_attention(q, k, v, causal=True)
            elif self.sequence_parallel in (True, "ring"):
                from ..distributed.ring_attention import ring_flash_attention
                out = ring_flash_attention(q, k, v, causal=True)
            else:
                raise ValueError(
                    f"unknown sequence_parallel={self.sequence_parallel!r}; "
                    "expected False, True/'ring', or 'ulysses'")
        else:
            if self.attn_window is not None:
                from ..ops.pallas import flash_attention as _fa
                out = _fa(q, k, v, causal=True, window=self.attn_window,
                          dropout_p=(self.attn_dropout_p
                                     if self.training else 0.0))
            else:
                out = scaled_dot_product_attention(
                    q, k, v, causal=True, dropout_p=self.attn_dropout_p,
                    training=self.training)
        out = out.transpose([0, 2, 1, 3]).reshape([b, s, h])
        return self.resid_dropout(self.out_proj(out))

    # -------------------------------------------------- incremental decode
    def init_cache(self, batch, max_len, dtype=jnp.float32):
        """KV cache [B, heads, L, head_dim] x2 (ref paddlenlp gen cache /
        fused multi-transformer CacheKV)."""
        shape = (batch, self.num_heads, max_len, self.head_dim)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def init_paged_cache(self, num_blocks, block_size, dtype=jnp.float32):
        """A layer's block pool (nn.paged_attention owns its form) —
        requests claim BLOCKS (named by a host-managed table), not dense
        rows; see serving/paged."""
        from ..nn.paged_attention import init_block_kv
        return init_block_kv(num_blocks, self.num_heads, block_size,
                             self.head_dim, dtype)

    def _qkv_heads(self, x):
        """x [B, S, H] Tensor -> q, k, v arrays [B, nh, S, D]."""
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        a = qkv._data if isinstance(qkv, Tensor) else qkv
        a = a.reshape(b, s, 3, self.num_heads, self.head_dim)
        a = jnp.transpose(a, (2, 0, 3, 1, 4))           # [3, B, nh, S, D]
        return a[0], a[1], a[2]

    def _merge_heads(self, out, x):
        """out [B, nh, S, D] -> the output projection of [B, S, H], in
        x's dtype."""
        b, _, s, _ = out.shape
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, -1)
        return self.out_proj(Tensor(out.astype(x._data.dtype)))

    def decode(self, x_t, cache, pos):
        """One-token step on the dense cache: write K/V at `pos`, attend
        q over cache[:pos]. x_t: [B, 1, H] Tensor; pos: traced int — a
        scalar (lockstep batch) or a [B] vector (slot-wise serving
        decode: per-row cache scatter + per-row mask, same shapes, one
        program)."""
        q, k_t, v_t = self._qkv_heads(x_t)
        from ..nn.transformer import cached_decode_attention, scatter_kv_at
        ck, cv = cache
        if jnp.ndim(pos):
            ck = scatter_kv_at(ck, k_t, pos)
            cv = scatter_kv_at(cv, v_t, pos)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k_t.astype(ck.dtype), pos, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v_t.astype(cv.dtype), pos, axis=2)
        out = cached_decode_attention(q, ck, cv, pos,
                                      1.0 / math.sqrt(self.head_dim),
                                      window=self.attn_window)
        return self._merge_heads(out, x_t), (ck, cv)

    def paged_step(self, x, cache, block_tables, start, valid_len=None):
        """C positions a lane against the block pool, x: [B, C, H]. Their
        K/V go through the tables [B, nblk] to the absolute positions
        start + arange(C) (nothing at i >= valid_len: a padded tail, a
        horizon or spec_len clamp), then the C queries attend the pool —
        what the lane cached before plus the span's own causal prefix.
        `start` and `valid_len` are scalars or [B] vectors. One body at
        every width: the decode wave is C == 1, the prefill chunk B == 1,
        the speculative verify wave [S, k + 1]."""
        q, k, v = self._qkv_heads(x)
        from ..nn.paged_attention import paged_attend
        out, cache = paged_attend(q, k, v, cache, block_tables, start,
                                  valid_len,
                                  1.0 / math.sqrt(self.head_dim),
                                  window=self.attn_window)
        return self._merge_heads(out, x), cache

    def prefill(self, x, cache):
        """Prompt-phase step: the forward attention math over x [B, P, H]
        that also writes the prompt's K/V into cache[:, :, :P] so decode
        continues at pos=P (cells past the true prompt length are rewritten
        by the decode frontier before the ks<=pos mask ever exposes them)."""
        q, k, v = self._qkv_heads(x)
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, 0, 0, 0))
        from ..ops.pallas.flash_attention import _flash_array
        out = _flash_array(q, k, v, causal=True, window=self.attn_window)
        return self._merge_heads(out, x), (ck, cv)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.fc_in = nn.Linear(cfg.hidden_size, cfg.ffn_hidden_size,
                               weight_attr=nn.ParamAttr(initializer=init))
        self.fc_out = nn.Linear(cfg.ffn_hidden_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(
                                    initializer=I.Normal(
                                        0.0, cfg.initializer_range
                                        / math.sqrt(2 * cfg.num_layers))))
        self.dropout = nn.Dropout(cfg.dropout)
        self.fc_in.weight.sharding = P(None, mesh_mod.MP_AXIS)
        self.fc_in.bias.sharding = P(mesh_mod.MP_AXIS)
        self.fc_out.weight.sharding = P(mesh_mod.MP_AXIS, None)

    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x),
                                               approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size)
        if cfg.moe_experts:
            from ..incubate.moe import MoELayer
            self.mlp = MoELayer(cfg.hidden_size, cfg.ffn_hidden_size,
                                cfg.moe_experts, k=cfg.moe_k,
                                capacity_factor=cfg.moe_capacity_factor,
                                initializer_range=cfg.initializer_range)
        else:
            self.mlp = GPTMLP(cfg)

    def forward(self, x):
        x = x + self.attn(self.ln_1(x))
        m = self.mlp(self.ln_2(x))
        if isinstance(m, tuple):         # MoE FFN: (out, aux_loss)
            x = x + m[0]
            return x, m[1]
        return x + m

    def _mlp_residual(self, x):
        m = self.mlp(self.ln_2(x))
        if isinstance(m, tuple):         # MoE FFN: (out, aux_loss) — aux
            m = m[0]                     # is a training-only signal
        return x + m

    def decode(self, x, cache, pos):
        a, cache = self.attn.decode(self.ln_1(x), cache, pos)
        return self._mlp_residual(x + a), cache

    def prefill(self, x, cache):
        a, cache = self.attn.prefill(self.ln_1(x), cache)
        return self._mlp_residual(x + a), cache

    def paged_step(self, x, cache, block_tables, start, valid_len=None):
        a, cache = self.attn.paged_step(self.ln_1(x), cache, block_tables,
                                        start, valid_len)
        return self._mlp_residual(x + a), cache


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.position_embeddings = nn.Embedding(
            cfg.max_seq_len, cfg.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.dropout = nn.Dropout(cfg.dropout)
        self.word_embeddings.weight.sharding = P(mesh_mod.MP_AXIS, None)

    def forward(self, input_ids, position_ids=None):
        import paddle_tpu as pt
        if position_ids is None:
            s = input_ids.shape[-1]
            position_ids = pt.arange(s, dtype="int32").unsqueeze(0)
        return self.dropout(self.word_embeddings(input_ids)
                            + self.position_embeddings(position_ids))


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None):
        """Returns hidden states; with MoE blocks, (hidden, aux_total) —
        the aux loss flows through the data path (remat-safe: it is an
        output of each checkpointed block, so it is a valid outer-trace
        value; no global side-channel)."""
        x = self.embeddings(input_ids, position_ids)
        use_remat = self.cfg.use_recompute
        moe = bool(self.cfg.moe_experts)
        aux_total = None
        for blk in self.blocks:
            if use_remat:
                from ..incubate.recompute import recompute
                out = recompute(blk, x)
            else:
                out = blk(x)
            if moe:
                x, aux = out
                aux_total = aux if aux_total is None else aux_total + aux
            else:
                x = out
        h = self.ln_f(x)
        return (h, aux_total) if moe else h

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        if max_len > self.cfg.max_seq_len:
            raise ValueError(
                f"decode length {max_len} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}: the position-embedding gather at "
                "a traced pos would clamp silently")
        return [blk.attn.init_cache(batch, max_len, dtype)
                for blk in self.blocks]

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32):
        """Per-layer block pools [num_blocks, heads, block_size, 2 * hd].
        max_len is the per-request horizon (nblk * block_size) — checked
        against the position-embedding table here because inside the
        decode wave `pos` is traced and the gather would clamp
        silently."""
        if max_len > self.cfg.max_seq_len:
            raise ValueError(
                f"decode length {max_len} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}: the position-embedding gather "
                "at a traced pos would clamp silently")
        return [blk.attn.init_paged_cache(num_blocks, block_size, dtype)
                for blk in self.blocks]

    def decode_step(self, tok, caches, pos):
        """One token a row on the dense caches. tok: [B, 1] ids; pos:
        traced position — a scalar, or a [B] vector for slot-wise
        serving decode. Returns (h, caches)."""
        pos = pos._data if isinstance(pos, Tensor) else pos
        if jnp.ndim(pos):
            pos_ids = jnp.asarray(pos, jnp.int32)[:, None]
        else:
            pos_ids = jnp.full(
                (tok.shape[0] if hasattr(tok, "shape") else 1, 1),
                0, jnp.int32) + pos
        x = self.embeddings(tok, Tensor(pos_ids))
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, cache = blk.decode(x, cache, pos)
            new_caches.append(cache)
        return self.ln_f(x), new_caches

    def paged_step(self, tok, caches, block_tables, start, valid_len=None):
        """[B, C] ids at absolute positions start + arange(C) against the
        block pools (see GPTAttention.paged_step for the three widths the
        paged engines run it at). Position-embedding rows are gathered at
        the per-lane position matrix; a padded tail's positions past the
        table clamp harmlessly (their K/V is not written and their logits
        are not read). Returns (h [B, C, H], caches)."""
        start = start._data if isinstance(start, Tensor) else start
        c = tok.shape[1]
        pos_ids = jnp.minimum(
            jnp.reshape(start, (-1, 1)) + jnp.arange(c, dtype=jnp.int32),
            self.cfg.max_seq_len - 1)
        x = self.embeddings(tok, Tensor(pos_ids))
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, cache = blk.paged_step(x, cache, block_tables, start,
                                      valid_len)
            new_caches.append(cache)
        return self.ln_f(x), new_caches

    def prefill(self, input_ids, max_len, dtype=jnp.float32):
        """Prompt-phase forward over [B, P] ids that also populates fresh
        [B, heads, max_len, head_dim] KV caches for positions [0, P).
        Returns (hidden, caches) — decode continues at pos=P."""
        x = self.embeddings(input_ids)
        caches = self.init_cache(input_ids.shape[0], max_len, dtype)
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, cache = blk.prefill(x, cache)
            new_caches.append(cache)
        return self.ln_f(x), new_caches


class GPTForPretraining(nn.Layer):
    """LM head tied to word embeddings (ref weight-tying convention)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.cfg = cfg

    def forward(self, input_ids, position_ids=None):
        out = self.gpt(input_ids, position_ids)
        hidden, aux = out if isinstance(out, tuple) else (out, None)
        w = self.gpt.embeddings.word_embeddings.weight
        from ..ops.math import matmul
        logits = matmul(hidden, w, transpose_y=True)
        if aux is not None:
            # ride the exact Tensor handed to the loss fn — per-call, no
            # global state, safe across interleaved models/forwards
            logits._moe_aux_loss = aux
        if _use_fused_head(self.cfg, logits.shape):
            # hand the loss fn the pre-head pieces: gpt_pretrain_loss uses
            # the vocab-chunked fused CE and never touches `logits`, so
            # under jit the dense head matmul above is dead code (users who
            # consume logits directly still get them). The ARRAY snapshot
            # of w matters: functional_call restores Parameter._data on
            # exit, and the loss fn runs after — holding only the Tensor
            # would silently swap the traced weight for a constant and
            # drop the head's gradient into the tied embedding.
            logits._fused_head = (hidden, w, w._data)
        return logits

    def loss(self, logits, labels):
        return gpt_pretrain_loss(logits, labels)

    def init_cache(self, batch, max_len, dtype=jnp.float32):
        return self.gpt.init_cache(batch, max_len, dtype)

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32, num_slots=1):
        return self.gpt.init_paged_cache(num_blocks, block_size, max_len,
                                         dtype)

    def paged_layout(self):
        """K/V pages, every layer alike (nn/paged_layout.py)."""
        cfg = self.cfg
        return PagedLayout(groups=(AttentionGroup(
            cfg.num_layers, "kv", cfg.num_heads, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, window=cfg.attn_window),))

    def _logits(self, h, frontier=None):
        """Tied-head logits of h [B, S, H]; frontier (traced index): of
        that one position only, [B, 1, V] — the serving engines want ONE
        next-token row, and indexing before the head keeps the vocab
        matmul [1, V] instead of [S, V]."""
        if frontier is not None:
            hr = h._data if isinstance(h, Tensor) else h
            h = Tensor(jax.lax.dynamic_slice_in_dim(hr, frontier, 1,
                                                    axis=1))
        w = self.gpt.embeddings.word_embeddings.weight
        from ..ops.math import matmul
        return matmul(h, w, transpose_y=True)

    def decode_step(self, tok, caches, pos, block_tables=None, active=None):
        """One token a row: tok [B, 1] at pos (a scalar, or [B]). On the
        dense caches; given block_tables [B, nblk] the caches are block
        POOLS and the step is a chunk of one (the paged decode wave).
        The one place that chooses between the two; `active` unused."""
        if block_tables is not None:
            return self.prefill_chunk(tok, caches, block_tables, pos, None)
        h, caches = self.gpt.decode_step(tok, caches, pos)
        return self._logits(h), caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier=None, slot=None):
        """[B, C] ids a lane against the block pools, at absolute
        positions chunk_start + arange(C) (chunk_start and valid_len:
        scalars or [B]): a prompt chunk of one lane, or every lane's
        k + 1 drafted tokens in the speculative verify wave, whose
        logits for ALL C positions ([S, C, V]) are the cost that program
        pays on purpose. frontier: see _logits; only the final chunk's
        frontier is consumed by the serving engine. `slot` unused."""
        h, caches = self.gpt.paged_step(tok_chunk, caches, block_tables,
                                        chunk_start, valid_len)
        return self._logits(h, frontier), caches

    def prefill(self, input_ids, max_len, dtype=jnp.float32,
                frontier=None):
        h, caches = self.gpt.prefill(input_ids, max_len, dtype)
        return self._logits(h, frontier), caches


# auto threshold for fused_head_loss=None: chunk once the f32 logits
# would exceed this (tests patch it to exercise both sides cheaply)
CHUNKED_CE_AUTO_BYTES = 2 << 30


def _use_fused_head(cfg, logits_shape):
    if cfg.fused_head_loss is not None:
        return cfg.fused_head_loss
    import numpy as _np
    if not all(isinstance(d, (int, _np.integer)) for d in logits_shape):
        # symbolic dims (shape-polymorphic jit.save export) have no
        # concrete size: keep the dense head — exported forwards serve
        # logits, they don't pair with the training-only fused loss
        return False
    b, s, v = (int(d) for d in logits_shape)
    return b * s * v * 4 > CHUNKED_CE_AUTO_BYTES


def gpt_pretrain_loss(logits, labels):
    """Next-token CE. Shift the LABELS (cheap int32 op) instead of slicing
    the logits: logits[:, :-1] yields a 1023-row tensor that breaks the
    TPU (8,128) tiling and costs a full relayout copy of the [B,S,V]
    logits (~512MB at the bench config, visible as reshape+fusion ops in
    the device trace); the last position is masked via ignore_index.

    When the model attached `_fused_head` (cfg.fused_head_loss), the loss
    is computed by the vocab-chunked fused head+CE (ops/chunked_ce.py)
    from the pre-head hidden states — the wide logits are never read, so
    XLA removes the dense head matmul entirely."""
    b, s, v = logits.shape
    from ..ops.manipulation import concat
    from ..ops.creation import full
    ign = full([b, 1], -1, dtype="int64")
    shifted = concat([labels[:, 1:].astype("int64"), ign], axis=1)
    fused = getattr(logits, "_fused_head", None)
    if fused is not None:
        import jax as _jax
        from ..ops.dispatch import apply
        from ..ops.chunked_ce import chunked_lm_loss
        hidden, w_t, w_arr = fused
        # traced: use the array snapshot — the Tensor's _data was restored
        # to the pre-trace constant when functional_call exited, and using
        # it would silently drop the head's grad into the tied embedding.
        # Eager: use the Tensor so the tape links w.grad.
        w_in = w_arr if isinstance(w_arr, _jax.core.Tracer) else w_t
        h2 = hidden.reshape([b * s, hidden.shape[-1]])
        lab = shifted.reshape([b * s])
        # small vocabs: chunk to the (128-aligned) vocab, not 4096 — padding
        # a 512-wide vocab to 4096 would 8x the head FLOPs
        chunk = min(4096, ((v + 127) // 128) * 128)

        def f(h_, w_, l_):
            return chunked_lm_loss(h_, w_, l_, -1, chunk)

        loss = apply(f, (h2, w_in, lab), name="chunked_lm_loss")
    else:
        loss = F.cross_entropy(logits.reshape([b * s, v]),
                               shifted.reshape([b * s]), ignore_index=-1)
    # MoE load-balance aux rides the logits Tensor (GPTForPretraining
    # attaches it); same-trace under TrainStep, concrete eagerly
    aux = getattr(logits, "_moe_aux_loss", None)
    if aux is not None:
        loss = loss + aux
    return loss


_GEN_CACHE_MAX = 8     # distinct (shape, knob) programs kept per model


def _gen_program_cache(model):
    """Per-model cache of traced generate programs: generate() used to
    build a fresh @jax.jit closure per call, so every call re-traced the
    whole model (seconds on a 1-core host) even when the XLA executable
    was disk-cached. The dict lives ON the model instance (the jitted
    closures capture the model, so a global weak map would never
    collect); model -> cache -> closure -> model is a plain cycle the
    gc reclaims when the model is dropped. Insertion-ordered, bounded:
    variable-shape serving loops evict oldest instead of accumulating
    one executable per (B, L, prompt_len) forever."""
    cache = getattr(model, "_pt_gen_programs", None)
    if cache is None:
        cache = {}
        # bypass Layer.__setattr__ (it interns sublayers/params)
        object.__setattr__(model, "_pt_gen_programs", cache)
    return cache


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
                 top_k=0, top_p=1.0, temperature=1.0, eos_token_id=None,
                 seed=None, use_cache=False):
    """Autoregressive decode for any causal LM exposing forward(ids) ->
    logits and (for use_cache=True) init_cache/decode_step — GPT and
    LLaMA both do (ref paddlenlp generation_utils.generate: greedy +
    top-k/top-p sampling).

    TPU-native: ONE jitted lax.fori_loop over a fixed [B, Lmax] buffer.
    use_cache=False recomputes the (causal) forward over the whole buffer
    per step and reads the frontier logits — exact, zero dynamic shapes,
    right for short decodes. use_cache=True runs the incremental KV-cache
    path (GPTModel.decode_step): O(T) attention per token against
    [B, heads, Lmax, head_dim] caches, the long-decode configuration;
    the prompt is consumed through the same single-token loop (prefill
    positions teacher-force from the buffer), so both paths are one
    compiled program.

    Returns ids [B, prompt_len + max_new_tokens] (prompt included), padded
    with eos after finish when eos_token_id is given.
    """
    import numpy as np
    from ..framework import state as _state
    from ..framework.tensor import Tensor as _T
    from ..nn.decode import top_k_top_p_filtering

    ids = input_ids._data if isinstance(input_ids, _T) else jnp.asarray(
        np.asarray(input_ids))
    ids = ids.astype(jnp.int32)
    B, prompt_len = ids.shape
    L = prompt_len + int(max_new_tokens)
    eos = -1 if eos_token_id is None else int(eos_token_id)

    was_training = model.training
    model.eval()            # generation is inference: dropout must be off
    params, buffers = model.functional_state()

    def logits_at(p, b, buf, t):
        out, _ = model.functional_call(p, b, _T(buf))
        lo = out._data if isinstance(out, _T) else out
        # frontier logits: position t-1 predicts token t
        return jax.lax.dynamic_index_in_dim(lo, t - 1, axis=1,
                                            keepdims=False)

    def make_step(p, b):
        def step(t, carry):
            buf, finished, key = carry
            lo = logits_at(p, b, buf, t).astype(jnp.float32)
            if temperature and temperature != 1.0:
                lo = lo / temperature
            if do_sample:
                lo = top_k_top_p_filtering(_T(lo), top_k=top_k,
                                           top_p=top_p)._data
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, lo,
                                             axis=-1).astype(jnp.int32)
            else:
                tok = jnp.argmax(lo, axis=-1).astype(jnp.int32)
            tok = jnp.where(finished, jnp.int32(max(eos, 0)), tok)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, tok[:, None], t, axis=1)
            if eos_token_id is not None:
                finished = finished | (tok == eos)
            return buf, finished, key
        return step

    buf0 = jnp.zeros((B, L), jnp.int32)
    buf0 = jax.lax.dynamic_update_slice_in_dim(buf0, ids, 0, axis=1)
    key0 = (jax.random.PRNGKey(seed) if seed is not None
            else _state.next_rng_key())

    spec = (B, L, prompt_len, bool(use_cache), bool(do_sample),
            int(top_k), float(top_p), float(temperature), eos)
    programs = _gen_program_cache(model)

    if not use_cache:
        if spec not in programs:
            @jax.jit
            def run(p, b, buf, key):
                # params enter as jit ARGUMENTS (not baked constants), so
                # repeated generate() calls after training reuse the program
                finished = jnp.zeros((B,), bool)
                buf, _, _ = jax.lax.fori_loop(prompt_len, L,
                                              make_step(p, b),
                                              (buf, finished, key))
                return buf
            programs[spec] = run
            while len(programs) > _GEN_CACHE_MAX:
                programs.pop(next(iter(programs)))

        try:
            return _T(programs[spec](params, buffers, buf0, key0))
        finally:
            if was_training:
                model.train()

    # ---------------- KV-cache path
    def make_cached_step(p, b):
        def step(t, carry):
            buf, caches, finished, key = carry
            tok_t = jax.lax.dynamic_slice_in_dim(buf, t, 1, axis=1)
            logits, caches = _functional_decode_step(model, p, b, tok_t,
                                                     caches, t)
            lo = logits[:, 0, :].astype(jnp.float32)
            if temperature and temperature != 1.0:
                lo = lo / temperature
            if do_sample:
                lo = top_k_top_p_filtering(_T(lo), top_k=top_k,
                                           top_p=top_p)._data
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, lo,
                                             axis=-1).astype(jnp.int32)
            else:
                tok = jnp.argmax(lo, axis=-1).astype(jnp.int32)
            # prefill positions teacher-force the known next token
            nxt = jnp.where(t + 1 < prompt_len, buf[:, (t + 1) % L], tok)
            nxt = jnp.where(finished, jnp.int32(max(eos, 0)), nxt)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, nxt[:, None], jnp.minimum(t + 1, L - 1), axis=1)
            if eos_token_id is not None:
                finished = finished | ((t + 1 >= prompt_len) & (nxt == eos))
            return buf, caches, finished, key
        return step

    def _functional_decode_step(model, p, b, tok, caches, pos):
        out, _ = model.functional_call(
            p, b, _T(tok), caches, pos, method="decode_step")
        logits, new_caches = out
        return (logits._data if isinstance(logits, _T) else logits,
                new_caches)

    if spec not in programs:
        def _cache_dtype(p):
            # KV caches in the model's compute dtype: a bf16 model
            # decoding with f32 caches doubles the per-token HBM stream
            # (the decode einsum upcasts scores to f32 either way);
            # measured 2x decode tok/s on gpt2s b=8, which reads the
            # full [B,H,L,D] cache pair every token. Decided from the
            # TRACED params at trace time — jit retraces when param
            # dtypes change, so model.to(...) after a cached generate
            # cannot leave a stale dtype baked in — and by element-count
            # majority, so a model with only a bf16 embedding table
            # keeps f32 caches for its f32 attention compute.
            counts = {}
            for leaf in jax.tree_util.tree_leaves(p):
                dt = leaf.dtype
                if dt in (jnp.bfloat16, jnp.float16, jnp.float32):
                    counts[dt] = counts.get(dt, 0) + int(np.prod(leaf.shape))
            low = {d: c for d, c in counts.items() if d != jnp.float32}
            if low and sum(low.values()) > counts.get(jnp.float32, 0):
                return max(low, key=low.get)
            return jnp.float32

        @jax.jit
        def run_cached(p, b, buf, key):
            caches = model.init_cache(B, L, dtype=_cache_dtype(p))
            finished = jnp.zeros((B,), bool)
            buf, _, _, _ = jax.lax.fori_loop(
                0, L - 1, make_cached_step(p, b),
                (buf, caches, finished, key))
            return buf
        programs[spec] = run_cached
        while len(programs) > _GEN_CACHE_MAX:
            programs.pop(next(iter(programs)))

    try:
        return _T(programs[spec](params, buffers, buf0, key0))
    finally:
        if was_training:
            model.train()


gpt_generate = generate      # back-compat name
