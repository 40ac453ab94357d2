"""DeepSeek-V3-family decoder LM (`model_type: deepseek_v3`): multi-head
latent attention (MLA), `first_k_dense_replace` leading SwiGLU layers,
then routed-expert layers of gated experts with a gated shared expert.
Every block is pre-norm residual: `h + attn(RMSNorm(h))`, then
`h + mlp(RMSNorm(h))`.

MLA. `q = x W_q` (or, with `q_lora_rank`, `RMSNorm(x W_qa) W_qb`), a
head's 192 values `[q_nope 128 | q_rope 64]`;
`a = x W_kv_a` (rank + rope wide): `c = RMSNorm(a[:rank])`, `k_rope =
rope(a[rank:])`, one rotary key shared by every head; a head's
`[k_nope | v] = c W_kv_b`; scores `(q_nope . k_nope + q_rope . k_rope)
/ sqrt(nope + rope)`, causal softmax, `o = P v`, `y = o W_o`. Rotary
pairs are interleaved, (x[2i], x[2i+1]), as the source stores them
(`rope_interleave`); `nlp/llama.py` rotates them. `rope_scaling` of
type `yarn` stretches the table as the published DeepSeek-V3 modelling
code does (`yarn_frequencies`) and corrects the softmax scale.

Hyper-connections (`hc_mult` n > 1; mHC, arXiv:2512.24880): a token's
residual state is n streams, `[n, B, L, hidden]`, all equal to the
embedding row at the bottom and summed before the final norm. Every
sub-layer F (attention, MLP) is wrapped alike: `xt = RMSNorm(vec(x))`
over the n x hidden values with no learned scale, `[p | q | r] = xt
Phi` (Phi stored [2n + n^2, n hidden]: out, in); `H_pre = sigmoid(a_pre
p + b_pre)` [n], `H_post = 2 sigmoid(a_post q + b_post)` [n], `H_res`
[n, n] = `exp(clip(a_res r + b_res))` made doubly stochastic by
`hc_sinkhorn_iters` Sinkhorn-Knopp iterations (rows, then columns, each
sum + `hc_eps`); `u = sum_j H_pre[j] x[j]`, `y = F(RMSNorm_F(u))`,
`x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y`. The maps are float32;
the streams are stored in the parameters' dtype. With `hc_mult` 1 there
are no maps and the stack is the plain residual one.

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
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..framework.tensor import Tensor
from ..nn import initializer as I
from .llama import (LlamaMLP, _rms_norm_raw, _rotate_pairs,
                    apply_rope_bshd, apply_rope_positions, rope_tables)
from .moe import ParamBlock, RoutedExperts

_HIGHEST = jax.lax.Precision.HIGHEST


class DeepseekV3Config:
    """Sizes under the names of the published `config.json`.
    `param_dtype` is the dtype parameters are created in;
    `init_weights=False` creates the matrices as zeros for a caller that
    installs its own (drawing billions of values on the host takes
    minutes). `rope_scaling` is the published group (`type`, `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `mscale`, `mscale_all_dim`). What the program does not compute is
    refused by name: grouped routing (`n_group`, `topk_group` over 1), a
    `rope_scaling.type` other than `yarn`, `hc_mult` below 1."""

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
                 hc_mult=1, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 param_dtype="float32", init_weights=True):
        scaling = dict(rope_scaling or {})
        for name, value, only in (
                ("n_group", n_group, 1), ("topk_group", topk_group, 1),
                ("rope_scaling.type",
                 scaling.get("type") if scaling else "yarn", "yarn")):
            if value != only:
                raise ValueError(f"{name}={value!r} is not computed by "
                                 f"this model (only {only!r})")
        if int(hc_mult) < 1:
            raise ValueError(f"hc_mult={hc_mult!r}: a token has at least "
                             "one residual stream")
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
        self.q_lora_rank = int(q_lora_rank) if q_lora_rank else None
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
        self.rope_scaling = scaling or None
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.mhc_h_res_clamp_min = float(mhc_h_res_clamp_min)
        self.mhc_h_res_clamp_max = float(mhc_h_res_clamp_max)
        self.max_position_embeddings = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.param_dtype = jnp.dtype(param_dtype).name
        self.init_weights = bool(init_weights)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


# ------------------------------------------------------ YaRN positions
def yarn_frequencies(dim, theta, scaling):
    """(inverse frequencies [dim / 2] float64, the factor on cos and
    sin, the factor on the softmax scale) of a YaRN-scaled rotary table,
    as the published DeepSeek-V3 modelling code computes them: a pair
    that turns more than `beta_fast` times over the original length keeps
    its frequency, one that turns less than `beta_slow` times has it
    divided by `factor`, a linear ramp between; `m(a) = 0.1 a ln(factor)
    + 1` gives cos and sin the factor `m(mscale) / m(mscale_all_dim)` and
    the scores `m(mscale_all_dim)^2`."""
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])
    f = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(rotations):        # the pair that turns so many times
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turns_at(float(scaling.get("beta_slow", 1)))),
               dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)

    def m(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 and a else 1.0

    m_all = m(float(scaling.get("mscale_all_dim", 0)))
    return (f / factor * ramp + f * (1 - ramp),
            m(float(scaling.get("mscale", 1))) / m_all, m_all * m_all)


#: a position is `hi * _SPLIT + lo`
_SPLIT = 512


@functools.lru_cache(maxsize=8)
def _split_tables(inv_freq, positions, mult):
    """cos and sin of p x `inv_freq` for p < `positions` as two small
    float32 tables each (of `hi * _SPLIT` and of `lo`, joined by the
    angle-addition formulas in `_split_rows`), computed in float64: one
    table of 262,144 rows would be 67 MB of constants in every serving
    program. `mult` multiplies cos and sin."""
    inv = np.asarray(inv_freq, np.float64)
    hi = np.outer(np.arange(-(-positions // _SPLIT)) * _SPLIT, inv)
    lo = np.outer(np.arange(_SPLIT), inv)
    return tuple(jnp.asarray(t, jnp.float32)
                 for t in (np.cos(hi) * mult, np.sin(hi) * mult,
                           np.cos(lo), np.sin(lo)))


def _split_rows(tables, positions):
    """(cos, sin) rows [..., D/2] at traced positions; a position past
    the tables (a padded chunk's tail) reads their last."""
    cos_hi, sin_hi, cos_lo, sin_lo = tables
    positions = jnp.minimum(positions, cos_hi.shape[0] * _SPLIT - 1)
    hi, lo = positions // _SPLIT, positions % _SPLIT
    return (cos_hi[hi] * cos_lo[lo] - sin_hi[hi] * sin_lo[lo],
            sin_hi[hi] * cos_lo[lo] + cos_hi[hi] * sin_lo[lo])


@functools.lru_cache(maxsize=8)
def _sequence_tables(inv_freq, length, mult):
    """cos and sin [length, D/2] of positions 0..length-1 (the sequence
    forward's, whose length is static), as numpy arrays: the call may
    come from inside a trace, where a cached `jnp` value would be a
    leaked tracer."""
    ang = np.outer(np.arange(length), np.asarray(inv_freq, np.float64))
    return ((np.cos(ang) * mult).astype(np.float32),
            (np.sin(ang) * mult).astype(np.float32))


class MLAttention(ParamBlock):
    """Multi-head latent attention (the module's docstring)."""

    def __init__(self, cfg):
        super().__init__(cfg)
        h, self.nh = cfg.hidden_size, cfg.num_attention_heads
        self.rank, self.nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        self.rope, self.vd = cfg.qk_rope_head_dim, cfg.v_head_dim
        self.scale = 1.0 / math.sqrt(self.nope + self.rope)
        self.q_rank = cfg.q_lora_rank
        if self.q_rank:
            self.q_a_proj = self._matrix(h, self.q_rank)
            self.q_a_norm_weight = self._vector(self.q_rank, 1.0)
            self.q_b_proj = self._matrix(
                self.q_rank, self.nh * (self.nope + self.rope))
        else:
            self.q_proj = self._matrix(h, self.nh * (self.nope + self.rope))
        self.kv_a_proj = self._matrix(h, self.rank + self.rope)
        self.kv_a_norm_weight = self._vector(self.rank, 1.0)
        self.kv_b_proj = self._matrix(self.rank,
                                      self.nh * (self.nope + self.vd))
        self.o_proj = self._matrix(self.nh * self.vd, h)
        self._yarn = None
        if cfg.rope_scaling:
            inv, mult, on_scores = yarn_frequencies(
                self.rope, cfg.rope_theta, cfg.rope_scaling)
            self._yarn = (tuple(inv), float(mult))
            self._yarn_tables = _split_tables(
                self._yarn[0], cfg.max_position_embeddings, self._yarn[1])
            self.scale *= on_scores
        else:
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
        if self.q_rank:
            with jax.named_scope("mla_q_lora"):
                q = _rms_norm_raw(x @ self.q_a_proj._data,
                                  self.q_a_norm_weight._data,
                                  self.cfg.rms_norm_eps) @ self.q_b_proj._data
        else:
            q = x @ self.q_proj._data
        q = q.reshape(b, length, self.nh, -1)
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
        cos, sin = (_sequence_tables(self._yarn[0], length, self._yarn[1])
                    if self._yarn else (self._cos, self._sin))
        q = jnp.concatenate(
            [q[..., :self.nope],
             apply_rope_bshd(q[..., self.nope:], cos, sin)],
            axis=-1)
        k_rope = apply_rope_bshd(k_rope[:, :, None], cos, sin)
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
        if self._yarn:
            cos, sin = (t[:, None] for t in _split_rows(
                self._yarn_tables, positions))

            def rotate(t):
                return _rotate_pairs(t, cos, sin)
        else:
            def rotate(t):
                return apply_rope_positions(t, self._cos, self._sin,
                                            positions)
        q_rope = rotate(q[..., self.nope:])
        k_rope = rotate(k_rope[:, None])[:, 0]
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


class HyperConnection(ParamBlock):
    """The maps of one sub-layer of a stack with `hc_mult` n > 1 residual
    streams, and the two mixes they drive (the module's docstring).
    Streams are `[n, B, L, hidden]`: a stream is a slice of the leading
    axis, laid out as the one stream of a plain stack is."""

    def __init__(self, cfg):
        super().__init__(cfg)
        n = self.n = cfg.hc_mult
        # stored [out, in], as the experts' `up` is: 24 columns would be
        # padded to a tile of 128 and transposed on the way to the MXU
        self.phi = self._matrix(2 * n + n * n, n * cfg.hidden_size)
        self.pre_scale = self._vector(1, 1.0)
        self.pre_bias = self._vector(n, 0.0)
        self.post_scale = self._vector(1, 1.0)
        self.post_bias = self._vector(n, 0.0)
        self.res_scale = self._vector(1, 1.0)
        self.res_offset = self._matrix(n, n)

    def sinkhorn(self, r):
        """r [n, n, T] float32 -> exp(clip(r)) made doubly stochastic by
        `hc_sinkhorn_iters` iterations, rows then columns. The matrix is
        held as its n x n entries, each a [T] vector, the sums are
        written out as adds of entries and the loop is unrolled: every
        operation is elementwise on [T] and no value depends on another
        token's. A v5e's compiler makes about one fusion an iteration of
        that; whole [n, n, T] arrays with sliced sums gave four, and a
        reduction or a `fori_loop` an iteration more still (PERF.md,
        PR 39). One launch for the lot needs a kernel."""
        cfg, n = self.cfg, self.n
        r = jnp.exp(jnp.clip(r, cfg.mhc_h_res_clamp_min,
                             cfg.mhc_h_res_clamp_max))
        m = [[r[i, j] for j in range(n)] for i in range(n)]
        for _ in range(cfg.hc_sinkhorn_iters):
            for i in range(n):
                total = sum(m[i]) + cfg.hc_eps
                m[i] = [v / total for v in m[i]]
            for j in range(n):
                total = sum(m[i][j] for i in range(n)) + cfg.hc_eps
                for i in range(n):
                    m[i][j] = m[i][j] / total
        return jnp.stack([jnp.stack(row) for row in m])

    def maps(self, x):
        """x [n, B, L, hidden] -> float32 H_pre [n, T], H_post [n, T],
        H_res [n, n, T], T = B L tokens. `RMSNorm(vec(x)) Phi` is
        computed as `(vec(x) Phi) / rms`: the products of stored values
        are exact in float32 either way."""
        n, hidden = self.n, x.shape[-1]
        xs = x.reshape(n, -1, hidden)
        phi = self.phi._data
        raw = sum(jax.lax.dot_general(
            xs[j], phi[:, j * hidden:(j + 1) * hidden],
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
            for j in range(n))                                  # [T, m]
        xf = xs.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(0, 2))
                            + self.cfg.rms_norm_eps)
        pqr = (raw * rms[:, None]).T                            # [m, T]

        def f32(p):
            return p._data.astype(jnp.float32)

        h_pre = jax.nn.sigmoid(f32(self.pre_scale) * pqr[:n]
                               + f32(self.pre_bias)[:, None])
        h_post = 2.0 * jax.nn.sigmoid(f32(self.post_scale) * pqr[n:2 * n]
                                      + f32(self.post_bias)[:, None])
        h_res = self.sinkhorn(
            f32(self.res_scale) * pqr[2 * n:].reshape(n, n, -1)
            + f32(self.res_offset)[:, :, None])
        return h_pre, h_post, h_res

    def forward(self, x, f):
        """x [n, B, L, hidden], f([B, L, hidden]) -> [B, L, hidden]:
        the streams after the sub-layer f."""
        n, lead = self.n, (*x.shape[1:-1], 1)
        with jax.named_scope("mhc_map"):
            h_pre, h_post, h_res = self.maps(x)
        with jax.named_scope("mhc_mix"):
            xf = x.astype(jnp.float32)
            u = sum(h_pre[j].reshape(lead) * xf[j]
                    for j in range(n)).astype(x.dtype)
        y = f(u)
        with jax.named_scope("mhc_mix"):
            yf = y.astype(jnp.float32)
            return jnp.stack([
                sum(h_res[i, j].reshape(lead) * xf[j] for j in range(n))
                + h_post[i].reshape(lead) * yf
                for i in range(n)]).astype(x.dtype)


class DeepseekV3Block(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        self.dense = index < cfg.first_k_dense_replace
        self.hc_attn = self.hc_mlp = None
        if cfg.hc_mult > 1:
            self.hc_attn, self.hc_mlp = (HyperConnection(cfg),
                                         HyperConnection(cfg))

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
        """x [B, L, hidden] raw, or [n, B, L, hidden] with `hc_mult` n
        streams; `attend(attention layer, its cache, normed x)` ->
        (attention output, new cache). Returns (x, the new cache)."""
        def attn(u):
            nonlocal cache
            a, cache = attend(self.self_attn, cache, _rms_norm_raw(
                u, self.input_norm_weight._data, self.eps))
            return a

        def mlp(u):
            y = _rms_norm_raw(u, self.post_norm_weight._data, self.eps)
            return (_raw(self.mlp(Tensor(y))) if self.dense
                    else self.mlp(y))

        if self.hc_attn is None:
            x = x + attn(x)
            return x + mlp(x), cache
        x = self.hc_attn(x, attn)
        return self.hc_mlp(x, mlp), cache


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
        #: sub-layers whose maps a token's streams go through on its way
        #: down the stack (0 with one stream: there are no maps)
        self.mhc_mixes_per_token = (2 * cfg.num_layers if cfg.hc_mult > 1
                                    else 0)

    def _run(self, ids, caches, attend):
        """The residual stack over ids [B, L]; `attend(attention layer,
        its cache, normed x)` -> (output, new cache). Returns (final
        hidden, caches)."""
        x = self.embeddings._data[_raw(ids)]
        streams = self.cfg.hc_mult
        if streams > 1:
            x = jnp.broadcast_to(x, (streams, *x.shape))
        caches = list(caches)
        for i, blk in enumerate(self.layers):
            x, caches[i] = blk.run(x, caches[i], attend)
        dtype = x.dtype
        if streams > 1:
            x = x.astype(jnp.float32).sum(axis=0)
        return _rms_norm_raw(x, self.norm_weight._data,
                             self.cfg.rms_norm_eps).astype(dtype), caches

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
