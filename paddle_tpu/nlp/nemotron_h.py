"""Nemotron-H hybrid decoder LM: one stack whose blocks are chosen by a
pattern string — `M` a Mamba-2 mixer, `E` a routed expert layer with a
shared expert, `*` grouped-query attention (no rotary embedding). Every
block is `h + mixer(RMSNorm(h))`.

One cache interface a mixer kind: attention keeps K/V in the paged pool
(block tables, `nn/paged_attention.py`); a Mamba mixer keeps one fixed
record a serving slot (`ssm` [slots, heads, head_dim, state] float32 and
the last `conv_kernel - 1` conv inputs); an expert layer keeps nothing.
`slot_state` tells the paged engine that such records exist, so it hands
`prefill_chunk` the slot and `decode_step` the lanes that decode.

State and router arithmetic are float32 (the state, dt, the decay, the
sigmoid scores); everything else runs in the parameters' dtype.
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn import initializer as I
from .llama import _gqa_flash_bshd, _rms_norm_raw
from .moe import ParamBlock as _Block, RoutedExperts, route  # noqa: F401

_HIGHEST = jax.lax.Precision.HIGHEST


class NemotronHConfig:
    """Sizes under the names of the published `config.json`
    (`model_type: nemotron_h`). `param_dtype` is the dtype parameters are
    created in; `init_weights=False` creates them as zeros for a caller
    that installs its own (drawing billions of values on the host takes
    minutes)."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern="MEMEM*EME",
                 num_attention_heads=32, num_key_value_heads=2,
                 head_dim=128, mamba_num_heads=64, mamba_head_dim=64,
                 n_groups=8, ssm_state_size=128, conv_kernel=4,
                 chunk_size=128, n_routed_experts=128,
                 num_experts_per_tok=6, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
                 initializer_range=0.02, num_hidden_layers=None,
                 param_dtype="float32", init_weights=True):
        bad = set(hybrid_override_pattern) - set("ME*")
        if bad or not hybrid_override_pattern:
            raise ValueError("hybrid_override_pattern is a string of M, E "
                             f"and *, got {hybrid_override_pattern!r}")
        if num_hidden_layers not in (None, len(hybrid_override_pattern)):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} is not the length "
                f"of hybrid_override_pattern {hybrid_override_pattern!r}")
        if num_attention_heads % num_key_value_heads:
            raise ValueError(f"num_attention_heads {num_attention_heads} "
                             "not divisible by num_key_value_heads "
                             f"{num_key_value_heads}")
        if mamba_num_heads % n_groups:
            raise ValueError(f"mamba_num_heads {mamba_num_heads} not "
                             f"divisible by n_groups {n_groups}")
        if num_experts_per_tok > n_routed_experts:
            raise ValueError(f"num_experts_per_tok {num_experts_per_tok} > "
                             f"n_routed_experts {n_routed_experts}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.hybrid_override_pattern = hybrid_override_pattern
        self.num_layers = len(hybrid_override_pattern)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.mamba_num_heads = int(mamba_num_heads)
        self.mamba_head_dim = int(mamba_head_dim)
        self.n_groups = int(n_groups)
        self.ssm_state_size = int(ssm_state_size)
        self.conv_kernel = int(conv_kernel)
        self.chunk_size = int(chunk_size)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.moe_shared_expert_intermediate_size = int(
            moe_shared_expert_intermediate_size)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.layer_norm_epsilon = float(layer_norm_epsilon)
        self.initializer_range = float(initializer_range)
        self.param_dtype = jnp.dtype(param_dtype).name
        self.init_weights = bool(init_weights)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


# ------------------------------------------------------------------ Mamba-2
def ssd_chunk(x, dt, a, b, c, s0):
    """One chunk of the Mamba-2 scan, all float32.

    x [B, L, H, P], dt [B, L, H] (0 where a position is padding: the
    state then passes it untouched), a [H] (negative), b and c
    [B, L, G, N] shared by the H // G heads of a group, s0 [B, H, P, N].
    Per head S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T and y_t = S_t c_t,
    computed as the chunk's own lower-triangular part plus what the
    carried state contributes. Returns (y [B, L, H, P], S_L)."""
    rep = x.shape[2] // b.shape[2]
    cum = jnp.cumsum(dt * a, axis=1)                        # [B, L, H]
    length = x.shape[1]
    causal = jnp.tril(jnp.ones((length, length), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]           # [B, l, s, H]
    decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("blgn,bsgn->blsg", c, b, precision=_HIGHEST)
    w = decay * jnp.repeat(cb, rep, axis=3) * dt[:, None, :, :]
    y = jnp.einsum("blsh,bshp->blhp", w, x, precision=_HIGHEST)
    ch = jnp.repeat(c, rep, axis=2) * jnp.exp(cum)[..., None]
    y = y + jnp.einsum("blhn,bhpn->blhp", ch, s0, precision=_HIGHEST)
    tail = jnp.exp(cum[:, -1:, :] - cum) * dt               # [B, L, H]
    s1 = (s0 * jnp.exp(cum[:, -1])[:, :, None, None]
          + jnp.einsum("blh,blhp,blhn->bhpn", tail, x,
                       jnp.repeat(b, rep, axis=2), precision=_HIGHEST))
    return y, s1


class Mamba2Mixer(_Block):
    def __init__(self, cfg):
        super().__init__(cfg)
        h = cfg.hidden_size
        self.heads, self.hd = cfg.mamba_num_heads, cfg.mamba_head_dim
        self.groups, self.state = cfg.n_groups, cfg.ssm_state_size
        self.d_inner = self.heads * self.hd
        self.conv_dim = self.d_inner + 2 * self.groups * self.state
        self.in_proj = self._matrix(
            h, self.d_inner + self.conv_dim + self.heads)
        self.conv_weight = self._matrix(cfg.conv_kernel, self.conv_dim)
        self.conv_bias = self._vector(self.conv_dim, 0.0)
        self.dt_bias = self._vector(self.heads, 0.0)
        self.A_log = self._vector(self.heads, 1.0)
        self.D = self._vector(self.heads, 1.0)
        self.norm_weight = self._vector(self.d_inner, 1.0)
        self.out_proj = self._matrix(self.d_inner, h)

    def init_state(self, num_slots, dtype):
        return {"ssm": jnp.zeros((num_slots, self.heads, self.hd,
                                  self.state), jnp.float32),
                "conv": jnp.zeros((num_slots, self.cfg.conv_kernel - 1,
                                   self.conv_dim), dtype)}

    def _project(self, x):
        """x [B, L, hidden] -> z [B, L, d_inner], xBC before the conv
        [B, L, conv_dim], dt before its bias [B, L, H] float32."""
        zxd = x @ self.in_proj._data
        z, xbc, dt = jnp.split(
            zxd, [self.d_inner, self.d_inner + self.conv_dim], axis=-1)
        return z, xbc, dt.astype(jnp.float32)

    def _conv(self, taps, xbc):
        """Depthwise causal conv over [taps; xbc] and silu: output t sees
        inputs t-3 .. t. taps [B, K-1, C], xbc [B, L, C]."""
        k = self.cfg.conv_kernel
        xp = jnp.concatenate([taps.astype(xbc.dtype), xbc], axis=1)
        w = self.conv_weight._data.astype(jnp.float32)
        length = xbc.shape[1]
        out = self.conv_bias._data.astype(jnp.float32)
        for j in range(k):
            out = out + w[j] * xp[:, j:j + length].astype(jnp.float32)
        return jax.nn.silu(out).astype(xbc.dtype), xp

    def _split(self, xbc):
        """Conv output -> x [.., H, P], b and c [.., G, N], float32."""
        lead = xbc.shape[:-1]
        gn = self.groups * self.state
        x, b, c = jnp.split(xbc.astype(jnp.float32),
                            [self.d_inner, self.d_inner + gn], axis=-1)
        return (x.reshape(*lead, self.heads, self.hd),
                b.reshape(*lead, self.groups, self.state),
                c.reshape(*lead, self.groups, self.state))

    def _dt_a(self, dt):
        dt = jax.nn.softplus(dt + self.dt_bias._data.astype(jnp.float32))
        return dt, -jnp.exp(self.A_log._data.astype(jnp.float32))

    def _gate_out(self, y, x, z):
        """y, x [.., H, P] float32, z [.., d_inner]: the D skip, the gate
        (before the norm), RMSNorm over each group's channels, out_proj."""
        lead = z.shape[:-1]
        y = y + self.D._data.astype(jnp.float32)[:, None] * x
        y = y.reshape(*lead, self.d_inner) * jax.nn.silu(
            z.astype(jnp.float32))
        g = y.reshape(*lead, self.groups, self.d_inner // self.groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + self.cfg.layer_norm_epsilon)
        y = (g.reshape(*lead, self.d_inner)
             * self.norm_weight._data.astype(jnp.float32))
        return y.astype(z.dtype) @ self.out_proj._data

    def scan(self, x, ssm, conv, valid_len=None):
        """The chunked scan over x [B, L, hidden] from the carried state
        (ssm [B, H, P, N] float32, conv [B, K-1, C]). Positions at or
        past `valid_len` leave both untouched. Returns (out, ssm, conv)."""
        length, chunk = x.shape[1], self.cfg.chunk_size
        valid = length if valid_len is None else valid_len
        with jax.named_scope("ssm_scan"):
            z, xbc, dt = self._project(x)
            act, xp = self._conv(conv, xbc)
            # the taps a later call starts from: the K-1 inputs that end
            # at the last valid position
            conv = jax.lax.dynamic_slice_in_dim(
                xp, valid, self.cfg.conv_kernel - 1, axis=1)
            xs, b, c = self._split(act)
            dt, a = self._dt_a(dt)
            dt = jnp.where((jnp.arange(length) < valid)[None, :, None],
                           dt, 0.0)
            pad = -length % chunk
            if pad:
                xs, b, c, dt = (jnp.pad(t, [(0, 0), (0, pad)]
                                        + [(0, 0)] * (t.ndim - 2))
                                for t in (xs, b, c, dt))

            def chunks(t):      # [B, n*chunk, ..] -> [n, B, chunk, ..]
                t = t.reshape(t.shape[0], -1, chunk, *t.shape[2:])
                return jnp.moveaxis(t, 1, 0)

            def step(s, inp):
                y, s = ssd_chunk(inp[0], inp[1], a, inp[2], inp[3], s)
                return s, y

            ssm, ys = jax.lax.scan(step, ssm.astype(jnp.float32),
                                   tuple(chunks(t) for t in (xs, dt, b, c)))
            y = jnp.moveaxis(ys, 0, 1).reshape(
                x.shape[0], -1, self.heads, self.hd)[:, :length]
            return self._gate_out(y, xs[:, :length], z), ssm, conv

    def step(self, x, ssm, conv, active):
        """The one-step recurrence for every lane: x [B, 1, hidden];
        lanes where `active` is false keep their state as it was."""
        with jax.named_scope("ssm_step"):
            z, xbc, dt = self._project(x)
            act, xp = self._conv(conv, xbc)
            xs, b, c = self._split(act[:, 0])
            dt, a = self._dt_a(dt[:, 0])
            rep = self.heads // self.groups
            bh, ch = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
            new = (ssm * jnp.exp(dt * a)[:, :, None, None]
                   + (dt[:, :, None] * xs)[..., None] * bh[:, :, None, :])
            y = jnp.sum(new * ch[:, :, None, :], axis=-1)
            ssm = jnp.where(active[:, None, None, None], new, ssm)
            conv = jnp.where(active[:, None, None], xp[:, 1:], conv)
            return self._gate_out(y[:, None], xs[:, None], z), ssm, conv


# ---------------------------------------------------------------- attention
class NemotronHAttention(_Block):
    """Causal grouped-query attention, no bias, no window, no rotary
    embedding (the config's `rope_theta` is unused by this model).
    `scale` multiplies q k^T: 1/sqrt(head_dim) unless the model states
    its own (`nlp/granite_hybrid.py`: `attention_multiplier`)."""

    def __init__(self, cfg, scale=None):
        super().__init__(cfg)
        self.nh, self.nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.hd = cfg.head_dim
        self.scale = (1.0 / math.sqrt(self.hd) if scale is None
                      else float(scale))
        self.qkv_proj = self._matrix(cfg.hidden_size,
                                     (self.nh + 2 * self.nkv) * self.hd)
        self.o_proj = self._matrix(self.nh * self.hd, cfg.hidden_size)

    def init_paged_cache(self, num_blocks, block_size, dtype):
        from ..nn.paged_attention import init_block_kv
        return init_block_kv(num_blocks, self.nkv, block_size, self.hd,
                             dtype)

    def _qkv(self, x):
        """x [B, L, hidden] -> q [B, L, nh, hd], k and v [B, L, nkv, hd]."""
        b, length = x.shape[:2]
        q, k, v = jnp.split(x @ self.qkv_proj._data,
                            [self.nh * self.hd,
                             (self.nh + self.nkv) * self.hd], axis=-1)
        return (q.reshape(b, length, self.nh, self.hd),
                k.reshape(b, length, self.nkv, self.hd),
                v.reshape(b, length, self.nkv, self.hd))

    def _out(self, o, dtype):
        """o [B, nh, L, hd] -> [B, L, hidden]."""
        b, _, length, _ = o.shape
        o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, length, -1)
        return o.astype(dtype) @ self.o_proj._data

    def forward(self, x):
        q, k, v = self._qkv(x)
        o = _gqa_flash_bshd(q, k, v, self.nh, self.nkv, None,
                            scale=self.scale)
        return (o.reshape(*x.shape[:2], -1).astype(x.dtype)
                @ self.o_proj._data)

    def paged_step(self, x, cache, tables, start, valid_len=None):
        """x [B, C, hidden] at positions start + arange(C) against the
        block pool: one token a lane in the wave (C == 1), a chunk of
        one lane's prompt (B == 1; nothing written past valid_len)."""
        from ..nn.paged_attention import paged_attend
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in self._qkv(x))
        o, cache = paged_attend(q, k, v, cache, tables, start, valid_len,
                                self.scale)
        return self._out(o, x.dtype), cache


# ------------------------------------------------------------------ experts
class NemotronHMoE(RoutedExperts):
    """Dropless routed experts `down(relu(up(x))^2)` and one shared
    expert of the same form: the ungated form of the experts that the
    routed-expert models share (`nlp/moe.py`: router, sort by expert,
    grouped kernel)."""

    def __init__(self, cfg):
        super().__init__(cfg, cfg.moe_shared_expert_intermediate_size)


# ------------------------------------------------------ ways down a stack
# What a hybrid stack's three entry points say to a layer's mixer:
# `call(mixer, cache, x) -> (mixer output, the layer's cache after it)`.
# A Mamba mixer's cache is its slots' records, an attention layer's its
# block pool, any other mixer's None. One set for every stack whose
# mixers are these classes (this file's, `nlp/granite_hybrid.py`'s).
def cache_group(mixer):
    """"state", "kv" or None: the list of the paged cache that holds
    this mixer's entry."""
    if isinstance(mixer, Mamba2Mixer):
        return "state"
    return "kv" if isinstance(mixer, NemotronHAttention) else None


def init_paged_caches(mixers, num_blocks, block_size, dtype, num_slots):
    """{"kv": a K/V block pool an attention layer, "state": a Mamba
    record (`ssm`, `conv`; leading dimension `num_slots`) a Mamba
    layer}, each list in the layers' order."""
    return {"kv": [m.init_paged_cache(num_blocks, block_size, dtype)
                   for m in mixers if cache_group(m) == "kv"],
            "state": [m.init_state(num_slots, dtype) for m in mixers
                      if cache_group(m) == "state"]}


def through(call, mixer, caches, i, x):
    """`call` on one layer's mixer with entry `i` of the list that holds
    its cache (if it keeps one), which the new entry replaces in
    `caches`; returns the mixer's output."""
    store = caches.get(cache_group(mixer))
    out, new = call(mixer, None if store is None else store[i], x)
    if store is not None:
        store[i] = new
    return out


def sequence_call(batch):
    """A whole sequence: attention over the sequence itself, the scan
    from a zero state; nothing is cached."""
    def call(mixer, cache, x):
        if isinstance(mixer, Mamba2Mixer):
            fresh = mixer.init_state(batch, x.dtype)
            return mixer.scan(x, fresh["ssm"], fresh["conv"])[0], cache
        return mixer(x), cache
    return call


def wave_call(pos, tables, active):
    """One token a lane (lane b is slot b) at positions `pos` [B];
    lanes where `active` is false keep their records."""
    def call(mixer, cache, x):
        if isinstance(mixer, Mamba2Mixer):
            out, ssm, conv = mixer.step(x, cache["ssm"], cache["conv"],
                                        active)
            return out, {"ssm": ssm, "conv": conv}
        if isinstance(mixer, NemotronHAttention):
            return mixer.paged_step(x, cache, tables, pos)
        return mixer(x), cache
    return call


def chunk_call(tables, chunk_start, valid_len, slot):
    """One prompt chunk [1, C] of the request in `slot` at absolute
    positions chunk_start + arange(C), `valid_len` of them real."""
    def call(mixer, cache, x):
        if isinstance(mixer, Mamba2Mixer):
            ssm = jax.lax.dynamic_slice_in_dim(cache["ssm"], slot, 1)
            conv = jax.lax.dynamic_slice_in_dim(cache["conv"], slot, 1)
            out, ssm, conv = mixer.scan(x, ssm, conv, valid_len)
            return out, {
                "ssm": jax.lax.dynamic_update_slice_in_dim(
                    cache["ssm"], ssm, slot, 0),
                "conv": jax.lax.dynamic_update_slice_in_dim(
                    cache["conv"], conv.astype(cache["conv"].dtype),
                    slot, 0)}
        if isinstance(mixer, NemotronHAttention):
            return mixer.paged_step(x, cache, tables, chunk_start,
                                    valid_len)
        return mixer(x), cache
    return call


# -------------------------------------------------------------------- stack
_MIXERS = {"M": Mamba2Mixer, "E": NemotronHMoE, "*": NemotronHAttention}


class NemotronHBlock(nn.Layer):
    def __init__(self, cfg, kind):
        super().__init__()
        self.kind, self.eps = kind, cfg.layer_norm_epsilon
        self.norm_weight = self.create_parameter(
            [cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.mixer = _MIXERS[kind](cfg)


class NemotronHForCausalLM(nn.Layer):
    """The stack, its embedding, final norm and untied head. The methods
    are the ones the trainer's forward and the paged engine call:
    `forward`, `init_paged_cache`, `decode_step`, `prefill_chunk`."""

    #: per-slot records live beside the paged K/V (the paged engine
    #: reads this: it passes slots, zeroes a record when a slot begins a
    #: prompt, and turns off what moves or shares pages alone)
    slot_state = True

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        init = (I.Normal(0.0, cfg.initializer_range) if cfg.init_weights
                else I.Constant(0.0))
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=init)
        self.layers = nn.LayerList([NemotronHBlock(cfg, kind) for kind
                                    in cfg.hybrid_override_pattern])
        self.norm_f_weight = self.create_parameter(
            [cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [cfg.hidden_size, cfg.vocab_size], dtype=cfg.param_dtype,
            default_initializer=init)
        kinds = cfg.hybrid_override_pattern
        # where each block's cache lies in its kind's list
        self._cache_index = [kinds[:i].count(k) for i, k in enumerate(kinds)]
        #: (token, expert) pairs one token makes on its way down the stack
        self.moe_picks_per_token = kinds.count("E") * cfg.num_experts_per_tok

    def _run(self, ids, caches, call):
        """The residual stack over ids [B, L]; `call` is one of the ways
        down a stack above. Returns (final hidden, caches)."""
        x = self.embeddings._data[_raw(ids)]
        caches = {k: list(v) for k, v in caches.items()}
        for blk, i in zip(self.layers, self._cache_index):
            x = x + through(call, blk.mixer, caches, i, _rms_norm_raw(
                x, blk.norm_weight._data, blk.eps))
        x = _rms_norm_raw(x, self.norm_f_weight._data,
                          self.cfg.layer_norm_epsilon)
        return x, caches

    def forward(self, input_ids):
        """Logits [B, L, V] of a whole sequence: attention over the
        sequence itself, the scan from a zero state."""
        x, _ = self._run(input_ids, {},
                         sequence_call(_raw(input_ids).shape[0]))
        return Tensor(x @ self.lm_head._data)

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32, num_slots=1):
        """`init_paged_caches` of this stack's mixers."""
        return init_paged_caches([blk.mixer for blk in self.layers],
                                 num_blocks, block_size, dtype, num_slots)

    def decode_step(self, tok, caches, pos, block_tables, active):
        """One token a lane: tok [B, 1], pos [B], tables [B, nblk],
        active [B] bool (lane b is slot b). Returns (logits [B, 1, V],
        caches)."""
        x, caches = self._run(tok, caches, wave_call(
            _raw(pos), _raw(block_tables), _raw(active)))
        return x @ self.lm_head._data, caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier, slot):
        """One prompt chunk [1, C] of the request in `slot` at absolute
        positions chunk_start + arange(C). Returns (logits [1, 1, V] at
        the chunk's `frontier` row, caches)."""
        x, caches = self._run(tok_chunk, caches, chunk_call(
            _raw(block_tables), _raw(chunk_start), _raw(valid_len),
            _raw(slot)))
        x = jax.lax.dynamic_slice_in_dim(x, frontier, 1, axis=1)
        return x @ self.lm_head._data, caches
