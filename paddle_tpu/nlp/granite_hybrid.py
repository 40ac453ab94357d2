"""Granite 4.0-H (`model_type: granitemoehybrid`) decoder LM without
routed experts: one stack whose layers are chosen by `layer_types`, each
a mixer and a gated MLP behind scaled residuals,

    h += residual_multiplier * mixer(RMSNorm(h))
    h += residual_multiplier * mlp(RMSNorm(h))

with `mixer` a Mamba-2 mixer ("mamba") or grouped-query attention
without positions ("attention", `position_embedding_type: nope`, scores
scaled by `attention_multiplier`, not 1/sqrt(head_dim)). The embedding
is multiplied by `embedding_multiplier`, the head is the embedding
transposed, and the logits are divided by `logits_scaling`.

The mixers, their caches (K/V in the paged pool, one Mamba record a
serving slot) and the three ways down a stack are `nlp/nemotron_h.py`'s:
this file adds the layer, the MLP and the four multipliers. State, dt,
decay and softmax are float32, everything else runs in the parameters'
dtype.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..framework.tensor import Tensor
from ..nn import initializer as I
from .llama import _rms_norm_raw
from .moe import ParamBlock
from .nemotron_h import (Mamba2Mixer, NemotronHAttention, _raw, chunk_call,
                         hybrid_layout, init_paged_caches, sequence_call,
                         through, wave_call)

LAYER_TYPES = ("mamba", "attention")


class GraniteHybridConfig:
    """Sizes under the names of the published `config.json`. The MLP of
    a model without routed experts is the family's shared MLP,
    `shared_intermediate_size` wide (`intermediate_size` is the routed
    experts' width and unused). `param_dtype` is the dtype parameters
    are created in; `init_weights=False` creates them as zeros for a
    caller that installs its own."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 layer_types=("mamba",) * 5 + ("attention",)
                 + ("mamba",) * 4, num_hidden_layers=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 shared_intermediate_size=8192, intermediate_size=8192,
                 mamba_n_heads=64, mamba_d_head=64, mamba_n_groups=1,
                 mamba_d_state=128, mamba_d_conv=4, mamba_expand=2,
                 mamba_chunk_size=256, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.015625,
                 logits_scaling=8.0, rms_norm_eps=1e-5,
                 num_local_experts=0, num_experts_per_tok=0,
                 position_embedding_type="nope", tie_word_embeddings=True,
                 initializer_range=0.02, param_dtype="float32",
                 init_weights=True):
        layer_types = tuple(layer_types)
        bad = set(layer_types) - set(LAYER_TYPES)
        if bad or not layer_types:
            raise ValueError(f"layer_types is a list of {LAYER_TYPES}, "
                             f"got {sorted(bad) or layer_types}")
        if num_hidden_layers not in (None, len(layer_types)):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} is not the length "
                f"of layer_types ({len(layer_types)})")
        if num_local_experts or num_experts_per_tok:
            raise ValueError(
                f"num_local_experts {num_local_experts} (num_experts_per_tok"
                f" {num_experts_per_tok}): this stack has no routed experts,"
                " every layer's MLP is the shared one")
        if position_embedding_type != "nope":
            raise ValueError("position_embedding_type "
                             f"{position_embedding_type!r}: the attention "
                             "layers apply no position ('nope')")
        if not tie_word_embeddings:
            raise ValueError("tie_word_embeddings false: the head is the "
                             "embedding transposed")
        if num_attention_heads % num_key_value_heads:
            raise ValueError(f"num_attention_heads {num_attention_heads} "
                             "not divisible by num_key_value_heads "
                             f"{num_key_value_heads}")
        if hidden_size % num_attention_heads:
            raise ValueError(f"hidden_size {hidden_size} not divisible by "
                             f"num_attention_heads {num_attention_heads}")
        if mamba_n_heads % mamba_n_groups:
            raise ValueError(f"mamba_n_heads {mamba_n_heads} not divisible "
                             f"by mamba_n_groups {mamba_n_groups}")
        if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
            raise ValueError(
                f"mamba_n_heads {mamba_n_heads} x mamba_d_head "
                f"{mamba_d_head} is not mamba_expand {mamba_expand} x "
                f"hidden_size {hidden_size}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.layer_types = layer_types
        self.num_layers = len(layer_types)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.shared_intermediate_size = int(shared_intermediate_size)
        self.mamba_n_heads = int(mamba_n_heads)
        self.mamba_d_head = int(mamba_d_head)
        self.mamba_n_groups = int(mamba_n_groups)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_chunk_size = int(mamba_chunk_size)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = float(initializer_range)
        self.param_dtype = jnp.dtype(param_dtype).name
        self.init_weights = bool(init_weights)
        # the names the shared mixers read their sizes under
        # (nlp/nemotron_h.py: Mamba2Mixer, NemotronHAttention)
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.mamba_num_heads = self.mamba_n_heads
        self.mamba_head_dim = self.mamba_d_head
        self.n_groups = self.mamba_n_groups
        self.ssm_state_size = self.mamba_d_state
        self.conv_kernel = self.mamba_d_conv
        self.chunk_size = self.mamba_chunk_size
        self.layer_norm_epsilon = self.rms_norm_eps


class GraniteGatedMLP(ParamBlock):
    """`(silu(g) * u) @ W_out` with `g, u = split(x @ W_in)`: the gated
    MLP of `nlp/llama.py` with gate and up stored as one matrix."""

    def __init__(self, cfg):
        super().__init__(cfg)
        self.width = cfg.shared_intermediate_size
        self.input_linear = self._matrix(cfg.hidden_size, 2 * self.width)
        self.output_linear = self._matrix(self.width, cfg.hidden_size)

    def forward(self, x):
        with jax.named_scope("gated_mlp"):
            g, u = jnp.split(x @ self.input_linear._data, 2, axis=-1)
            return (jax.nn.silu(g) * u) @ self.output_linear._data


class GraniteHybridLayer(nn.Layer):
    def __init__(self, cfg, kind):
        super().__init__()
        self.kind = kind
        self.input_norm_weight, self.post_norm_weight = (
            self.create_parameter([cfg.hidden_size], dtype=cfg.param_dtype,
                                  default_initializer=I.Constant(1.0))
            for _ in range(2))
        self.mixer = (Mamba2Mixer(cfg) if kind == "mamba" else
                      NemotronHAttention(cfg, scale=cfg.attention_multiplier))
        self.mlp = GraniteGatedMLP(cfg)


class GraniteHybridForCausalLM(nn.Layer):
    """The stack, its embedding (also the head) and final norm. The
    methods are the ones the trainer's forward and the paged engine call:
    `forward`, `init_paged_cache`, `decode_step`, `prefill_chunk`, with
    `NemotronHForCausalLM`'s signatures."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=(I.Normal(0.0, cfg.initializer_range)
                                 if cfg.init_weights else I.Constant(0.0)))
        self.layers = nn.LayerList([GraniteHybridLayer(cfg, kind)
                                    for kind in cfg.layer_types])
        self.norm_f_weight = self.create_parameter(
            [cfg.hidden_size], dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        kinds = cfg.layer_types
        # where each layer's cache lies in its kind's list
        self._cache_index = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    def _run(self, ids, caches, call):
        """The stack over ids [B, L]; `call` is one of the ways down a
        stack (`nlp/nemotron_h.py`). Returns (final hidden, caches)."""
        cfg, eps = self.cfg, self.cfg.rms_norm_eps
        emb = self.embeddings._data
        x = emb[_raw(ids)] * jnp.asarray(cfg.embedding_multiplier, emb.dtype)

        def add(x, out):
            # float32 and rounded once: 0.22 is not a bfloat16 number
            return (x.astype(jnp.float32) + cfg.residual_multiplier
                    * out.astype(jnp.float32)).astype(x.dtype)

        caches = {k: list(v) for k, v in caches.items()}
        for lyr, i in zip(self.layers, self._cache_index):
            x = add(x, through(call, lyr.mixer, caches, i, _rms_norm_raw(
                x, lyr.input_norm_weight._data, eps)))
            x = add(x, lyr.mlp(
                _rms_norm_raw(x, lyr.post_norm_weight._data, eps)))
        return _rms_norm_raw(x, self.norm_f_weight._data, eps), caches

    def _logits(self, x):
        """The tied head: x @ E^T / logits_scaling, float32 out of the
        matmul so that the division rounds once."""
        lo = jnp.einsum("blh,vh->blv", x, self.embeddings._data,
                        preferred_element_type=jnp.float32)
        return (lo / self.cfg.logits_scaling).astype(x.dtype)

    def forward(self, input_ids):
        """Logits [B, L, V] of a whole sequence: attention over the
        sequence itself, the scan from a zero state."""
        x, _ = self._run(input_ids, {},
                         sequence_call(_raw(input_ids).shape[0]))
        return Tensor(self._logits(x))

    def init_paged_cache(self, num_blocks, block_size, max_len,
                         dtype=jnp.float32, num_slots=1):
        """`init_paged_caches` of this stack's mixers."""
        return init_paged_caches([lyr.mixer for lyr in self.layers],
                                 num_blocks, block_size, dtype, num_slots)

    paged_layout = hybrid_layout

    def decode_step(self, tok, caches, pos, block_tables, active):
        """One token a lane: tok [B, 1], pos [B], tables [B, nblk],
        active [B] bool (lane b is slot b). Returns (logits [B, 1, V],
        caches)."""
        x, caches = self._run(tok, caches, wave_call(
            _raw(pos), _raw(block_tables), _raw(active)))
        return self._logits(x), caches

    def prefill_chunk(self, tok_chunk, caches, block_tables, chunk_start,
                      valid_len, frontier, slot):
        """One prompt chunk [1, C] of the request in `slot` at absolute
        positions chunk_start + arange(C). Returns (logits [1, 1, V] at
        the chunk's `frontier` row, caches)."""
        x, caches = self._run(tok_chunk, caches, chunk_call(
            _raw(block_tables), _raw(chunk_start), _raw(valid_len),
            _raw(slot)))
        return self._logits(
            jax.lax.dynamic_slice_in_dim(x, frontier, 1, axis=1)), caches
