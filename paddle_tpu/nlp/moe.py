"""What the routed-expert models share: parameter creation in the
config's dtype, the router, and the dropless dispatch of sorted picks
through the grouped kernel (`ops/pallas/grouped_mlp.py`), in the two
forms an expert takes: `down(relu(up(x))^2)` (two matrices an expert)
and, gated, `down(silu(gate(x)) * up(x))` (three). `nlp/nemotron_h.py`
uses the first, `nlp/deepseek_v3.py` and `nlp/laguna.py` the second; the
router, the sort, the segments and the shared expert are one body. A
forward's picks go through the kernel in one call wherever the kernel's
VMEM plan holds them (`grouped_mlp.max_rows`, from the layer's own
widths, dtype and matrices), and a segment at a time, each as large as
fits, only past that.

A config gives `hidden_size`, `n_routed_experts`, `num_experts_per_tok`,
`moe_intermediate_size`, `routed_scaling_factor`, `initializer_range`,
`param_dtype` and `init_weights`. The router's arithmetic is float32, and
it scores in one of two ways: a sigmoid a logit with a correction bias
that only chooses (`route`), or a softmax over the logits (`route_softmax`,
`nlp/laguna.py`).
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from ..ops.pallas.grouped_mlp import (grouped_mlp, max_rows, note_fetched,
                                     walk)

_HIGHEST = jax.lax.Precision.HIGHEST


class ParamBlock(nn.Layer):
    """Parameter creation in the config's dtype: matrices
    normal(0, initializer_range) or, with `init_weights=False`, zeros."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg

    def _matrix(self, *shape):
        cfg = self.cfg
        init = (I.Normal(0.0, cfg.initializer_range) if cfg.init_weights
                else I.Constant(0.0))
        return self.create_parameter(list(shape), dtype=cfg.param_dtype,
                                     default_initializer=init)

    def _vector(self, n, value):
        return self.create_parameter(
            [n], dtype=self.cfg.param_dtype,
            default_initializer=I.Constant(value))


def route(scores_bias, logits, k, scale):
    """(experts [T, k], weights [T, k] float32) from router logits
    [T, E]: s = sigmoid(logits); the k largest of s + bias are chosen;
    their weights are the chosen s, without the bias, over their sum,
    times `scale`."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + scores_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def route_softmax(logits, k, scale):
    """(experts [T, k], weights [T, k] float32) from router logits
    [T, E]: p = softmax(logits); the k largest of p are chosen; their
    weights are the chosen p over their sum, times `scale`."""
    w, idx = jax.lax.top_k(jax.nn.softmax(logits.astype(jnp.float32),
                                          axis=-1), k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def routed_layout(model):
    """`PagedLayout` fields of `model`'s routed layers: (token, expert)
    pairs a token, experts stored, tokens a kernel call of each."""
    routed = [m for m in model.sublayers() if isinstance(m, RoutedExperts)]
    return {"moe_picks": sum(m.cfg.num_experts_per_tok for m in routed),
            "experts_stored": sum(m.cfg.n_routed_experts for m in routed),
            "expert_tokens": tuple(m.tokens_a_call() for m in routed)}


class RoutedExperts(ParamBlock):
    """Dropless routed experts and one shared expert of the same form
    (`gated`: see the module's docstring), `shared_width` wide. Picks
    are sorted by expert and one kernel walks the experts, each over its
    own rows: no capacity, nothing dropped, no per-token copy of an
    expert's weights. `scoring` is "sigmoid" (`route`, with its
    correction bias) or "softmax" (`route_softmax`, no bias)."""

    def __init__(self, cfg, shared_width, gated=False, scoring="sigmoid"):
        super().__init__(cfg)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"router scoring {scoring!r} is not computed "
                             "(only 'sigmoid' or 'softmax')")
        h, e, m = (cfg.hidden_size, cfg.n_routed_experts,
                   cfg.moe_intermediate_size)
        self.gated = bool(gated)
        self.scoring = scoring
        self.router_weight = self._matrix(h, e)
        if scoring == "sigmoid":
            self.e_score_correction_bias = self._vector(e, 0.0)
        # all [experts, width, hidden]: `up` (and `gate`) as [out, in],
        # `down` as [in, out], the layout the kernel reads without a copy
        self.experts_up = self._matrix(e, m, h)
        if self.gated:
            self.experts_gate = self._matrix(e, m, h)
        self.experts_down = self._matrix(e, m, h)
        self.shared_up = self._matrix(h, shared_width)
        if self.gated:
            self.shared_gate = self._matrix(h, shared_width)
        self.shared_down = self._matrix(shared_width, h)

    def route(self, x):
        logits = jnp.matmul(x.astype(jnp.float32),
                            self.router_weight._data.astype(jnp.float32),
                            precision=_HIGHEST)
        if self.scoring == "softmax":
            return route_softmax(logits, self.cfg.num_experts_per_tok,
                                 self.cfg.routed_scaling_factor)
        return route(self.e_score_correction_bias._data, logits,
                     self.cfg.num_experts_per_tok,
                     self.cfg.routed_scaling_factor)

    def tokens_a_call(self):
        """Tokens whose picks one kernel call takes: the rows the
        kernel's plan holds at this layer's widths, dtype and matrices
        (`grouped_mlp.max_rows`) over top-k, at least one."""
        _, width, hidden = self.experts_up.shape
        rows = max_rows(hidden, width, self.experts_up._data.dtype.itemsize,
                        3 if self.gated else 2)
        return max(1, rows // self.cfg.num_experts_per_tok)

    def kernel_calls(self, tokens):
        """The kernel calls `experts` makes for `tokens` tokens."""
        return -(-tokens // self.tokens_a_call())

    def _segment(self, x, idx, weights):
        """x [T, hidden], idx and weights [T, k] -> ([T, hidden] float32,
        the experts the kernel's call fetched), T <= `tokens_a_call()`."""
        k = idx.shape[1]
        flat = idx.reshape(-1)
        order = jnp.argsort(flat)                   # stable: by expert
        sizes = jnp.bincount(flat, length=self.cfg.n_routed_experts)
        out = grouped_mlp(
            x[order // k], self.experts_up._data, self.experts_down._data,
            sizes, gate=self.experts_gate._data if self.gated else None)
        out = out * weights.reshape(-1)[order][:, None]
        # back to token order: pick j of token t sits at row inv[t k + j]
        inv = jnp.argsort(order)
        return (out[inv].reshape(-1, k, out.shape[-1]).sum(axis=1),
                walk(sizes)[1])

    def experts(self, x, idx, weights):
        """x [T, hidden], idx and weights [T, k] -> [T, hidden] float32:
        the picks in one kernel call where it holds them, else a segment
        of `tokens_a_call()` tokens a call (`kernel_calls`). The experts
        its calls fetched go to the program's `fetch_tally`, if it keeps
        one."""
        t = idx.shape[0]
        seg = self.tokens_a_call()
        if t <= seg:
            out, fetched = self._segment(x, idx, weights)
            note_fetched(fetched)
            return out
        pad = -t % seg              # padded tokens: expert 0, weight 0
        parts = [jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, seg, a.shape[1])
                 for a in (x, idx, weights)]
        out, fetched = jax.lax.map(lambda a: self._segment(*a), tuple(parts))
        note_fetched(jnp.sum(fetched))
        return out.reshape(-1, out.shape[-1])[:t]

    def shared(self, x):
        hid = x @ self.shared_up._data
        hid = (jax.nn.silu(x @ self.shared_gate._data) * hid if self.gated
               else jnp.square(jax.nn.relu(hid)))
        return hid @ self.shared_down._data

    def forward(self, x):
        lead = x.shape[:-1]
        x = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe_route"):
            idx, weights = self.route(x)
        y = self.experts(x, idx, weights)
        with jax.named_scope("moe_shared"):
            y = y + self.shared(x).astype(jnp.float32)
        return y.astype(x.dtype).reshape(*lead, -1)
