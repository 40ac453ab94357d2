"""Operations and bytes one decode wave, one prefill chunk, and the two
kernels in them need of a decoder whose layers are full or sliding-window
grouped-query attention (each with its own head count and a per-head
output gate) and a dense SwiGLU or softmax-routed gated experts with a
shared one, computed from shapes.

`sh` is `harness.shapes(config)`: layers, hidden, vocab, layer_types,
mlp_layer_types, layer_heads (query heads of each layer), kv_heads,
head_dim, ffn, experts, experts_per_token, expert_width, shared_width,
window, gating.

Conventions (benchmark/flops.py's): a multiply-add is 2 operations;
every matrix a step uses is read once whatever the tokens; of the routed
experts only those some token chose are read, and with uniform routing
that is E (1 - (1 - k/E)^tokens) of E expected; a cached position a query
attends costs 4 operations a query head and head element (q.k, p.v) and
its K and V rows of every kv-head are read once a layer; norms, the gate's
sigmoid, the embedding rows and the activations are left out.
"""

from .flops_mla_moe import (GATED_MATRICES, expert_weights,  # noqa: F401
                            experts_touched,
                            gated_expert_cost as expert_cost)

SLIDING = "sliding_attention"


def _kinds(sh):
    """(query heads, window or None, dense) of each layer."""
    return [(int(h), sh["window"] if t == SLIDING else None, m == "dense")
            for h, t, m in zip(sh["layer_heads"], sh["layer_types"],
                               sh["mlp_layer_types"])]


def attention_weights(sh, heads):
    """q, k, v, o (and the gate) of one layer of `heads` query heads."""
    hd, h = sh["head_dim"], sh["hidden"]
    return (h * (heads + 2 * sh["kv_heads"]) * hd + heads * hd * h
            + (h * heads if sh["gating"] else 0))


def sparse_layers(sh):
    return sum(not dense for _, _, dense in _kinds(sh))


def _fixed(sh):
    """Weights every token multiplies but the routed experts' and the
    head: attention, the dense MLPs, the routers and shared experts."""
    h = sh["hidden"]
    total = 0
    for heads, _, dense in _kinds(sh):
        total += attention_weights(sh, heads)
        total += (GATED_MATRICES * h * sh["ffn"] if dense else
                  h * sh["experts"]
                  + GATED_MATRICES * h * sh["shared_width"])
    return total


def parameters(sh):
    """Every parameter that takes part in a matmul, the embedding and the
    untied head: the configuration's size."""
    return (_fixed(sh) + sparse_layers(sh) * sh["experts"]
            * expert_weights(sh) + 2 * sh["vocab"] * sh["hidden"])


def paged_attention_cost(sh, full_rows, window_rows, itemsize=2):
    """(operations, bytes) of the paged attention of one step, all layers,
    when the step's queries together attend `full_rows` cached positions
    in a full layer and `window_rows` in a window layer."""
    kv_row = 2 * sh["kv_heads"] * sh["head_dim"] * itemsize
    ops = nbytes = 0.0
    for heads, window, _ in _kinds(sh):
        rows = full_rows if window is None else window_rows
        ops += 4.0 * heads * sh["head_dim"] * rows
        nbytes += kv_row * rows
    return ops, nbytes


def window_rows(sh, lanes, attended):
    """Cached positions the window layers' queries attend in a wave over
    `lanes` lanes that attend `attended` positions in a full layer:
    min(position + 1, window) a lane, taken at the lanes' mean (exact
    where every lane is past the window, as in every cell that has one:
    no prompt is shorter than the window)."""
    return lanes * min(attended / lanes, sh["window"]) if lanes else 0.0


def decode_wave_cost(sh, lanes, attended, itemsize=2):
    """(operations, bytes) of one decode wave over `lanes` decoding lanes
    that together attend `attended` cached positions in a full layer:
    every matrix but the routed experts' and the head read once, 2
    operations a weight a lane; the routed experts some lane chose, 3
    matrices each, in every sparse layer; the full layers' attended K/V
    rows and the window layers' `window_rows`."""
    head = sh["hidden"] * sh["vocab"]
    moe = sparse_layers(sh)
    attn_ops, attn_bytes = paged_attention_cost(
        sh, attended, window_rows(sh, lanes, attended), itemsize)
    per_token = (_fixed(sh) + head
                 + moe * sh["experts_per_token"] * expert_weights(sh))
    read = (_fixed(sh) + head
            + moe * experts_touched(sh, lanes) * expert_weights(sh))
    return 2.0 * lanes * per_token + attn_ops, itemsize * read + attn_bytes


def prefill_chunk_cost(sh, tokens, keys, itemsize=2):
    """(operations, bytes) of one prompt chunk of `tokens` tokens whose
    queries attend `keys` cached positions in all in a full layer (the sum
    of position + 1): the weights at 2 operations a token, the head on
    one row; q.k and p.v over those keys in the full layers, and over
    min(keys, tokens x window) in the window layers (each query at most a
    window: at most window + tokens keys between them); the experts some
    token chose read once, every other matrix once, and the K/V rows of
    the keys the chunk attends (a full layer's whole span, a window
    layer's window and the chunk)."""
    head = sh["hidden"] * sh["vocab"]
    moe = sparse_layers(sh)
    win_keys = min(keys, tokens * sh["window"])
    attn_ops, _ = paged_attention_cost(sh, keys, win_keys, itemsize)
    # the distinct positions the chunk reads: up to its last query
    span = (keys / tokens + (tokens - 1) / 2.0) if tokens else 0.0
    _, attn_bytes = paged_attention_cost(
        sh, span, min(span, sh["window"] + tokens), itemsize)
    per_token = _fixed(sh) + moe * sh["experts_per_token"] * expert_weights(sh)
    read = (_fixed(sh) + head
            + moe * experts_touched(sh, tokens) * expert_weights(sh))
    return (2.0 * (tokens * per_token + head) + attn_ops,
            itemsize * read + attn_bytes)
