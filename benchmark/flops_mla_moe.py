"""Operations and bytes one decode wave and one prefill chunk need of a
decoder with latent attention (MLA) and gated routed experts, computed
from shapes: `layers` blocks of latent attention, the first
`dense_layers` with a dense SwiGLU, the others with routed SwiGLU experts
and a shared one, and the head.

`sh` is `harness.shapes(config)`: layers, dense_layers, hidden, vocab,
heads, latent_rank, rope_dim, nope_dim, v_dim, ffn, experts,
experts_per_token, expert_width, shared_experts.

Conventions (benchmark/flops.py's): a multiply-add is 2 operations; every
matrix the step uses is read once whatever the tokens; of the routed
experts only those some token chose are read, and with uniform routing
that is E (1 - (1 - k/E)^tokens) of E expected; a latent row a lane
attends is read once a layer, its rank + rope values (the zeros the pool
stores beside them are not needed); norms, the embedding rows and the
activations are left out.
"""

GATED_MATRICES = 3              # up, gate, down


def attention_weights(sh):
    """q_proj, kv_a (latent + rotary key), kv_b (K_nope and V of every
    head), o_proj of one layer."""
    h, heads, rank = sh["hidden"], sh["heads"], sh["latent_rank"]
    return (h * heads * (sh["nope_dim"] + sh["rope_dim"])
            + h * (rank + sh["rope_dim"])
            + kv_b_weights(sh) + heads * sh["v_dim"] * h)


def kv_b_weights(sh):
    """kv_b of one layer: what a chunk applies to a row it expands and a
    wave to a token (as the two absorbed products)."""
    return sh["latent_rank"] * sh["heads"] * (sh["nope_dim"] + sh["v_dim"])


def expert_weights(sh):
    return GATED_MATRICES * sh["hidden"] * sh["expert_width"]


def shared_weights(sh):
    return (GATED_MATRICES * sh["hidden"] * sh["shared_experts"]
            * sh["expert_width"])


def experts_touched(sh, tokens):
    """Expected number of routed experts that at least one of `tokens`
    tokens chose, each choosing k of E uniformly."""
    e, k = sh["experts"], sh["experts_per_token"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def parameters(sh):
    """Every parameter that takes part in a matmul, and the embedding."""
    moe = sh["layers"] - sh["dense_layers"]
    return (2 * sh["vocab"] * sh["hidden"]
            + sh["layers"] * attention_weights(sh)
            + sh["dense_layers"] * GATED_MATRICES * sh["hidden"] * sh["ffn"]
            + moe * (sh["hidden"] * sh["experts"]
                     + sh["experts"] * expert_weights(sh)
                     + shared_weights(sh)))


def _stack(sh, tokens):
    """(weights a token multiplies but for kv_b, weights read but for
    kv_b) of all layers, for a step of `tokens` tokens; the head and
    kv_b are the callers'."""
    moe = sh["layers"] - sh["dense_layers"]
    fixed = (sh["layers"] * (attention_weights(sh) - kv_b_weights(sh))
             + sh["dense_layers"] * GATED_MATRICES * sh["hidden"] * sh["ffn"]
             + moe * (sh["hidden"] * sh["experts"] + shared_weights(sh)))
    return (fixed + moe * sh["experts_per_token"] * expert_weights(sh),
            fixed + moe * experts_touched(sh, tokens) * expert_weights(sh))


def gated_expert_cost(sh, tokens, itemsize=2):
    """(operations, bytes) of one call of the grouped expert kernel in
    its gated form (`moe_experts`: up and gate, silu x, down, over picks
    sorted by expert) for `tokens` tokens: each pick multiplies its
    expert's three matrices; the matrices of the experts some token chose
    are read once, the picks' rows read (in the weights' type) and
    written (float32)."""
    picks = tokens * sh["experts_per_token"]
    return (2.0 * picks * expert_weights(sh),
            itemsize * experts_touched(sh, tokens) * expert_weights(sh)
            + picks * sh["hidden"] * (itemsize + 4))


def latent_decode_cost(sh, attended_rows, itemsize=2):
    """(operations, bytes) of the absorbed attention of one decode wave,
    all layers, when the lanes together attend `attended_rows` cached
    positions: a row is read once a layer (rank + rope values) and every
    head scores it (2 (rank + rope) operations) and mixes it (2 rank):
    2 x 32 x (576 + 512) a row at the published sizes."""
    rank, rope = sh["latent_rank"], sh["rope_dim"]
    return (sh["layers"] * 2.0 * sh["heads"] * (2 * rank + rope)
            * attended_rows,
            sh["layers"] * (rank + rope) * itemsize * attended_rows)


def decode_wave_cost(sh, lanes, attended_rows, itemsize=2):
    """(operations, bytes) of one decode wave over `lanes` decoding lanes
    that together attend `attended_rows` cached positions: every
    non-expert matrix and the head read once, 2 operations a weight a
    lane (kv_b as the two absorbed products, the same count); the routed
    experts some lane chose; the latent rows attended."""
    per_token, read = _stack(sh, lanes)
    kv_b = sh["layers"] * kv_b_weights(sh)
    head = sh["hidden"] * sh["vocab"]
    attn_ops, attn_bytes = latent_decode_cost(sh, attended_rows, itemsize)
    return (2.0 * lanes * (per_token + kv_b + head) + attn_ops,
            itemsize * (read + kv_b + head) + attn_bytes)


def prefill_chunk_cost(sh, tokens, expanded_rows, itemsize=2):
    """(operations, bytes) of one prompt chunk of `tokens` tokens whose
    lane puts `expanded_rows` cached positions (its own among them)
    through the expansion: the wave's count with kv_b applied to every
    expanded row and not to every token, the head to one row, and the
    expanded scores: a (query, row, head) costs 2 (nope + rope) + 2 v and
    a query sees the rows before it (all but half the chunk's own)."""
    per_token, read = _stack(sh, tokens)
    kv_b = sh["layers"] * kv_b_weights(sh)
    head = sh["hidden"] * sh["vocab"]
    seen = max(expanded_rows - tokens / 2.0, tokens / 2.0)
    scores = (sh["layers"] * sh["heads"] * 2.0
              * (sh["nope_dim"] + sh["rope_dim"] + sh["v_dim"])
              * tokens * seen)
    rows = (sh["layers"] * (sh["latent_rank"] + sh["rope_dim"]) * itemsize
            * expanded_rows)
    return (2.0 * (tokens * per_token + expanded_rows * kv_b + head)
            + scores,
            itemsize * (read + kv_b + head) + rows)
