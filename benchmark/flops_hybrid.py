"""Operations and bytes one decode wave of a hybrid stack needs, computed
from shapes: Mamba-2 mixers (M), routed experts with a shared expert (E)
and grouped-query attention (*), as the configuration's pattern string
orders them, and the head.

`sh` is `harness.shapes(config)`: pattern, hidden, vocab, heads, kv_heads,
head_dim, mamba_heads, mamba_head_dim, mamba_groups, mamba_state,
conv_kernel, experts, experts_per_token, expert_width, shared_width.

Conventions (benchmark/flops.py's): a multiply-add is 2 operations; every
matrix the wave uses is read once whatever the lanes; of the routed
experts only those some lane chose are read, and with uniform routing
that is E (1 - (1 - k/E)^lanes) of E expected; what belongs to a lane
(Mamba state and conv taps, read and written; the K and V rows it
attends, read) is counted for each decoding lane; norms, biases, the
embedding rows and the activations are left out (under 0.1% at 128 lanes).
"""


def mamba_weights(sh):
    """in_proj (z, xBC, dt), the conv taps, out_proj of one mixer."""
    d_inner = sh["mamba_heads"] * sh["mamba_head_dim"]
    conv_dim = d_inner + 2 * sh["mamba_groups"] * sh["mamba_state"]
    return (sh["hidden"] * (d_inner + conv_dim + sh["mamba_heads"])
            + sh["conv_kernel"] * conv_dim + d_inner * sh["hidden"])


def mamba_state_elements(sh):
    """(float32 state elements, conv taps kept) of one mixer, one lane."""
    d_inner = sh["mamba_heads"] * sh["mamba_head_dim"]
    conv_dim = d_inner + 2 * sh["mamba_groups"] * sh["mamba_state"]
    return (d_inner * sh["mamba_state"],
            (sh["conv_kernel"] - 1) * conv_dim)


def attention_weights(sh):
    q = sh["heads"] * sh["head_dim"]
    kv = sh["kv_heads"] * sh["head_dim"]
    return sh["hidden"] * (q + 2 * kv) + q * sh["hidden"]


def experts_touched(sh, lanes):
    """Expected number of routed experts that at least one of `lanes`
    tokens chose, each choosing k of E uniformly."""
    e, k = sh["experts"], sh["experts_per_token"]
    return e * (1.0 - (1.0 - k / e) ** lanes)


def expert_mlp_cost(sh, tokens, itemsize=2):
    """(operations, bytes) of one call of the grouped expert kernel
    (`moe_experts`: up, relu^2, down over picks sorted by expert) for
    `tokens` tokens: each pick multiplies both of its expert's matrices;
    the matrices of the experts some token chose are read once, the
    picks' rows read (in the weights' type) and written (float32)."""
    picks = tokens * sh["experts_per_token"]
    expert = 2 * sh["hidden"] * sh["expert_width"]
    return (2.0 * picks * expert,
            itemsize * experts_touched(sh, tokens) * expert
            + picks * sh["hidden"] * (itemsize + 4))


def paged_attention_cost(sh, attended_tokens, itemsize=2):
    """(operations, bytes) of the attention layers' paged core in one
    decode wave whose lanes together attend `attended_tokens` cached
    positions: `flops.paged_decode_cost`'s count (each position's K and V
    row of every kv head read once; q.k and p.v 2 operations each per
    query head and element), once for every `*` of the pattern and not
    for every layer."""
    n_a = sh["pattern"].count("*")
    return (n_a * 4.0 * sh["heads"] * sh["head_dim"] * attended_tokens,
            n_a * 2.0 * sh["kv_heads"] * sh["head_dim"] * itemsize
            * attended_tokens)


def decode_wave_cost(sh, lanes, attended_tokens, itemsize=2):
    """(operations, bytes) of one decode wave over `lanes` decoding lanes
    that together attend `attended_tokens` cached positions."""
    n_m, n_e, n_a = (sh["pattern"].count(c) for c in "ME*")
    expert = 2 * sh["hidden"] * sh["expert_width"]       # up and down
    shared = 2 * sh["hidden"] * sh["shared_width"]
    router = sh["hidden"] * sh["experts"]
    state, taps = mamba_state_elements(sh)
    head = sh["hidden"] * sh["vocab"]
    attn_ops, attn_bytes = paged_attention_cost(sh, attended_tokens,
                                                itemsize)

    per_token_weights = (
        n_m * mamba_weights(sh) + n_a * attention_weights(sh)
        + n_e * (router + sh["experts_per_token"] * expert + shared) + head)
    # the state update S = decay S + (dt x) b^T is 3 operations an
    # element, y = S c two more
    ops = (2.0 * lanes * per_token_weights
           + n_m * lanes * 5.0 * state
           + attn_ops)

    weights_read = (
        n_m * mamba_weights(sh) + n_a * attention_weights(sh)
        + n_e * (router + experts_touched(sh, lanes) * expert + shared)
        + head)
    nbytes = (itemsize * weights_read
              + n_m * lanes * 2.0 * (4 * state + itemsize * taps)
              + attn_bytes)
    return ops, nbytes
