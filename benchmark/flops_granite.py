"""Operations and bytes the two serving programs of a dense hybrid stack
need, computed from shapes: Mamba-2 mixers (M) and grouped-query
attention (*) as the configuration's pattern string orders them, a gated
MLP in every layer, and a tied head (`granite-4.0-h-micro`).

`sh` is `harness.shapes(config)`: pattern, hidden, vocab, ffn, heads,
kv_heads, head_dim, mamba_heads, mamba_head_dim, mamba_groups,
mamba_state, conv_kernel, scan_chunk.

Conventions (benchmark/flops.py's and flops_hybrid.py's, whose counts of
a mixer's weights, state and paged attention are called, not repeated): a
multiply-add is 2 operations; every matrix a program uses is read once
whatever the tokens; a causal product counts the keys a query may see and
no others; norms, biases, the embedding rows, activations and the
elementwise decay of the scan are left out (under 1%).
"""
from . import flops_hybrid


def layer_counts(sh):
    return sh["pattern"].count("M"), sh["pattern"].count("*")


def mlp_weights(sh):
    """Gate and up as one matrix, and down."""
    return 3 * sh["hidden"] * sh["ffn"]


def matmul_weights(sh):
    """Weights that multiply every token: the mixers' projections (the
    conv taps with them, as `flops_hybrid.mamba_weights` counts), the
    MLPs and the head (the embedding, counted once, as the head)."""
    n_m, n_a = layer_counts(sh)
    return (n_m * flops_hybrid.mamba_weights(sh)
            + n_a * flops_hybrid.attention_weights(sh)
            + (n_m + n_a) * mlp_weights(sh) + sh["hidden"] * sh["vocab"])


def scan_ops_per_token(sh):
    """Operations one token costs one Mamba mixer's chunked scan at the
    configuration's scan chunk L: c.b^T over the (L + 1) / 2 positions of
    the chunk a token may see (2 N a group) and the weighted sum of their
    x (2 P a head): the chunk-local products; what the carried state
    contributes, y += S c (2 P N a head); and the token's part of the
    state's update (2 P N a head)."""
    h, p = sh["mamba_heads"], sh["mamba_head_dim"]
    n, g = sh["mamba_state"], sh["mamba_groups"]
    seen = (sh["scan_chunk"] + 1) / 2.0
    return seen * (2.0 * n * g + 2.0 * p * h) + 4.0 * h * p * n


def prefill_chunk_cost(sh, tokens, attended, itemsize=2):
    """(operations, bytes) of one prefill chunk that carries `tokens`
    prompt tokens whose queries together attend `attended` cached
    positions (a layer): every matmul weight but the head's at 2
    operations a token, the head once (the frontier row), the scans, and
    q.k and p.v over the attended positions; bytes are every weight read
    once, one slot's records read and written, and the K and V rows up
    to the chunk's last query read once a chunk (the queries share
    them: the mean query's positions and half the chunk more)."""
    n_m, n_a = layer_counts(sh)
    head = sh["hidden"] * sh["vocab"]
    state, taps = flops_hybrid.mamba_state_elements(sh)
    ops = (2.0 * tokens * (matmul_weights(sh) - head) + 2.0 * head
           + n_m * tokens * scan_ops_per_token(sh)
           + n_a * 4.0 * sh["heads"] * sh["head_dim"] * attended)
    keys = attended / max(tokens, 1.0) + tokens / 2.0
    nbytes = (itemsize * matmul_weights(sh)
              + n_m * 2.0 * (4 * state + itemsize * taps)
              + n_a * 2.0 * sh["kv_heads"] * sh["head_dim"] * itemsize
              * keys)
    return ops, nbytes


def decode_wave_cost(sh, lanes, attended_tokens, slots, itemsize=2):
    """(operations, bytes) of one decode wave over `lanes` decoding lanes
    that together attend `attended_tokens` cached positions, in an engine
    of `slots` slots: every weight and the head are read once and cost 2
    operations a lane; the state update S = decay S + (dt x) b^T is 3
    operations an element and y = S c two more, a lane; the wave's
    program reads and writes the record of EVERY slot, decoding or not
    (state float32, conv taps in the weights' type); the attended K and
    V rows are read once (`flops_hybrid.paged_attention_cost`)."""
    n_m, _ = layer_counts(sh)
    state, taps = flops_hybrid.mamba_state_elements(sh)
    attn_ops, attn_bytes = flops_hybrid.paged_attention_cost(
        sh, attended_tokens, itemsize)
    ops = (2.0 * lanes * matmul_weights(sh) + n_m * lanes * 5.0 * state
           + attn_ops)
    nbytes = (itemsize * matmul_weights(sh)
              + n_m * slots * 2.0 * (4 * state + itemsize * taps)
              + attn_bytes)
    return ops, nbytes
