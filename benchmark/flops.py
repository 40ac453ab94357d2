"""Operations and bytes that the algorithms need, computed from shapes.

Kept with the benchmark because the program's own cost analysis reads
`None` on a TPU, and because a PR that claims a gain may not move the
yardstick. `sh` is `harness.shapes(config)`: layers, hidden, heads,
kv_heads, head_dim, ffn, ffn_matrices (2: GELU MLP, 3: SwiGLU), vocab,
window, tied_head.

Conventions: a multiply-add is 2 operations; causal attention counts the
keys a query may see and no others; recomputation counts for nothing.
"""


def matmul_params(sh):
    """Weights that take part in a matrix multiplication for every token:
    the blocks' projections and the head. The embedding look-ups, norms
    and biases do none; a tied head is counted once, as the head."""
    q = sh["heads"] * sh["head_dim"]
    kv = sh["kv_heads"] * sh["head_dim"]
    block = (sh["hidden"] * (q + 2 * kv)            # q, k, v projections
             + q * sh["hidden"]                     # output projection
             + sh["ffn_matrices"] * sh["hidden"] * sh["ffn"])
    return sh["layers"] * block + sh["hidden"] * sh["vocab"]


def mean_attended_keys(seq, window=None):
    """Mean over query positions 0..seq-1 of the keys each may see:
    min(position + 1, window)."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    full = seq - window                      # queries that see `window`
    return (window * (window + 1) / 2.0 + full * window) / seq


def attention_flops_per_token(sh, seq):
    """Forward q.k and p.v for one token, all layers: 2 matmuls x 2
    operations x (heads x head_dim) x keys seen."""
    return (4.0 * sh["layers"] * sh["heads"] * sh["head_dim"]
            * mean_attended_keys(seq, sh.get("window")))


def train_flops_per_token(sh, seq):
    """Forward plus backward (twice the forward): 6 per matmul weight,
    plus three times the forward attention."""
    return 6.0 * matmul_params(sh) + 3.0 * attention_flops_per_token(sh, seq)


def mfu(flops_per_token, tokens_per_s, chips, peak_flops):
    return flops_per_token * tokens_per_s / (chips * peak_flops)


# --------------------------------------------------------------- kernels
def flash_train_cost(sh, batch, seq, itemsize=2):
    """(operations, bytes) the flash-attention algorithm needs for one
    training step, all layers: the forward's 2 matmuls and the backward's
    5 (s is needed again for dp; dv, dp, dq, dk), over the causal band;
    bytes are q, k, v, o read or written once forward and q, k, v, o, do
    read and dq, dk, dv written backward."""
    qd = sh["heads"] * sh["head_dim"]
    per_matmul = 2.0 * batch * seq * qd * mean_attended_keys(
        seq, sh.get("window"))
    ops = sh["layers"] * 7.0 * per_matmul
    tensor = batch * seq * qd * itemsize
    return ops, sh["layers"] * (4 + 8) * tensor


def paged_decode_cost(sh, attended_tokens, itemsize=2):
    """(operations, bytes) of one decode wave's attention, all layers,
    when the lanes together attend `attended_tokens` cached positions:
    each position's K and V row of every kv head is read once; q.k and
    p.v are 2 operations each per query head and element."""
    kv_row = sh["kv_heads"] * sh["head_dim"]
    q_row = sh["heads"] * sh["head_dim"]
    return (sh["layers"] * 4.0 * q_row * attended_tokens,
            sh["layers"] * 2.0 * kv_row * itemsize * attended_tokens)


def decode_wave_cost(sh, lanes, attended_tokens, itemsize=2):
    """(operations, bytes) of one decode wave of a dense decoder over
    `lanes` decoding lanes that together attend `attended_tokens` cached
    positions: every matmul weight and the head are read once whatever
    the lanes and cost 2 operations a lane; the K and V rows the lanes
    attend are read once (`paged_decode_cost`). Norms, biases, embedding
    rows, activations and the new K/V rows written are left out (under
    1% at 64 lanes). The whole step's count: a kernel taken off the path
    leaves it as it is."""
    attn_ops, attn_bytes = paged_decode_cost(sh, attended_tokens, itemsize)
    weights = matmul_params(sh)
    return (2.0 * lanes * weights + attn_ops,
            itemsize * weights + attn_bytes)


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take, and which of the two bounds
    it: ("compute" | "memory")."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
