"""Operations and bytes one decode wave and one prefill chunk need of a
decoder with latent attention, a low-rank query, gated routed experts and
`hc_streams` residual streams mixed by input-dependent maps
(manifold-constrained hyper-connections), computed from shapes.

`sh` is `harness.shapes(config)`: `flops_mla_moe`'s keys and `q_rank`,
`hc_streams`. What such a model shares with the plain latent one is
`flops_mla_moe`'s count, taken as it stands: the experts touched, the
gated experts, the latent rows attended and expanded, the head. What
differs is counted here:

  * the query: `W_qa` [hidden, q_rank] and `W_qb` [q_rank, heads x (nope
    + rope)] in the place of one full-rank matrix;
  * every sub-layer (attention, MLP: 2 a layer) of n streams: the map
    product `vec(x) Phi`, Phi [n hidden, 2n + n^2] read once; the pre-mix
    (n multiply-adds a value), the res map and the post term (n^2 + n);
    the streams read once and written once, n hidden values each way a
    token. The 20 Sinkhorn iterations on a 4 x 4 matrix are 1,280
    operations a token a sub-layer, 0.2% of the map product: left out,
    like the norms.

Conventions: benchmark/flops.py's and flops_mla_moe's.
"""
from . import flops_mla_moe as base

SUBLAYERS = 2                   # attention and the MLP, each wrapped


def query_weights(sh):
    """W_qa and W_qb of one layer."""
    return sh["q_rank"] * (sh["hidden"] + sh["heads"]
                           * (sh["nope_dim"] + sh["rope_dim"]))


def _query_delta(sh):
    """The factored query's weights less the full-rank matrix
    `flops_mla_moe` counts in its place, a layer."""
    return query_weights(sh) - sh["hidden"] * sh["heads"] * (
        sh["nope_dim"] + sh["rope_dim"])


def attention_weights(sh):
    return base.attention_weights(sh) + _query_delta(sh)


def map_weights(sh):
    """Phi of one sub-layer."""
    n = sh["hc_streams"]
    return n * sh["hidden"] * (2 * n + n * n)


def parameters(sh):
    """Every parameter of rank 2 and more (the matrices, the embedding
    and each sub-layer's n x n offset of the res map)."""
    n = sh["hc_streams"]
    return (base.parameters(sh) + sh["layers"] * (
        _query_delta(sh) + SUBLAYERS * (map_weights(sh) + n * n)))


def mix_cost(sh, tokens, itemsize=2):
    """(operations, bytes) of the maps and the two mixes of every
    sub-layer of the stack for `tokens` tokens."""
    n, h = sh["hc_streams"], sh["hidden"]
    subs = SUBLAYERS * sh["layers"]
    per_token = 2.0 * (map_weights(sh) + n * h + (n * n + n) * h)
    return (subs * tokens * per_token,
            subs * itemsize * (map_weights(sh) + 2.0 * tokens * n * h))


def _with(sh, tokens, cost, itemsize):
    """`flops_mla_moe`'s (operations, bytes) of a step of `tokens`
    tokens with the factored query in the full one's place and the
    mixing on top."""
    ops, nbytes = cost
    delta = sh["layers"] * _query_delta(sh)
    mix_ops, mix_bytes = mix_cost(sh, tokens, itemsize)
    return (ops + 2.0 * tokens * delta + mix_ops,
            nbytes + itemsize * delta + mix_bytes)


def decode_wave_cost(sh, lanes, attended_rows, itemsize=2):
    """(operations, bytes) of one decode wave over `lanes` decoding
    lanes that together attend `attended_rows` cached positions."""
    return _with(sh, lanes, base.decode_wave_cost(sh, lanes, attended_rows,
                                                  itemsize), itemsize)


def prefill_chunk_cost(sh, tokens, expanded_rows, itemsize=2):
    """(operations, bytes) of one prompt chunk of `tokens` tokens whose
    lane puts `expanded_rows` cached positions through the expansion."""
    return _with(sh, tokens, base.prefill_chunk_cost(
        sh, tokens, expanded_rows, itemsize), itemsize)
