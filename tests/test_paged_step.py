"""The one paged step of a model file, at the three widths the paged
engines run it at.

`GPTForPretraining` and `LlamaForCausalLM` answer the engines through
two names, `decode_step(tok, caches, pos, block_tables=)` and
`prefill_chunk(tok_chunk, caches, block_tables, chunk_start, valid_len)`,
and both are one body (`paged_step` at every level below the head): the
decode wave is its C == 1 call, the prefill chunk its B == 1 call, the
speculative verify span its [S, k + 1] call. Held here, for each family
and both cores: the logits of the valid rows are the model's own dense
`forward` over the same tokens, and whichever width wrote them, the
pools hold the same K/V rows at the written positions and nothing past
them.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.nn import paged_attention as pa

VOCAB, MAX_LEN, BLOCK, LANES, LENGTH = 96, 64, 8, 4, 40
NBLK = MAX_LEN // BLOCK
STARTS = np.asarray([3, 8, 17, 30], np.int32)    # ragged, one on a page edge


def _build(family):
    from paddle_tpu.nlp import (GPTConfig, GPTForPretraining, LlamaConfig,
                                LlamaForCausalLM)
    pt.seed(29)
    if family == "gpt":
        model = GPTForPretraining(GPTConfig(
            vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=MAX_LEN, dropout=0.0, attn_dropout=0.0))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=MAX_LEN,
            attn_window=12 if family == "llama-window" else None))
    model.eval()
    return model


@pytest.fixture(scope="module", params=["gpt", "llama", "llama-window"])
def case(request):
    """(model, tokens [LANES, LENGTH], the dense forward's logits, the
    K/V rows [layer][LANES, Hkv, MAX_LEN, 2D] one full-width call of the
    oracle core stores for them)."""
    import jax.numpy as jnp
    model = _build(request.param)
    tokens = np.random.default_rng(31).integers(
        0, VOCAB, (LANES, LENGTH)).astype(np.int32)
    dense = np.asarray(model(Tensor(jnp.asarray(tokens)))._data, np.float32)
    with pa.kernel_scope("reference"):
        _, pools = model.prefill_chunk(
            Tensor(jnp.asarray(tokens)), _pools(model), _tables(LANES),
            jnp.zeros(LANES, jnp.int32), jnp.full(LANES, LENGTH, jnp.int32))
    return model, tokens, dense, _rows(pools, _tables(LANES))


def _pools(model):
    import jax.numpy as jnp
    return model.init_paged_cache(LANES * NBLK + 1, BLOCK, MAX_LEN,
                                  dtype=jnp.float32)


def _tables(lanes):
    import jax.numpy as jnp
    return jnp.asarray(1 + np.random.default_rng(37).permutation(
        LANES * NBLK).reshape(LANES, NBLK)[:lanes], jnp.int32)


def _rows(pools, tables):
    """Each layer's pool as the lanes see it: [lanes, Hkv, MAX_LEN, 2D]."""
    return [np.concatenate([np.asarray(a) for a in
                            pa.gather_block_kv(pool, tables)], axis=-1)
            for pool in pools]


def _written_history(model, tokens, tables, upto):
    """Fresh pools holding positions [0, upto[lane]) of every lane, by
    one full-width call of the step itself."""
    import jax.numpy as jnp
    lanes = len(upto)
    _, pools = model.prefill_chunk(
        Tensor(jnp.asarray(tokens[:lanes])), _pools(model), tables,
        jnp.zeros(lanes, jnp.int32), jnp.asarray(upto, jnp.int32))
    return pools


@pytest.mark.parametrize("core", pa.KERNELS)
@pytest.mark.parametrize("shape", ["wave", "chunk", "span"])
def test_paged_step_gives_the_dense_forward(case, shape, core):
    import jax.numpy as jnp
    model, tokens, dense, want_rows = case
    with pa.kernel_scope(core):
        if shape == "wave":
            # four lanes at ragged positions, one token each, through
            # the head's decode entry: its C == 1 call
            tables, start, valid = _tables(LANES), STARTS, np.ones(LANES, int)
            pools = _written_history(model, tokens, tables, start)
            tok = tokens[np.arange(LANES), start][:, None]
            logits, pools = model.decode_step(
                Tensor(jnp.asarray(tok)), pools, Tensor(jnp.asarray(start)),
                block_tables=tables)
        elif shape == "chunk":
            # one lane, 16 tokens at chunk_start 16 of which 11 are the
            # prompt's, scalars as the chunk program passes them
            tables, start, valid = _tables(1), np.asarray([16]), [11]
            pools = _written_history(model, tokens, tables, start)
            logits, pools = model.prefill_chunk(
                Tensor(jnp.asarray(tokens[:1, 16:32])), pools, tables,
                jnp.int32(16), jnp.int32(11))
        else:
            # the verify wave: four lanes, five tokens each at per-lane
            # starts, spans clamped to ragged lengths
            tables, start = _tables(LANES), STARTS
            valid = np.asarray([5, 3, 1, 4])
            pools = _written_history(model, tokens, tables, start)
            tok = np.stack([tokens[i, s:s + 5] for i, s in enumerate(start)])
            logits, pools = model.prefill_chunk(
                Tensor(jnp.asarray(tok)), pools, tables,
                jnp.asarray(start), jnp.asarray(valid, jnp.int32))
    logits = np.asarray(logits._data, np.float32)
    got_rows = _rows(pools, tables)
    for lane, (s, n) in enumerate(zip(start, valid)):
        np.testing.assert_allclose(logits[lane, :n], dense[lane, s:s + n],
                                   rtol=1e-4, atol=1e-4)
        for got, want in zip(got_rows, want_rows):
            np.testing.assert_allclose(got[lane, :, :s + n],
                                       want[lane, :, :s + n],
                                       rtol=1e-4, atol=1e-4)
            assert (got[lane, :, s + n:] == 0).all()     # and nothing past
