"""The sampling tail runs its sort, filter and draw only where some lane
samples (PR 27, `serving/engine.py::_if_any_samples`): a lane's token may
not depend on which branch ran."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.serving import engine as eng_mod

LANES, VOCAB = 4, 64


def _wave(sample, seed=0):
    rng = np.random.default_rng(seed)
    lo = jnp.asarray(rng.normal(size=(LANES, VOCAB)), jnp.float32)
    return eng_mod._select_wave_tokens(
        lo, jnp.zeros(LANES, jnp.int32), jnp.arange(LANES, dtype=jnp.int32),
        jnp.ones(LANES, bool), jnp.asarray(sample, bool),
        jnp.full(LANES, 0.7, jnp.float32), jnp.full(LANES, 8, jnp.int32),
        jnp.full(LANES, 0.9, jnp.float32),
        jnp.zeros((LANES, VOCAB), jnp.float32), jnp.zeros(LANES, bool),
        jax.random.PRNGKey(3)), lo


def _unconditional(lo, sample):
    """The tail as it was before the `lax.cond`: filter and draw for every
    lane, greedy lanes choosing the argmax afterwards."""
    scaled = lo / 0.7
    drawn = jax.random.categorical(
        jax.random.PRNGKey(3), eng_mod._filter_top_k_top_p(
            scaled, jnp.full(LANES, 8, jnp.int32),
            jnp.full(LANES, 0.9, jnp.float32)), axis=-1)
    return np.where(sample, np.asarray(drawn), np.asarray(lo.argmax(-1)))


@pytest.mark.parametrize("sample", [
    [False] * LANES, [True] * LANES, [False, True, False, False]],
    ids=["all-greedy", "all-sampled", "mixed"])
def test_wave_tokens_equal_the_unconditional_tail(sample):
    (nxt, new_pos, finite), lo = _wave(sample)
    assert np.array_equal(np.asarray(nxt), _unconditional(lo, sample))
    assert np.array_equal(np.asarray(new_pos), np.arange(LANES) + 1)
    assert np.asarray(finite).all()


def test_a_greedy_wave_does_not_run_the_filter():
    """The sort is inside the conditional: the lowered program holds it
    in a branch, and a greedy wave takes the other."""
    calls = []

    def draw():
        calls.append(1)
        return jnp.full(LANES, 5, jnp.int32)

    greedy = jnp.arange(LANES, dtype=jnp.int32)
    out = eng_mod._if_any_samples(jnp.zeros(LANES, bool), draw, greedy)
    assert np.array_equal(np.asarray(out), np.arange(LANES))
    out = eng_mod._if_any_samples(jnp.asarray([False, True, False, False]),
                                  draw, greedy)
    assert np.array_equal(np.asarray(out), np.full(LANES, 5))
    text = jax.jit(lambda lo, s: eng_mod._select_first_token(
        lo, s, jnp.float32(1.0), jnp.int32(4), jnp.float32(0.9),
        jnp.zeros(VOCAB, jnp.float32), jax.random.PRNGKey(0))).lower(
            jnp.zeros(VOCAB, jnp.float32), jnp.bool_(False)).as_text()
    assert "case" in text or "cond" in text or "if" in text


@pytest.mark.parametrize("sample", [False, True])
def test_first_token_equals_the_unconditional_tail(sample):
    rng = np.random.default_rng(1)
    lo = jnp.asarray(rng.normal(size=(VOCAB,)), jnp.float32)
    key = jax.random.PRNGKey(9)
    got = eng_mod._select_first_token(
        lo, jnp.bool_(sample), jnp.float32(0.8), jnp.int32(5),
        jnp.float32(0.95), jnp.zeros(VOCAB, jnp.float32), key)
    drawn = jax.random.categorical(key, eng_mod._filter_top_k_top_p(
        (lo / 0.8)[None, :], jnp.int32(5)[None], jnp.float32(0.95)[None])[0])
    assert int(got) == (int(drawn) if sample else int(lo.argmax()))
