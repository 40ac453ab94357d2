"""nlp.GraniteHybridForCausalLM — 9 Mamba-2 layers and one attention
layer without positions in one period of ten, a gated MLP behind scaled
residuals in each, a tied head — on the normal path and through the
paged engine.

Everything runs at a tiny size on the CPU. The yardstick is
`benchmark/reference/granite_hybrid.py`, the plain float32 forward written
from the equations (a sequential recurrence, a full softmax in blocks of
query rows), which shares no code with the program.

Tolerances, all relative to the largest reference logit:
  * 2e-5 where both sides are float32 and differ only in the order of
    their sums (chunked scan against the recurrence, the paged path
    against the full forward): float32 rounds at 6e-8 and a logit sums a
    few thousand products over ten layers;
  * 3e-2 where the program's parameters, activations and K/V are
    bfloat16 (state, dt, decay and softmax stay float32): a bfloat16
    value is off by up to 2^-9 = 0.2% of itself, every matmul's output
    is rounded again (about six a layer, ten layers and the head), and
    the errors add up like a random walk: sqrt(60) x 0.2% = 1.5% of the
    largest logit is what to expect and 0.5% to 1.5% what three seeds
    of the cases below read; the reference with 8-bit matmul operands
    reads 4.6% to 5.8% on the same seeds. 3% lies between, a factor of
    two above the one and 1.5 below the other
    (`test_the_bfloat16_tolerance_is_not_idle`).
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from benchmark.reference import granite_hybrid as ref
from paddle_tpu.nlp import GraniteHybridConfig, GraniteHybridForCausalLM
from paddle_tpu.serving import PagedServingEngine, Scheduler

# an engine chunk (16) is two of the model's scan chunks (8)
VOCAB, MAX_LEN, BLOCK, CHUNK = 96, 96, 8, 16
TOL, TOL_BF16 = 2e-5, 3e-2
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
SIZES = dict(vocab_size=VOCAB, hidden_size=64, layer_types=PERIOD,
             num_attention_heads=4, num_key_value_heads=2,
             shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=8, initializer_range=0.2)


def _model(seed=11, **over):
    """A seeded tiny model whose vectors are off their neutral values,
    so that A, D, the dt bias and the conv bias all take part."""
    pt.seed(seed)
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        **{**SIZES, **over}))
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if len(p.shape) == 1 and not name.endswith("norm_weight"):
            p.set_value((np.asarray(p._data, np.float32)
                         + rng.normal(0, 0.3, p.shape)).astype(p._data.dtype))
    return model.eval()


def _cfg(model, **over):
    """The configuration file's keys, as the reference reads them."""
    c = model.cfg
    return {"layer_types": list(c.layer_types), "num_local_experts": 0,
            **{k: getattr(c, k) for k in (
                "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
                "mamba_d_state", "mamba_d_conv", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier",
                "logits_scaling", "rms_norm_eps")}, **over}


def _reference_logits(model, ids, cfg_over=None, **control):
    state = {n: p._data for n, p in model.named_parameters()}
    rw = ref.from_state_dict(state, model.cfg.num_layers)
    return np.asarray(ref.forward(rw, np.asarray(ids),
                                  _cfg(model, **(cfg_over or {})),
                                  **control))


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want)
    assert got.shape == want.shape
    return np.abs(got - want).max() / np.abs(want).max()


def _close(got, want, tol=TOL):
    assert _gap(got, want) <= tol


@pytest.fixture(scope="module")
def model():
    return _model()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("seq", [5, 21, 40])
def test_forward_equals_the_reference(model, seq):
    """Sequences under one scan chunk, of several with a ragged last
    one, and of whole chunks."""
    ids = np.random.default_rng(seq).integers(0, VOCAB, (2, seq))
    _close(model(ids)._data, _reference_logits(model, ids))


@pytest.mark.parametrize("key,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("attention_multiplier", 1.0),
    ("attention_multiplier", 1.0 / math.sqrt(16))])
def test_each_multiplier_takes_part(model, key, neutral):
    """The reference with one multiplier left at 1 (the attention's
    scale also at 1/sqrt(head_dim)) is not what the model computes: a
    model that left it there would fail `test_forward_equals_..`."""
    ids = np.random.default_rng(1).integers(0, VOCAB, (2, 24))
    assert getattr(model.cfg, key) != neutral
    wrong = _reference_logits(model, ids, {key: neutral})
    assert _gap(model(ids)._data, wrong) > 100 * TOL


def test_the_head_is_the_embedding(model):
    names = set(model.state_dict())
    assert "embeddings" in names and not any("lm_head" in n for n in names)
    # the logits move with the embedding alone
    ids = np.asarray([_prompt(3, 6)])
    before = np.asarray(model(ids)._data)
    kept = model.embeddings._data
    try:
        model.embeddings.set_value(kept.at[7].multiply(2.0))
        after = np.asarray(model(ids)._data)
    finally:
        model.embeddings.set_value(kept)
    assert 7 not in ids and not np.allclose(after[..., 7], before[..., 7])


def test_a_bfloat16_state_fails_the_float32_tolerance(model):
    """The reference with its Mamba state rounded to bfloat16 after every
    step, in the program's place: the float32 tolerance refuses it."""
    ids = np.random.default_rng(2).integers(0, VOCAB, (2, 24))
    lower = _reference_logits(model, ids, state="bfloat16")
    assert _gap(lower, _reference_logits(model, ids)) > 10 * TOL
    assert _gap(model(ids)._data, lower) > 10 * TOL


def test_parameters_are_created_in_the_named_dtype():
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        **{**SIZES, "param_dtype": "bfloat16", "init_weights": False}))
    for name, p in model.named_parameters():
        assert p._data.dtype == jnp.bfloat16, name
        if len(p.shape) > 1:
            assert not np.asarray(p._data, np.float32).any(), name
    assert model.paged_layout().slot_record is True


def test_the_paged_layout(model):
    """One group of K/V layers (the period's one attention layer), a
    record a slot, no experts; no prefix sharing."""
    from paddle_tpu.nn.paged_layout import AttentionGroup
    layout = model.paged_layout()
    assert layout.groups == (AttentionGroup(1, "kv", 4, 2, 16),)
    assert layout.window is None and layout.slot_record
    assert (layout.moe_picks, layout.mhc_rows_mixed,
            layout.experts_stored) == (0, 0, 0)
    assert layout.chunk_calls(CHUNK) == (0, 0)
    assert layout.counts_work and not layout.shares_prefixes
    assert "recurrent state a slot" in layout.refusal("a hand-off")


@pytest.mark.parametrize("kw,what", [
    (dict(num_local_experts=1), "num_local_experts 1"),
    (dict(num_experts_per_tok=2), "num_local_experts"),
    (dict(layer_types=("mamba", "moe")), "layer_types"),
    (dict(num_hidden_layers=9), "length"),
    (dict(num_key_value_heads=3), "not divisible"),
    (dict(position_embedding_type="rope"), "nope"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(mamba_expand=3), "mamba_expand"),
])
def test_config_refuses_what_the_stack_does_not_have(kw, what):
    with pytest.raises(ValueError, match=what):
        GraniteHybridConfig(**{**SIZES, **kw})


# ---------------------------------------------------- through the engine
class _Logits:
    """Records the logits of every model call an uncompiled engine makes."""

    def __init__(self, model):
        self.model, self.chunks, self.waves = model, [], []
        for name, log in (("prefill_chunk", self.chunks),
                          ("decode_step", self.waves)):
            inner = getattr(model, name)

            def spy(*a, _inner=inner, _log=log, **k):
                logits, caches = _inner(*a, **k)
                _log.append(np.asarray(logits, np.float32))
                return logits, caches
            setattr(model, name, spy)

    def restore(self):
        del self.model.prefill_chunk, self.model.decode_step


def _served_logits(model, prompt, tokens, cache_dtype=None):
    """(chunk logits, wave logits, tokens served) of one request through
    an uncompiled engine's own programs."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK,
                             cache_dtype=cache_dtype, jit_compile=False)
    spy = _Logits(model)
    try:
        out = Scheduler(eng).generate(prompt, max_tokens=tokens)
    finally:
        spy.restore()
    return spy.chunks, spy.waves, out


@pytest.mark.parametrize("n", [5, 37, 48])
def test_prefill_in_chunks_then_decode_equals_the_reference(model, n):
    """Prompts of under one engine chunk, of three with the last one
    padded (16 + 16 + 5: each whole chunk is two scan chunks, so state
    and conv taps are carried both inside a program and between
    programs) and of exactly three, then six decoded tokens: every
    logit row against the reference's full forward over prompt +
    tokens, in float32."""
    prompt = _prompt(n, n)
    chunks, waves, out = _served_logits(model, prompt, 7)
    want = _reference_logits(model, [prompt + out])[0]
    assert len(chunks) == -(-n // CHUNK) and len(waves) == 6
    _close(chunks[-1][0, 0], want[n - 1])
    for i, lo in enumerate(waves):
        # slot 0 is the request's lane; the other lane is inactive
        _close(lo[0, 0], want[n + i])
    assert out == [int(np.argmax(want[n - 1 + i])) for i in range(7)]


@pytest.fixture(scope="module")
def model_bf16():
    return _model(param_dtype="bfloat16")


@pytest.mark.parametrize("n", [37, 48])
def test_the_bfloat16_program_stays_near_the_reference(model_bf16, n):
    """The same with bfloat16 parameters, activations and K/V pool
    (float32 state), against the float32 reference of the same weights:
    logits and not tokens, within `TOL_BF16` (the module's docstring
    gives its reason)."""
    prompt = _prompt(n, n)
    chunks, waves, out = _served_logits(model_bf16, prompt, 7,
                                        cache_dtype=jnp.bfloat16)
    want = _reference_logits(model_bf16, [prompt + out])[0]
    _close(chunks[-1][0, 0], want[n - 1], TOL_BF16)
    for i, lo in enumerate(waves):
        _close(lo[0, 0], want[n + i], TOL_BF16)


def test_the_bfloat16_tolerance_is_not_idle(model_bf16):
    """What `TOL_BF16` still refuses: the reference with every matmul
    operand in 8 bits (the benchmark's control forward), and a forward
    that leaves the residual multiplier at 1."""
    ids = [_prompt(37, 37)]
    want = _reference_logits(model_bf16, ids)
    assert _gap(_reference_logits(model_bf16, ids, lower="float8_e4m3fn"),
                want) > TOL_BF16
    assert _gap(_reference_logits(model_bf16, ids,
                                  {"residual_multiplier": 1.0}),
                want) > TOL_BF16


def test_an_inactive_lane_keeps_its_record(model):
    """A decode step over two lanes of which one decodes: the other
    lane's record (state and conv taps, all nine Mamba layers) comes
    out bit for bit as it went in."""
    caches = model.init_paged_cache(5, BLOCK, MAX_LEN, num_slots=2)
    rng = np.random.default_rng(4)
    caches["state"] = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(0, 1, a.shape), a.dtype),
        caches["state"])
    assert len(caches["state"]) == 9 and len(caches["kv"]) == 1
    tables = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    _, new = model.decode_step(
        jnp.asarray([[3], [4]]), caches, jnp.asarray([9, 0]), tables,
        jnp.asarray([True, False]))
    for old, rec in zip(caches["state"], new["state"]):
        for k in ("ssm", "conv"):
            assert np.array_equal(rec[k][1], old[k][1])
            assert not np.array_equal(rec[k][0], old[k][0])


def test_a_reused_slot_answers_like_a_fresh_engine(model):
    def engine():
        return PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                                  block_size=BLOCK, prefill_chunk_len=CHUNK)
    sched = Scheduler(engine())
    first = sched.generate(_prompt(1, 40), max_tokens=5)
    again = sched.generate(_prompt(2, 9), max_tokens=5)
    assert first == Scheduler(engine()).generate(_prompt(1, 40),
                                                 max_tokens=5)
    assert again == Scheduler(engine()).generate(_prompt(2, 9),
                                                 max_tokens=5)
    health = sched.engine._health()
    # 9 Mamba layers x 2 slots x (8 x 16 x 16 float32 + 3 x 160 float32)
    assert health["slot_state"] and health["state_bytes"] == \
        9 * 2 * (8 * 16 * 16 + 3 * 160) * 4
