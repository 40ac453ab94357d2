"""The plain float32 references against the program's own forward, at a
tiny size on the CPU, on the benchmark's seeded weights: the two must be
the same function. At float32 they agree to rounding (1e-5 of the largest
logit); the sequence is longer than the tiny window, so the sliding
window is in play for the Mistral block."""
import numpy as np
import pytest

from benchmark import harness, weights


def _tiny(name):
    cfg = harness.read_json(f"{harness.BENCH_DIR}/configs/{name}.json")
    cfg = harness.overlay(cfg, cfg["rehearse"])
    cfg["dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("name,seq", [("gpt2-small", 200),
                                      ("mistral-7b-d8", 200)])
def test_reference_equals_the_program_forward(name, seq):
    import jax.numpy as jnp
    cfg = _tiny(name)
    model, w = harness.build_model(cfg, seed=3)
    model.eval()
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, harness.shapes(cfg)["layers"])
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, seq)).astype(np.int32)
    want = np.asarray(ref.forward(rw, ids, cfg))
    params, buffers = model.functional_state()
    got = np.asarray(model.functional_call(params, buffers,
                                           jnp.asarray(ids))[0]._data)
    assert want.shape == got.shape == (2, seq, cfg["vocab_size"])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max() + 1e-6
    rows = [0, 17, seq - 1]
    np.testing.assert_allclose(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)), want[:, rows],
        atol=1e-6)


def test_mistral_window_changes_the_answer():
    """The tiny window (128) is shorter than the sequence: without it the
    late positions differ, so the test above does exercise the band."""
    cfg = _tiny("mistral-7b-d8")
    _, w = harness.build_model(cfg, seed=3)
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, 2)
    ids = np.random.default_rng(0).integers(0, 512, (1, 200)).astype(np.int32)
    banded = np.asarray(ref.forward(rw, ids, cfg))
    full = np.asarray(ref.forward(rw, ids, dict(cfg, sliding_window=None)))
    np.testing.assert_allclose(banded[:, :128], full[:, :128], atol=1e-6)
    assert np.abs(banded[:, 150:] - full[:, 150:]).max() > 1e-3


def test_weights_follow_the_seed_and_the_rule():
    spec = [("a.weight", (8, 4)), ("a.bias", (4,)), ("norm.weight", (4,))]
    one = weights.make_weights(spec, 5, std=0.5, dtype="float32")
    two = weights.make_weights(spec, 5, std=0.5, dtype="float32")
    other = weights.make_weights(spec, 6, std=0.5, dtype="float32")
    assert all(np.array_equal(one[k], two[k]) for k in one)
    assert not np.array_equal(one["a.weight"], other["a.weight"])
    assert np.all(np.asarray(one["a.bias"]) == 0)
    assert np.all(np.asarray(one["norm.weight"]) == 1)
    assert 0.2 < float(np.asarray(one["a.weight"]).std()) < 0.9
    assert weights.make_weights(spec, 5)["a.weight"].dtype.name == "bfloat16"


@pytest.mark.parametrize("name", ["gpt2-small", "mistral-7b-d8"])
def test_reference_loss_is_the_loss_the_program_trains_on(name):
    """The training kind holds the step's first loss to the reference's
    cross-entropy over the batch: that has to be the same mean the
    program's own loss function takes (next token, last position of each
    sequence left out)."""
    import paddle_tpu as paddle
    from benchmark.kinds import train

    class Ctx:
        config, seed = _tiny(name), 3
        cell = {"check": {"positions": 8, "logit_tol_bf16_steps": 8}}

    model, _ = harness.build_model(Ctx.config, seed=Ctx.seed)
    ids = np.random.default_rng(1).integers(
        0, Ctx.config["vocab_size"], (3, 96)).astype(np.int32)
    worst, tol, _, ref_loss = train._check_against_reference(Ctx, model, ids)
    assert worst <= 1e-5 <= tol
    x = paddle.to_tensor(ids)
    loss_fn = harness.import_attr(Ctx.config["program"]["loss"])
    assert float(loss_fn(model(x), x).numpy()) == pytest.approx(ref_loss,
                                                                abs=1e-5)
