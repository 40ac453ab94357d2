"""The hybrid configuration's files: its reference against the program
at the `rehearse` sizes, the wave's operations and bytes and the two new
readers against numbers worked out by hand, and the cell's rehearsal."""
import contextlib
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_hybrid, harness
from benchmark.kinds import _serving
from benchmark.layer_metrics import (hybrid_wave_mfu,
                                     moe_experts_device_share,
                                     moe_experts_roofline,
                                     state_host_ms_per_round)

CONFIG = "nemotron3-nano-30b-d9"
CELL = "nemotron3n-serve-reason"


def _tiny():
    cfg = harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")
    cfg = harness.overlay(cfg, cfg["rehearse"])
    cfg["dtype"] = "float32"
    cfg["program"] = harness.overlay(
        cfg["program"], {"kwargs": {"param_dtype": "float32"}})
    return cfg


def test_reference_equals_the_program_forward():
    """Both float32, on the benchmark's seeded weights (so A = -e, D = 1
    and the experts' `up` matrices stored two side by side): the same
    function to rounding, 2e-5 of the largest logit (float32 sums of a
    few thousand products over five blocks, in another order)."""
    import jax.numpy as jnp
    cfg = _tiny()
    model, w = harness.build_model(cfg, seed=3)
    model.eval()
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, harness.shapes(cfg)["layers"])
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 75)).astype(np.int32)
    want = np.asarray(ref.forward(rw, ids, cfg))
    params, buffers = model.functional_state()
    got = np.asarray(model.functional_call(params, buffers,
                                           jnp.asarray(ids))[0]._data)
    assert want.shape == got.shape == (2, 75, cfg["vocab_size"])
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max() + 1e-6
    rows = [0, 17, 74]
    np.testing.assert_allclose(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)), want[:, rows],
        atol=1e-6)


def test_the_configuration_is_the_published_one_but_for_its_depth():
    import json
    cfg = harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "hybrid_override_pattern"}
    assert cfg["hybrid_override_pattern"] == \
        row["config"]["hybrid_override_pattern"][:9]
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]


SH = {"pattern": "ME*", "hidden": 4, "vocab": 10, "heads": 2, "kv_heads": 1,
      "head_dim": 2, "mamba_heads": 2, "mamba_head_dim": 2,
      "mamba_groups": 1, "mamba_state": 3, "conv_kernel": 2, "experts": 4,
      "experts_per_token": 2, "expert_width": 3, "shared_width": 5}


def test_wave_cost_equals_the_hand_computed_numbers():
    # Mamba: d_inner 4, conv_dim 10: in_proj 4 x 16, taps 2 x 10,
    # out_proj 4 x 4; state 4 x 3 elements and 10 taps a lane
    assert flops_hybrid.mamba_weights(SH) == 64 + 20 + 16
    assert flops_hybrid.mamba_state_elements(SH) == (12, 10)
    # attention: q 4, kv 2: qkv 4 x 8, o 4 x 4
    assert flops_hybrid.attention_weights(SH) == 48
    # 2 lanes choosing 2 of 4: 4 (1 - 0.5^2) = 3 experts touched
    assert flops_hybrid.experts_touched(SH, 2) == pytest.approx(3.0)
    ops, nbytes = flops_hybrid.decode_wave_cost(SH, lanes=2,
                                                attended_tokens=7)
    # a token multiplies 100 (M) + 48 (*) + 16 + 2 x 24 + 40 (E) + 40
    # (head) = 292 weights; 2 lanes x 2; state 2 lanes x 5 x 12;
    # attention 4 x 4 x 7
    assert ops == pytest.approx(2 * 2 * 292 + 2 * 5 * 12 + 4 * 4 * 7)
    # read once: 100 + 48 + 16 + 3 x 24 + 40 + 40 = 316 weights of 2
    # bytes; a lane's state and taps read and written 2 x (4 x 12 + 2 x
    # 10); K and V rows of 2 x 2 bytes over 7 positions
    assert nbytes == pytest.approx(2 * 316 + 2 * 2 * 68 + 2 * 2 * 2 * 7)
    # all 128 experts' worth at the cell's size: 768 picks touch them all
    full = harness.shapes(harness.read_json(
        f"{harness.BENCH_DIR}/configs/{CONFIG}.json"))
    assert flops_hybrid.experts_touched(full, 128) == pytest.approx(
        127.7, abs=0.1)
    _, cell_bytes = flops_hybrid.decode_wave_cost(full, 128, 128 * 600)
    assert 13.4e9 < cell_bytes < 13.9e9


def _ctx(phases0, phases1):
    rounds = [(1.0, 2.0, 2, 6, 0, 5), (2.0, 3.0, 2, 8, 1, 5),
              (3.0, 4.0, 0, 0, 1, 5), (4.0, 5.0, 0, 0, 0, 5)]
    return {"shapes": SH, "cell": {"programs": {"decode": "decode_wave"}},
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
            "trace": {"module_s": {"decode_wave": [0.03, 0.02, 0.01]},
                      "kernel_s": {"pallas_other": 0.018,
                                   "paged_attention": 0.001},
                      "kernel_by_module": {
                          "decode_wave": {"pallas_other": 0.012},
                          "prefill_chunk": {"pallas_other": 0.006}},
                      "busy_s": 0.09},
            "trace_host": (0.5, 3.5),
            "obs": {"window": (0.0, 6.0), "rounds": rounds,
                    "snap0": {"phase_seconds": phases0},
                    "snap1": {"phase_seconds": phases1}}}


def test_hybrid_wave_mfu_equals_the_hand_computed_share():
    # the traced rounds with a lane decoding: 2 lanes, 7 positions on
    # average: 1400 operations, 960 bytes; memory binds, 9.6 ms of the
    # median wave's 20
    ctx = _ctx({}, {})
    assert hybrid_wave_mfu.read(ctx) == pytest.approx(48.0)
    assert (hybrid_wave_mfu.LAYER, hybrid_wave_mfu.SOURCE) == \
        ("hybrid_model_step", "device_trace")
    for gone in ("trace", "trace_host"):
        assert hybrid_wave_mfu.read({**ctx, gone: None}) is None
    assert hybrid_wave_mfu.read(
        {**ctx, "shapes": {"layers": 2}}) is None       # no block mix
    assert hybrid_wave_mfu.read(
        {**ctx, "trace": {"module_s": {}}}) is None     # no wave traced


def test_expert_kernel_readers_equal_the_hand_computed_numbers():
    # one call for 2 tokens: 4 picks x 24 weights x 2; 3 experts touched
    # x 24 weights x 2 bytes + 4 picks x 4 wide x (2 + 4) bytes
    ops, nbytes = flops_hybrid.expert_mlp_cost(SH, 2)
    assert (ops, nbytes) == (pytest.approx(192.0), pytest.approx(240.0))
    ctx = _ctx({}, {})
    # 18 ms of the kernel in 90 ms busy
    assert moe_experts_device_share.read(ctx) == pytest.approx(20.0)
    # one expert layer: 240 bytes at 1e5 a second = 2.4 ms least, against
    # 12 ms of the kernel in the 3 traced waves = 4 ms a wave
    assert moe_experts_roofline.read(ctx) == pytest.approx(60.0)
    for reader in (moe_experts_device_share, moe_experts_roofline):
        assert (reader.LAYER, reader.SOURCE) == ("moe_experts_kernel",
                                                 "device_trace")
        assert reader.read({**ctx, "trace": None}) is None
        # a program without the kernel: nothing to read, no error
        bare = {**ctx["trace"], "kernel_s": {}, "kernel_by_module": {}}
        assert reader.read({**ctx, "trace": bare}) is None


def test_state_host_ms_equals_the_hand_computed_number():
    # 30 ms of `state.reset` over the window's 3 rounds with work
    ctx = _ctx({"round": 1.0, "state.reset": 0.01},
               {"round": 2.0, "state.reset": 0.04})
    assert state_host_ms_per_round.read(ctx) == pytest.approx(10.0)
    # a program that keeps no slot state, one from before the finer
    # phases, a kind of cell without snapshots: nothing, and no error
    assert state_host_ms_per_round.read(
        _ctx({"round": 1.0}, {"round": 2.0})) is None
    assert state_host_ms_per_round.read(
        _ctx({"admission": 1.0}, {"admission": 2.0,
                                  "state.reset": 0.1})) is None
    assert state_host_ms_per_round.read({"obs": {"window": (0, 1)}}) is None


def test_the_cell_rehearses_green():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    last = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")][-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert "serve_tokens_per_s" in last["end_to_end"]
    assert "serve.state_host_ms_per_round" in last["per_layer"]


def _routed_ctx(check, config=None, notes=None, seed=1):
    import types
    return types.SimpleNamespace(
        seed=seed, trace=False, config=config or {"shapes": {"layers": 1}},
        cell={"check": check}, phase=lambda name: contextlib.nullcontext(),
        note=lambda what, **f: (notes if notes is not None else []).append(
            (what, f)))


def _record(prompt, tokens):
    import types
    return types.SimpleNamespace(
        request=types.SimpleNamespace(output_tokens=list(tokens)),
        planned=types.SimpleNamespace(prompt=list(prompt)))


def test_routed_kind_holds_the_mean_and_the_worst_gap(monkeypatch):
    """Three served tokens whose reference logits are known: gaps 0, 3.0
    and 0 under a largest logit of 4.0 (a bfloat16 step of 1/64), so the
    mean is 64 steps and the worst 192; each limit refuses alone. The
    control forward's tokens go through the same two limits."""
    import types
    from benchmark.kinds import serve_closed_routed as kind

    logits = np.asarray([[4.0, 1.0, 0.0], [3.5, 3.0, -1.0],
                         [2.0, 1.0, 3.0]], np.float32)
    # rows repeat the last position, so 3 tokens read rows 0, 1, 2 of a
    # 6-row forward whose rows come in pairs: logits[0], [0], [1]; the
    # control forward would have served tokens 2, 2, 1
    stub = types.SimpleNamespace(
        from_state_dict=lambda w, layers: None,
        forward=lambda rw, ids, cfg, rows=None, **control: np.repeat(
            logits[:, ::-1] if control else logits, 2,
            axis=0)[None][:, :len(rows)])
    monkeypatch.setattr(harness, "reference_for", lambda config: stub)

    def check(mean, worst):
        return {"requests": 1, "min_tokens": 1, "max_tokens": 6,
                "pad_to": 16, "mean_gap_tol_bf16_steps": mean,
                "logit_tol_bf16_steps": worst}

    picks = _serving.sampled(_routed_ctx(check(0, 0)),
                         [_record([5, 6], [0, 1, 0]), _record([1], [])])
    assert [r.planned.prompt for r in picks] == [[5, 6]]
    m = kind.measure(_routed_ctx(check(0, 0)), None, picks)
    # token 0 of [4, 1, 0]: 0; token 1 of [4, 1, 0]: 3.0; token 0 of
    # [3.5, 3, -1]: 0
    assert m["gaps"].tolist() == [0.0, 3.0, 0.0]
    assert (m["top"], m["same"]) == (4.0, 2)
    for limits, want in (((64, 192), True), ((63, 192), False),
                         ((64, 191), False)):
        ok, read = kind.verdict(check(*limits), m)
        assert ok is want
        assert read["mean_gap_steps"] == pytest.approx(64.0)
        assert read["worst_gap_steps"] == pytest.approx(192.0)
        assert read["argmax_match"] == pytest.approx(2 / 3)
    # the control's tokens: argmax of the reversed rows [0, 1, 4] twice
    # and [-1, 3, 3.5]: tokens 2, 2, 2 -> reference gaps 4, 4, 4.5
    c = kind.measure(_routed_ctx(check(0, 0)), None, picks,
                     control={"lower": "float8_e4m3fn"})
    assert c["gaps"].tolist() == [4.0, 4.0, 4.5] and c["same"] == 0
    assert kind.verdict(check(64, 192), c)[0] is False
    # nothing sampled, or a logit that is not finite: not correct
    assert kind.verdict(check(64, 192), {"gaps": np.zeros(0), "top": 0.0,
                                         "same": 0})[0] is False
    assert kind.verdict(check(64, 192), {"gaps": np.asarray([np.nan]),
                                         "top": 1.0, "same": 0})[0] is False


def test_routed_kind_adds_its_limit_to_serve_closed_verdict(monkeypatch):
    """`run` is `serve_closed.run` with `_serving.referee` tapped for the
    weights and records it is given, for that call only; the mean's limit
    can turn a verdict the worst gap let pass, and cannot mend one."""
    import types
    from benchmark.kinds import _serving, serve_closed_routed as kind

    logits = np.asarray([[4.0, 1.0, 0.0], [3.5, 3.0, -1.0]], np.float32)
    stub = types.SimpleNamespace(
        from_state_dict=lambda w, layers: None,
        forward=lambda rw, ids, cfg, rows=None, **kw: np.repeat(
            logits, 2, axis=0)[None][:, :len(rows)])
    monkeypatch.setattr(harness, "reference_for", lambda config: stub)
    records = [_record([5, 6], [0, 1, 0])]
    plain, seen = _serving.referee, []

    def closed_run(ctx, correct=True):
        seen.append(_serving.referee)
        worst, tol, _, n = _serving.referee(ctx, "weights", records)
        return {"correct": correct and n > 0 and worst <= tol}

    monkeypatch.setattr(kind.serve_closed, "run", closed_run)

    def run(mean, worst, **kw):
        notes = []
        ctx = _routed_ctx({"requests": 1, "min_tokens": 1, "max_tokens": 4,
                           "pad_to": 16, "mean_gap_tol_bf16_steps": mean,
                           "logit_tol_bf16_steps": worst}, notes=notes)
        if kw:
            monkeypatch.setattr(kind.serve_closed, "run",
                                lambda c: closed_run(c, **kw))
        return kind.run(ctx)["correct"], dict(notes)

    ok, notes = run(64, 192)
    assert ok and notes["referee"]["correct"]
    assert seen[-1] is not plain and _serving.referee is plain
    assert run(63, 192)[0] is False          # the mean alone refuses
    assert run(64, 191)[0] is False          # serve_closed's own limit
    assert run(64, 192, correct=False)[0] is False   # a fault stays one


def test_an_8bit_forward_is_judged_wrong_where_the_program_is_right():
    """The program in bfloat16, served through the front door, and the
    reference computed in 8 bits in its place, through the kind's own
    `measure` and `verdict`, at a size a CPU test can afford (hidden 128,
    64 experts top-6, nine blocks, vocabulary 8192). Readings here, 192
    tokens: the program's mean gap 0.63 bfloat16 steps, the 8-bit
    forward's 6.2; this test's limit, 1.5, lies between (the cell's own
    limits belong to the published widths, where every traced run notes
    the same verdicts)."""
    from paddle_tpu import inference
    from benchmark.kinds import serve_closed_routed as kind
    cfg = harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")
    cfg = harness.overlay(harness.overlay(cfg, cfg["rehearse"]), {
        "hidden_size": 128, "vocab_size": 8192, "n_routed_experts": 64,
        "num_experts_per_tok": 6, "num_hidden_layers": 9,
        "hybrid_override_pattern": "MEMEM*EME"})
    model, w = harness.build_model(cfg, seed=2147484001)
    pred = inference.create_llm_predictor(
        inference.Config().enable_llm_engine(
            num_slots=4, max_len=128, prefill_len=32, paged=True,
            block_size=16), model=model)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (40, 9, 33, 57)]
    reqs = [pred.submit(prompt=p, max_tokens=48) for p in prompts]
    pred.run()
    pred.close(drain=False)
    records = [_record(p, r.output_tokens) for p, r in zip(prompts, reqs)]
    check = {"requests": 4, "min_tokens": 8, "max_tokens": 48,
             "pad_to": 128, "mean_gap_tol_bf16_steps": 1.5,
             "logit_tol_bf16_steps": 160}
    ctx = _routed_ctx(check, config=cfg, seed=5)
    picks = _serving.sampled(ctx, records)
    assert len(picks) == 4
    ok, read = kind.verdict(check, kind.measure(ctx, w, picks))
    assert ok and read["tokens"] == 192, read
    c_ok, c_read = kind.verdict(check, kind.measure(
        ctx, w, picks, kind.CONTROLS["8bit"]))
    assert not c_ok and c_read["mean_gap_steps"] > 2 * 1.5, c_read
    assert c_read["argmax_match"] < read["argmax_match"]
    # a bfloat16 state moves next to no token at these weights (the state
    # forgets in two steps: PERF.md section 7), so no limit can refuse it
    s_ok, s_read = kind.verdict(check, kind.measure(
        ctx, w, picks, {"state": "bfloat16"}))
    assert s_ok and s_read["mean_gap_steps"] < 0.5, s_read


def test_reference_control_forward_and_margins():
    """`lower=None` is the plain forward; a margin is the last chosen
    score less the first unchosen one, the least over the expert layers;
    an 8-bit operand is scaled to its tensor's largest magnitude."""
    import jax.numpy as jnp
    cfg = _tiny()
    _, w = harness.build_model(cfg, seed=3)
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, harness.shapes(cfg)["layers"])
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 40)).astype(np.int32)
    plain = np.asarray(ref.forward(rw, ids, cfg))
    lo, margin = ref.forward(rw, ids, cfg, margins=True)
    np.testing.assert_array_equal(np.asarray(lo), plain)
    assert margin.shape == (1, 40) and (np.asarray(margin) > 0).all()
    # one expert layer (MEM*E): its router's scores give the margin
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (1, 5, 64)),
                    jnp.float32)
    lw = rw["layers"][1]
    _, m = ref._experts(x, lw, 3, 2.5)
    s = np.sort(np.asarray(1 / (1 + np.exp(-(
        x[0] @ lw["router_weight"].astype(jnp.float32))))), axis=-1)
    np.testing.assert_allclose(np.asarray(m)[0], s[:, -3] - s[:, -4],
                               rtol=1e-5)
    low = np.asarray(ref.forward(rw, ids, cfg, lower="float8_e4m3fn"))
    bf = np.asarray(ref.forward(rw, ids, cfg, lower="bfloat16",
                                state="bfloat16"))
    assert np.abs(bf - plain).max() < np.abs(low - plain).max()
    assert 0 < np.abs(low - plain).max() < 0.5 * np.abs(plain).max()
    v = jnp.asarray([1e-3, -2e-3, 4e-3], jnp.float32)
    np.testing.assert_allclose(np.asarray(ref._rounded(v, "float8_e4m3fn")),
                               np.asarray(v), rtol=2 ** -4)
    assert ref._rounded(v, None) is not None and np.array_equal(
        np.asarray(ref._rounded(v, None)), np.asarray(v))


def test_hybrid_paged_attn_roofline_counts_the_attention_layers_only():
    from benchmark.layer_metrics import hybrid_paged_attn_roofline as reader
    # one `*` in "ME*": 7 positions attended on average by the traced
    # waves: 4 x (2 x 2) x 7 = 112 operations, 2 x (1 x 2) x 2 bytes x 7
    # = 56 bytes: 0.56 ms at 1e5 bytes a second, memory binds
    assert flops_hybrid.paged_attention_cost(SH, 7) == (112.0, 56.0)
    ctx = _ctx({}, {})
    ctx["trace"]["kernel_by_module"]["decode_wave"]["paged_attention"] = \
        0.0042                    # 1.4 ms of the kernel in each of 3 waves
    assert reader.read(ctx) == pytest.approx(40.0)
    assert (reader.LAYER, reader.SOURCE) == ("paged_attention_core",
                                             "device_trace")
    assert reader.read({**ctx, "trace": None}) is None
    assert reader.read({**ctx, "shapes": {"layers": 2, "heads": 2}}) is None
    bare = {**ctx["trace"], "kernel_by_module": {"decode_wave": {}}}
    assert reader.read({**ctx, "trace": bare}) is None
