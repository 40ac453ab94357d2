"""The latent-attention configuration's files: its reference against the
program at the `rehearse` sizes, the parameter count from the file's own
keys, the wave's and the chunk's operations and bytes and the new readers
against numbers worked out by hand, the cell's rehearsal, the kind's
sample of both sorts of request, and planted faults against the referee."""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_mla_moe as cost, harness
from benchmark.kinds import _serving, serve_closed_routed as routed
from benchmark.kinds import serve_closed_routed_shared as kind
from benchmark.layer_metrics import (gated_experts_roofline, mla_chunk_mfu,
                                     mla_decode_attn_device_share,
                                     mla_decode_attn_roofline,
                                     mla_expanded_rows_per_prompt_token,
                                     mla_wave_mfu)

CONFIG = "kanana2-30b-a3b-d8"
CELL = "kanana2-serve-doc"


def _file():
    return harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")


def _tiny(**over):
    cfg = harness.overlay(_file(), {**_file()["rehearse"], **over})
    cfg["dtype"] = "float32"
    cfg["program"] = harness.overlay(
        cfg["program"], {"kwargs": {"param_dtype": "float32"}})
    return cfg


def test_reference_equals_the_program_forward():
    """Both float32, on the benchmark's seeded weights: the same function
    to rounding, 2e-5 of the largest logit (float32 sums of a few
    thousand products over three layers, in another order)."""
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
    # the keyword the routed kind's controls pass changes nothing here
    np.testing.assert_array_equal(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows, state="bfloat16")),
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)))


def test_the_configuration_is_the_published_one_but_for_its_depth():
    import json
    cfg = _file()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert (cfg["num_hidden_layers"], row["config"]["num_hidden_layers"]) \
        == (8, 48)
    for key in ("reduced_why", "deployment", "assumed", "source"):
        assert cfg[key]


def test_the_files_own_keys_count_5070_million_parameters():
    c = _file()
    h, heads = c["hidden_size"], c["num_attention_heads"]
    attention = (h * heads * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
                 + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                                + c["v_head_dim"])
                 + heads * c["v_head_dim"] * h)
    assert attention == 26_345_472
    dense = attention + 3 * h * c["intermediate_size"]
    expert = 3 * h * c["moe_intermediate_size"]
    layer = (attention + c["n_shared_experts"] * expert
             + h * c["n_routed_experts"] + c["n_routed_experts"] * expert)
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    total = (2 * c["vocab_size"] * h + c["first_k_dense_replace"] * dense
             + moe_layers * layer)
    assert round(dense / 1e6, 1) == 64.1 and round(layer / 1e6, 1) == 640.0
    assert total == cost.parameters(harness.shapes(c)) == 5_069_602_816
    assert round(total / 1e6) == 5070
    # and the program agrees, at the rehearsal's sizes: every matrix of
    # its state_dict (its norms' scales and the router's correction are
    # vectors)
    tiny = _tiny()
    model, _ = harness.build_model(tiny, seed=1)
    assert sum(int(np.prod(p.shape)) for _, p in model.named_parameters()
               if len(p.shape) >= 2) == cost.parameters(harness.shapes(tiny))


SH = {"layers": 3, "dense_layers": 1, "hidden": 4, "vocab": 10, "heads": 2,
      "latent_rank": 3, "rope_dim": 2, "nope_dim": 2, "v_dim": 1, "ffn": 5,
      "experts": 4, "experts_per_token": 2, "expert_width": 3,
      "shared_experts": 2}


def test_costs_equal_the_hand_computed_numbers():
    # attention: q 4 x 2 x 4, kv_a 4 x 5, kv_b 3 x 2 x 3, o 2 x 1 x 4
    assert cost.attention_weights(SH) == 32 + 20 + 18 + 8
    assert (cost.expert_weights(SH), cost.shared_weights(SH)) == (36, 72)
    # 2 tokens choosing 2 of 4: 4 (1 - 0.5^2) = 3 experts touched
    assert cost.experts_touched(SH, 2) == pytest.approx(3.0)
    # embedding and head 80, attention 3 x 78, dense 3 x 4 x 5, two
    # expert layers of router 16 + 4 x 36 + 72
    assert cost.parameters(SH) == 80 + 234 + 60 + 2 * 232
    # the gated kernel for 2 tokens: 4 picks x 36 weights x 2; 3 experts
    # x 36 weights x 2 bytes + 4 picks x 4 wide x (2 + 4) bytes
    assert cost.gated_expert_cost(SH, 2) == (pytest.approx(288.0),
                                             pytest.approx(312.0))
    # absorbed attention over 7 rows: 3 layers x 2 x 2 heads x (2 x 3 + 2)
    # x 7; a row is 5 values of 2 bytes a layer
    assert cost.latent_decode_cost(SH, 7) == (672.0, 210.0)
    # a wave of 2 lanes: a token multiplies, a layer, 78 (attention) and
    # 60 (dense) or 16 + 72 + 2 x 36 (experts), and the head 40:
    # 234 + 60 + 2 x 160 + 40 = 654 weights
    ops, nbytes = cost.decode_wave_cost(SH, lanes=2, attended_rows=7)
    assert ops == pytest.approx(2 * 2 * 654 + 672)
    # read once: 234 + 60 + 2 x (16 + 72 + 3 x 36) + 40 = 726 weights
    assert nbytes == pytest.approx(2 * 726 + 210)
    # a chunk of 2 tokens that expands 8 rows: kv_b (3 x 18 = 54) leaves
    # the tokens' count for the rows'; the head for one row; scores 3 x 2
    # heads x 2 x (2 + 2 + 1) x 2 tokens x (8 - 1) rows seen
    ops, nbytes = cost.prefill_chunk_cost(SH, tokens=2, expanded_rows=8)
    assert ops == pytest.approx(2 * (2 * (654 - 54 - 40) + 8 * 54 + 40)
                                + 3 * 2 * 10 * 2 * 7)
    assert nbytes == pytest.approx(2 * 726 + 3 * 5 * 2 * 8)
    # at the cell's size: a wave of 32 lanes at 8.4k rows reads 10.3 GB
    # (6.6 GB of about 100 experts a layer, 2.5 GB of latent rows)
    full = harness.shapes(_file())
    assert cost.experts_touched(full, 32) == pytest.approx(100.4, abs=0.1)
    _, wave_bytes = cost.decode_wave_cost(full, 32, 32 * 8400)
    assert 10.2e9 < wave_bytes < 10.4e9
    assert cost.latent_decode_cost(full, 1)[0] == 8 * 2 * 32 * (576 + 512)


def _ctx(snap0=None, snap1=None):
    rounds = [(1.0, 2.0, 2, 6, 0, 5), (2.0, 3.0, 2, 8, 1, 5),
              (3.0, 4.0, 0, 0, 1, 5), (4.0, 5.0, 0, 0, 0, 5)]
    return {"shapes": SH,
            "cell": {"programs": {"decode": "decode_wave",
                                  "prefill": "prefill_chunk"}},
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
            "trace": {"module_s": {"decode_wave": [0.04, 0.03, 0.02],
                                   "prefill_chunk": [0.05, 0.03]},
                      "kernel_s": {"pallas_other": 0.018,
                                   "paged_attention": 0.027},
                      "kernel_by_module": {
                          "decode_wave": {"pallas_other": 0.024,
                                          "paged_attention": 0.018},
                          "prefill_chunk": {"pallas_other": 0.006}},
                      "busy_s": 0.09},
            "trace_host": (0.5, 3.5),
            "obs": {"window": (0.0, 6.0), "rounds": rounds,
                    "snap0": snap0 or {}, "snap1": snap1 or {}}}


COUNTS = ({"prefill_chunks": 1, "prefill_tokens": 10,
           "mla_rows_expanded": 100},
          {"prefill_chunks": 5, "prefill_tokens": 18,
           "mla_rows_expanded": 132})


def test_readers_equal_the_hand_computed_numbers():
    ctx = _ctx(*COUNTS)
    # the traced rounds with a lane decoding: 2 lanes, 7 rows attended:
    # 3,288 operations, 1,662 bytes; memory binds, 16.62 ms of the median
    # wave's 30
    assert mla_wave_mfu.read(ctx) == pytest.approx(55.4)
    # the window's mean chunk: 2 tokens, 8 rows expanded: 3,604
    # operations, 1,692 bytes; memory binds, 16.92 ms of the median 40
    assert mla_chunk_mfu.read(ctx) == pytest.approx(42.3)
    # 27 ms of the latent kernel in 90 ms busy
    assert mla_decode_attn_device_share.read(ctx) == pytest.approx(30.0)
    # 210 bytes at 1e5 a second = 2.1 ms least (672 operations at 1e6 are
    # 0.672), against 18 ms of the kernel in 3 waves = 6 ms a wave
    assert mla_decode_attn_roofline.read(ctx) == pytest.approx(35.0)
    # two expert layers x 312 bytes = 6.24 ms least, against 24 ms of the
    # kernel in 3 waves = 8 ms a wave
    assert gated_experts_roofline.read(ctx) == pytest.approx(78.0)
    # 32 rows expanded for 8 prompt tokens
    assert mla_expanded_rows_per_prompt_token.read(ctx) == pytest.approx(4.0)


@pytest.mark.parametrize("reader,layer,source", [
    (mla_wave_mfu, "latent_moe_model_step", "device_trace"),
    (mla_chunk_mfu, "latent_moe_model_step", "device_trace"),
    (mla_decode_attn_device_share, "latent_attention_core", "device_trace"),
    (mla_decode_attn_roofline, "latent_attention_core", "device_trace"),
    (gated_experts_roofline, "moe_experts_kernel", "device_trace"),
    (mla_expanded_rows_per_prompt_token, "latent_attention_core",
     "program_counter")])
def test_readers_return_none_where_there_is_nothing_to_read(reader, layer,
                                                            source):
    """A program without the latent cache, its kernels or its counters
    (the parent of the PR that added them), an untraced run, a kind of
    cell without snapshots: nothing, and no error."""
    assert (reader.LAYER, reader.SOURCE) == (layer, source)
    ctx = _ctx(*COUNTS)
    bare = {**ctx["trace"], "kernel_s": {}, "kernel_by_module": {},
            "module_s": {}}
    empty = {**ctx, "obs": {"window": (0.0, 6.0)}}
    cases = [{**ctx, "trace": bare, "obs": _ctx()["obs"]},
             {**empty, "trace": None, "trace_host": None}]
    if source == "device_trace":
        cases += [{**ctx, "trace": None},
                  {**ctx, "shapes": {"layers": 2, "pattern": "ME"}}]
    for case in cases:
        assert reader.read(case) is None


def test_the_benchmark_lists_the_cell_where_its_readers_apply():
    bench = harness.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[-1] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    assert mine[-6:] == [
        "serve.mla_wave_mfu", "serve.mla_chunk_mfu",
        "serve.mla_decode_attn_device_share",
        "serve.mla_decode_attn_roofline", "serve.gated_experts_roofline",
        "serve.mla_expanded_rows_per_prompt_token"]
    assert set(mine[:-6]) == {"serve." + n for n in (
        "sched_host_ms_per_round", "round_ms_p50", "decode_wave_device_ms",
        "prefill_chunk_device_ms", "prefix_hit_share", "pool_live_share",
        "device_idle_share", "compiles_in_window", "host_unfed_ms_per_round",
        "wave_host_ms", "chunk_host_ms", "sched_other_ms_per_round",
        "host_busy_share")}
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL          # appended, last


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
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    last = notes[-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert "serve_tokens_per_s" in last["end_to_end"]
    assert {"serve.prefix_hit_share",
            "serve.mla_expanded_rows_per_prompt_token"} <= set(
                last["per_layer"])
    check = next(n for n in notes if n["note"] == "check")
    assert check["tokens_checked"] > 0 and not check["faults"]


def _record(prompt, tokens, prefix_id=None):
    return types.SimpleNamespace(
        request=types.SimpleNamespace(output_tokens=list(tokens)),
        planned=types.SimpleNamespace(prompt=list(prompt),
                                      prefix_id=prefix_id))


@pytest.mark.parametrize("prefixes,n,want", [
    ([0, 1, None, 2], 2, [0, 2]),        # all sharers: the stranger comes in
    ([None, None, 3, None], 2, [0, 2]),  # all strangers: the sharer does
    ([0, None, 1, 2], 2, [0, 1]),        # both there: the plain sample
    ([0, 1, 2, 0], 3, [0, 1, 2]),        # no stranger anywhere: as it is
    ([None, 1], 1, [0])])                # one request is one kind
def test_the_sample_holds_a_sharer_and_a_stranger(prefixes, n, want):
    order = [_record([i], [1, 2], p) for i, p in enumerate(prefixes)]
    assert [r.planned.prompt[0] for r in kind.both_kinds(order, n)] == want


def test_the_kind_samples_through_the_plain_order(monkeypatch):
    """`run` wraps `_serving.sampled` for the one call and puts it back;
    the wrapper's picks are the plain order's first, with one of the
    other kind."""
    seen = {}

    def fake_run(ctx):
        records = [_record([i] * (9 - i), [1] * 9, 0) for i in range(4)] \
            + [_record([7], [1] * 9, None)]
        seen["picks"] = _serving.sampled(ctx, records)
        return {"correct": True}

    monkeypatch.setattr(routed, "run", fake_run)
    plain = _serving.sampled
    ctx = types.SimpleNamespace(seed=5, cell={"check": {
        "requests": 2, "min_tokens": 8}})
    assert kind.run(ctx) == {"correct": True}
    assert _serving.sampled is plain
    picks = seen["picks"]
    # the longest first, as the plain sample has it; then the stranger
    assert picks[0].planned.prompt == [0] * 9 and len(picks) == 2
    assert picks[1].planned.prefix_id is None


# ------------------------------------------------- planted faults
def _serve(cfg, monkeypatch, fault):
    """The program at a size a CPU test can afford, served through the
    front door with `fault` planted in it; (weights, records)."""
    import jax.numpy as jnp
    from paddle_tpu import inference
    from paddle_tpu.nlp import deepseek_v3 as program
    from paddle_tpu.nn import paged_attention as pa
    if fault == "one head's W_UV zeroed":
        plain = pa.attend_latent

        def attend(q, pool, *a, **k):
            # the absorbed output of head 0 never reaches W_UV
            return plain(q, pool, *a, **k).at[:, 0].set(0.0)
        monkeypatch.setattr(pa, "attend_latent", attend)
    elif fault == "the rotary key left unrotated":
        plain_rope = program.apply_rope_positions

        def rope(x, cos, sin, positions):
            # the one shared key comes as [B, 1, C, rope]
            return x if x.shape[1] == 1 else plain_rope(x, cos, sin,
                                                        positions)
        monkeypatch.setattr(program, "apply_rope_positions", rope)
    model, w = harness.build_model(cfg, seed=2147484001)
    pred = inference.create_llm_predictor(
        inference.Config().enable_llm_engine(
            num_slots=4, max_len=128, prefill_len=32, paged=True,
            block_size=16), model=model)
    rng = np.random.default_rng(7)
    head = rng.integers(0, cfg["vocab_size"], 48).tolist()
    prompts = [head + rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (9, 25)] + \
        [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (40, 57)]
    # the first sharer alone, so that its pages are resident when the
    # second is admitted
    reqs = [pred.submit(prompt=prompts[0], max_tokens=24)]
    pred.run()
    reqs += [pred.submit(prompt=p, max_tokens=24) for p in prompts[1:]]
    pred.run()
    hits = pred.health()["prefix_cache_hits"]
    pred.close(drain=False)
    assert hits >= 3                 # the second request found the head
    return w, [_record(p, r.output_tokens, 0 if i < 2 else None)
               for i, (p, r) in enumerate(zip(prompts, reqs))]


@pytest.mark.parametrize("fault", [None, "one head's W_UV zeroed",
                                   "the rotary key left unrotated"])
def test_planted_faults_fail_the_referee(monkeypatch, fault):
    """Float32, hidden 128, four layers: the sound program serves the
    reference's own tokens (a mean gap of 0 steps); with one head's
    absorbed output cut off before W_UV in every wave, or the cached
    rotary key written unrotated, the served tokens lie 44 and 48
    bfloat16 steps under the reference's best in the mean (165 and 199 at
    worst; CPU probe), and the limits this size allows (mean 1, worst 16)
    refuse the run. Requests that hit a shared prefix and requests that
    do not are both in the sample. The matrices are drawn four times as
    wide as the cell's (0.08): at hidden 128 and 0.02 every score is
    next to nothing, attention is an average, and a wrong rotary key
    moves one token in fifty."""
    cfg = _tiny(hidden_size=128, num_hidden_layers=4, vocab_size=2048,
                max_position_embeddings=128)
    cfg["weights"] = {**cfg["weights"], "std": 0.08}
    w, records = _serve(cfg, monkeypatch, fault)
    check = {"requests": 4, "min_tokens": 8, "max_tokens": 24,
             "pad_to": 128, "mean_gap_tol_bf16_steps": 1.0,
             "logit_tol_bf16_steps": 16}
    ctx = types.SimpleNamespace(seed=5, trace=False, config=cfg,
                                cell={"check": check})
    picks = kind.both_kinds(
        _serving.sampled(ctx, records), 4)
    assert {r.planned.prefix_id for r in picks} == {0, None}
    ok, read = routed.verdict(check, routed.measure(ctx, w, picks))
    assert read["tokens"] == 96
    if fault is None:
        assert ok and read["mean_gap_steps"] < 0.01, read
    else:
        assert not ok and read["mean_gap_steps"] > 2.0, read
