"""The hyper-connected latent-attention configuration's files: its
reference against the program at the `rehearse` sizes, the parameter count
from the file's own keys, the wave's and the chunk's operations and bytes
and the three new readers against numbers worked out by hand, the cell's
rehearsal, and the entries `BENCHMARK.json` holds for it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_mla_moe as base, flops_xing4 as cost, harness
from benchmark.layer_metrics import (mhc_chunk_mfu, mhc_rows_per_served_token,
                                     mhc_wave_mfu)

CONFIG = "xing4.0-29b-a4b-d6"
CELL = "xing4-serve-repo-reason"
PUBLISHED = {"num_hidden_layers": 40, "first_k_dense_replace": 2,
             "num_nextn_predict_layers": 1}


def _file():
    return harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")


def _tiny(**over):
    cfg = harness.overlay(_file(), {**_file()["rehearse"], **over})
    cfg["dtype"] = "float32"
    cfg["program"] = harness.overlay(
        cfg["program"], {"kwargs": {"param_dtype": "float32"}})
    return cfg


def test_reference_equals_the_program_forward():
    """Both float32, on the benchmark's seeded weights (maps of deviation
    0.02 x sqrt(4 x 64) = 0.3 at this size), 75 positions across the
    YaRN original length of 32: the same function to rounding, 3e-5 of
    the largest logit."""
    import jax.numpy as jnp
    cfg = _tiny()
    assert cfg["rope_scaling"] == {
        **_file()["rope_scaling"], "factor": 8,
        "original_max_position_embeddings": 32}
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
    assert np.abs(got - want).max() <= 3e-5 * np.abs(want).max() + 1e-6
    rows = [0, 17, 74]
    np.testing.assert_allclose(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)), want[:, rows],
        atol=1e-6)
    # the keyword the routed kind's controls pass changes nothing here;
    # the 8-bit control is another function
    np.testing.assert_array_equal(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows, state="bfloat16")),
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)))
    low = np.asarray(ref.forward(rw, ids, cfg, rows=rows,
                                 lower="float8_e4m3fn"))
    assert np.abs(low - want[:, rows]).max() > 1e-3 * np.abs(want).max()


def test_the_weights_rule_gives_usable_maps():
    """`benchmark/weights.py::leaf_rule`, unedited, on the names the
    program gives the maps' parameters: Phi and the res map's offset
    drawn, the two other offsets 0, the three scalars 1."""
    model, w = harness.build_model(_tiny(), seed=5)
    hc = {k.rsplit(".", 1)[1]: np.asarray(v, np.float32)
          for k, v in w.items() if k.startswith("layers.1.hc_mlp.")}
    assert sorted(hc) == ["phi", "post_bias", "post_scale", "pre_bias",
                          "pre_scale", "res_offset", "res_scale"]
    assert hc["phi"].shape == (24, 4 * 64) and hc["phi"].std() > 0.015
    assert hc["res_offset"].shape == (4, 4) and hc["res_offset"].any()
    for name in ("pre_bias", "post_bias"):
        assert hc[name].shape == (4,) and not hc[name].any()
    for name in ("pre_scale", "post_scale", "res_scale"):
        assert hc[name].tolist() == [1.0]


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    cfg = _file()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == set(PUBLISHED)
    assert {k: row["config"][k] for k in PUBLISHED} == PUBLISHED \
        == cfg["published"]
    assert [cfg[k] for k in PUBLISHED] == [6, 1, 0]
    for key in ("reduced_why", "deployment", "assumed", "source"):
        assert cfg[key]


def test_the_files_own_keys_count_4793_million_parameters():
    c = _file()
    h, heads, n = c["hidden_size"], c["num_attention_heads"], c["hc_mult"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attention = (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
                 + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                                + c["v_head_dim"])
                 + heads * c["v_head_dim"] * h)
    assert attention == 28_409_856
    maps = 2 * (n * h * (2 * n + n * n) + n * n)
    assert maps == 688_160
    dense = attention + maps + 3 * h * c["intermediate_size"]
    expert = 3 * h * c["moe_intermediate_size"]
    layer = (attention + maps + c["n_shared_experts"] * expert
             + h * c["n_routed_experts"] + c["n_routed_experts"] * expert)
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    total = (2 * c["vocab_size"] * h + c["first_k_dense_replace"] * dense
             + moe_layers * layer)
    assert round(dense / 1e6, 1) == 128.2 and round(layer / 1e6, 1) == 745.0
    assert total == cost.parameters(harness.shapes(c)) == 4_792_615_104
    assert round(total / 1e6) == 4793
    # and the program agrees, at the rehearsal's sizes: every parameter
    # of rank 2 and more in its state_dict
    tiny = _tiny()
    model, _ = harness.build_model(tiny, seed=1)
    assert sum(int(np.prod(p.shape)) for _, p in model.named_parameters()
               if len(p.shape) >= 2) == cost.parameters(harness.shapes(tiny))


SH = {"layers": 3, "dense_layers": 1, "hidden": 4, "vocab": 10, "heads": 2,
      "latent_rank": 3, "rope_dim": 2, "nope_dim": 2, "v_dim": 1, "ffn": 5,
      "experts": 4, "experts_per_token": 2, "expert_width": 3,
      "shared_experts": 2, "q_rank": 3, "hc_streams": 2}


def test_costs_equal_the_hand_computed_numbers():
    # the query: q_a 4 x 3 + q_b 3 x 2 x 4 = 36 where the full one is 32
    assert cost.query_weights(SH) == 36
    assert cost.attention_weights(SH) == base.attention_weights(SH) + 4 == 82
    # Phi of a sub-layer: 2 streams x 4 wide x (2 + 2 + 4) outputs
    assert cost.map_weights(SH) == 64
    # the plain count + 3 layers x (4 + 2 sub-layers x (64 + 4))
    assert cost.parameters(SH) == base.parameters(SH) + 3 * (4 + 2 * 68)
    # 6 sub-layers, 5 tokens: a token multiplies Phi (64), the pre-mix
    # (2 x 4) and the res map and post term ((4 + 2) x 4): 96 x 2
    # operations; Phi read once (64 x 2 bytes), 5 x 2 x 4 values of
    # streams read and as many written
    assert cost.mix_cost(SH, 5) == (6 * 5 * 192.0, 6 * 2 * (64 + 80.0))
    # a wave of 2 lanes over 7 rows: the plain wave's 3,288 operations
    # and 1,662 bytes, + 2 x 2 lanes x 3 layers x 4 weights of query
    # + the mixing of 2 tokens
    ops, nbytes = cost.decode_wave_cost(SH, lanes=2, attended_rows=7)
    plain = base.decode_wave_cost(SH, lanes=2, attended_rows=7)
    assert plain == (pytest.approx(3288.0), pytest.approx(1662.0))
    assert ops == pytest.approx(3288 + 48 + 6 * 2 * 192)
    assert nbytes == pytest.approx(1662 + 2 * 12 + 6 * 2 * (64 + 32))
    # a chunk of 2 tokens that expands 8 rows: the same additions
    ops, nbytes = cost.prefill_chunk_cost(SH, tokens=2, expanded_rows=8)
    plain = base.prefill_chunk_cost(SH, tokens=2, expanded_rows=8)
    assert ops == pytest.approx(plain[0] + 48 + 6 * 2 * 192)
    assert nbytes == pytest.approx(plain[1] + 24 + 6 * 2 * (64 + 32))
    # at the cell's size: a wave of 16 lanes at 19k rows a lane reads
    # about 8.2 GB: 4.5 GB of about 41 experts a layer, 2.1 GB of latent
    # rows, 0.9 GB of head, the rest attention, dense and shared matrices
    full = harness.shapes(_file())
    assert base.experts_touched(full, 16) == pytest.approx(41.2, abs=0.1)
    _, wave_bytes = cost.decode_wave_cost(full, 16, 16 * 19000)
    assert 8.1e9 < wave_bytes < 8.4e9
    # the mixing of a wave: 12 Phis of 0.69 MB and 16 x 4 x 3584 values
    # each way a sub-layer: 19 MB, 0.2% of the wave
    assert cost.mix_cost(full, 16)[1] == pytest.approx(19.3e6, rel=0.01)


def _ctx(snap0=None, snap1=None):
    rounds = [(1.0, 2.0, 2, 6, 0, 5), (2.0, 3.0, 2, 8, 1, 5),
              (3.0, 4.0, 0, 0, 1, 5), (4.0, 5.0, 0, 0, 0, 5)]

    def record(times):
        import types
        return types.SimpleNamespace(token_t=times)

    return {"shapes": SH,
            "cell": {"programs": {"decode": "decode_wave",
                                  "prefill": "prefill_chunk"}},
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
            "trace": {"module_s": {"decode_wave": [0.04, 0.03, 0.02],
                                   "prefill_chunk": [0.05, 0.03]},
                      "kernel_s": {}, "kernel_by_module": {},
                      "busy_s": 0.09},
            "trace_host": (0.5, 3.5),
            "obs": {"window": (0.0, 6.0), "rounds": rounds,
                    "records": [record([0.5, 1.5, 7.0]), record([2.0, 5.5])],
                    "snap0": snap0 or {}, "snap1": snap1 or {}}}


COUNTS = ({"prefill_chunks": 1, "prefill_tokens": 10,
           "mla_rows_expanded": 100, "mhc_rows_mixed": 40},
          {"prefill_chunks": 5, "prefill_tokens": 18,
           "mla_rows_expanded": 132, "mhc_rows_mixed": 112})


def test_readers_equal_the_hand_computed_numbers():
    ctx = _ctx(*COUNTS)
    # the traced rounds with a lane decoding: 2 lanes, 7 rows attended:
    # 5,640 operations, 2,838 bytes; memory binds, 28.38 ms of the median
    # wave's 30
    assert mhc_wave_mfu.read(ctx) == pytest.approx(94.6)
    # the window's mean chunk: 2 tokens, 8 rows expanded: the plain
    # chunk's 1,692 bytes + 24 + 1,152 = 2,868; 28.68 ms of the median 40
    assert mhc_chunk_mfu.read(ctx) == pytest.approx(71.7)
    # 72 rows mixed for the 4 tokens that arrived inside the window
    assert mhc_rows_per_served_token.read(ctx) == pytest.approx(18.0)


@pytest.mark.parametrize("reader,layer,source", [
    (mhc_wave_mfu, "hyper_connected_latent_moe_step", "device_trace"),
    (mhc_chunk_mfu, "hyper_connected_latent_moe_step", "device_trace"),
    (mhc_rows_per_served_token, "paged_engine", "program_counter")])
def test_readers_return_none_where_there_is_nothing_to_read(reader, layer,
                                                            source):
    """A program without the counter or the streams (the parent of the PR
    that added them, a model with one stream), an untraced run, a kind of
    cell without snapshots: nothing, and no error."""
    assert (reader.LAYER, reader.SOURCE) == (layer, source)
    ctx = _ctx(*COUNTS)
    bare = {**ctx["trace"], "module_s": {}}
    empty = {**ctx, "obs": {"window": (0.0, 6.0)}}
    one_stream = {k: v for k, v in SH.items() if k != "hc_streams"}
    cases = [{**ctx, "trace": bare, "obs": _ctx()["obs"]},
             {**empty, "trace": None, "trace_host": None}]
    if source == "device_trace":
        cases += [{**ctx, "trace": None}, {**ctx, "shapes": one_stream},
                  {**ctx, "shapes": {"layers": 2, "pattern": "ME"}}]
    else:
        zero = dict(COUNTS[1], mhc_rows_mixed=40)
        cases += [{**ctx, "obs": {**ctx["obs"], "snap1": zero}}]
    for case in cases:
        assert reader.read(case) is None


def test_the_benchmark_holds_the_cell_and_its_entries():
    """Presence, not position: a later PR's cell may follow this one."""
    bench = harness.load_benchmark()
    cfg = harness.find_entry(bench["configs"], CONFIG, "config")
    assert cfg["reduced"] == list(PUBLISHED) == _file()["reduced"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = harness.find_entry(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "serve-repo-reason", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    new = {"serve.mhc_wave_mfu": "hyper_connected_latent_moe_step",
           "serve.mhc_chunk_mfu": "hyper_connected_latent_moe_step",
           "serve.mhc_rows_per_served_token": "paged_engine"}
    for name, layer in new.items():
        m = mine[name]
        assert (m["layer"], m["moves"], m["workloads"]) == (
            layer, "serve_tokens_per_s", [CELL])
        reader = harness.load_module("layer_metrics",
                                     harness.reader_name(name))
        assert (reader.LAYER, reader.SOURCE) == (layer, m["source"])
    assert {"serve.mla_decode_attn_device_share",
            "serve.mla_decode_attn_roofline", "serve.gated_experts_roofline",
            "serve.mla_expanded_rows_per_prompt_token",
            "serve.prefix_hit_share", "serve.dispatch_ahead_share",
            "serve.gc_pause_ms_per_round", "setup.compile_s",
            "serve.device_idle_share"} <= set(mine)
    # the plain latent model's shares count a full-rank query and one
    # stream: not this cell's
    assert not {"serve.mla_wave_mfu", "serve.mla_chunk_mfu", "serve.wave_mfu",
                "serve.ssm_wave_mfu", "serve.state_host_ms_per_round"} \
        & set(mine)
    traffic = harness.read_json(
        f"{harness.BENCH_DIR}/workloads/{CELL}.json")
    assert traffic["kind"] == "serve_closed_routed_shared"
    assert traffic["check"]["pad_to"] % harness.reference_for(
        _file()).QUERY_BLOCK == 0
    assert traffic["check"]["pad_to"] >= \
        traffic["load"]["prompt_len"]["max"] + traffic["check"]["max_tokens"]


def test_the_cell_rehearses_green():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "3900000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    last = notes[-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert "serve_tokens_per_s" in last["end_to_end"]
    assert {"serve.prefix_hit_share", "serve.mhc_rows_per_served_token",
            "serve.mla_expanded_rows_per_prompt_token"} <= set(
                last["per_layer"])
    check = next(n for n in notes if n["note"] == "check")
    assert check["tokens_checked"] > 0 and not check["faults"]
    referee = next(n for n in notes if n["note"] == "referee")
    assert referee["correct"] and referee["mean_gap_steps"] < 1.0
