"""The window-and-full configuration's files: its reference against the
program at the `rehearse` sizes, the parameter count from the file's own
keys, the wave's, the chunk's and the two kernels' operations and bytes
and the six new readers against numbers worked out by hand, the cell's
rehearsal, and the entries `BENCHMARK.json` holds for it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import flops_laguna as cost, harness
from benchmark.layer_metrics import (small_experts_roofline, swa_chunk_mfu,
                                     swa_wave_mfu, window_host_ms_per_round,
                                     window_paged_attn_roofline,
                                     window_pages_per_lane)

CONFIG = "laguna-xs.2-d5"
CELL = "laguna-xs2-serve-agent-mix"
CUT = ("num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads_per_layer")
F, S = "full_attention", "sliding_attention"


def _file():
    return harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")


def _tiny(**over):
    cfg = harness.overlay(_file(), {**_file()["rehearse"], **over})
    cfg["dtype"] = "float32"
    cfg["program"] = harness.overlay(
        cfg["program"], {"kwargs": {"param_dtype": "float32"}})
    return cfg


def test_reference_equals_the_program_forward():
    """Both float32, on the benchmark's seeded weights, 70 positions:
    across the YaRN original length of 32 in the full layers and past
    eight windows of 8 in the sliding ones; the same function to
    rounding, 3e-5 of the largest logit."""
    import jax.numpy as jnp
    cfg = _tiny()
    assert cfg["rope_parameters"][F] == {
        **_file()["rope_parameters"][F], "factor": 8,
        "original_max_position_embeddings": 32}
    assert cfg["layer_types"] == [F, S, S, S, F]
    model, w = harness.build_model(cfg, seed=3)
    model.eval()
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, harness.shapes(cfg)["layers"])
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 70)).astype(np.int32)
    want = np.asarray(ref.forward(rw, ids, cfg))
    params, buffers = model.functional_state()
    got = np.asarray(model.functional_call(params, buffers,
                                           jnp.asarray(ids))[0]._data)
    assert want.shape == got.shape == (2, 70, cfg["vocab_size"])
    assert np.abs(got - want).max() <= 3e-5 * np.abs(want).max() + 1e-6
    rows = [0, 17, 69]
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


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    """The cut keys are listed, their published values kept beside them,
    and each per-layer list is the published one's first five entries:
    one full layer with the dense MLP, then one period of the pattern."""
    cfg = _file()
    assert set(cfg["reduced"]) == set(CUT) == set(cfg["published"])
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["num_hidden_layers"] == 5
    for key in CUT[1:]:
        assert len(cfg["published"][key]) == 40
        assert cfg[key] == cfg["published"][key][:5]
    assert cfg["layer_types"] == [F, S, S, S, F]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    for key in ("reduced_why", "deployment", "assumed", "source"):
        assert cfg[key]


def test_the_files_own_keys_count_3870_million_parameters():
    c = _file()
    h, kv, hd = c["hidden_size"], c["num_key_value_heads"], c["head_dim"]

    def attention(heads):
        return (h * heads * hd + 2 * h * kv * hd + heads * hd * h
                + h * heads)

    assert (attention(48), attention(64)) == (29_458_432, 37_879_808)
    expert = 3 * h * c["moe_intermediate_size"]
    sparse = (h * c["num_experts"] + 3 * h
              * c["shared_expert_intermediate_size"]
              + c["num_experts"] * expert)
    dense = 3 * h * c["intermediate_size"]
    assert (dense, sparse) == (50_331_648, 808_976_384)
    total = 2 * c["vocab_size"] * h + sum(
        attention(n) + (dense if m == "dense" else sparse)
        for n, m in zip(c["num_attention_heads_per_layer"],
                        c["mlp_layer_types"]))
    assert total == cost.parameters(harness.shapes(c)) == 3_869_835_264
    assert round(total / 1e6, 1) == 3869.8        # 7.74 GB in bfloat16
    # and the program agrees, at the rehearsal's sizes: every parameter
    # of rank 2 and more in its state_dict
    tiny = _tiny()
    model, _ = harness.build_model(tiny, seed=1)
    assert sum(int(np.prod(p.shape)) for _, p in model.named_parameters()
               if len(p.shape) >= 2) == cost.parameters(harness.shapes(tiny))


SH = {"layers": 3, "hidden": 4, "vocab": 10, "layer_types": [F, S, F],
      "mlp_layer_types": ["dense", "sparse", "sparse"],
      "layer_heads": [2, 4, 2], "kv_heads": 1, "head_dim": 2, "ffn": 5,
      "experts": 4, "experts_per_token": 2, "expert_width": 3,
      "shared_width": 3, "window": 3, "gating": True}


def test_costs_equal_the_hand_computed_numbers():
    # q, k, v 4 x (2 + 2) x 2, o 4 x 4, gate 4 x 2; and at 4 heads
    assert cost.attention_weights(SH, 2) == 32 + 16 + 8
    assert cost.attention_weights(SH, 4) == 48 + 32 + 16
    assert cost.expert_weights(SH) == 36 and cost.sparse_layers(SH) == 2
    # attention 56 + 96 + 56, dense 60, two routers 16 and shared 36
    # each, 2 x 4 experts of 36, embedding and head 2 x 40
    assert cost.parameters(SH) == 208 + 60 + 2 * 52 + 288 + 80
    assert cost.experts_touched(SH, 1) == 2 and \
        cost.experts_touched(SH, 2) == 3
    # 2 tokens x 2 picks x 36 x 2 operations; 3 experts read, 4 picks'
    # rows in (2 bytes) and out (4)
    assert cost.expert_cost(SH, 2) == (288.0, 216.0 + 96)
    # 10 rows in each full layer, 6 in the window layer: 4 x heads x 2
    # operations a row, 8 bytes of K and V a row
    assert cost.paged_attention_cost(SH, 10, 6) == (160 + 192 + 160.0,
                                                    208.0)
    assert cost.window_rows(SH, 2, 10) == 6 and \
        cost.window_rows(SH, 2, 4) == 4
    # a wave of 2 lanes over 10 rows: 2 x 2 x (372 + 40 + 2 x 2 x 36)
    # + 512; (372 + 40 + 2 x 3 x 36) x 2 + 208
    assert cost.decode_wave_cost(SH, 2, 10) == (2736.0, 1464.0)
    # a chunk of 2 tokens whose queries attend 9 keys in a full layer
    # (6 in the window layer); it reads positions up to 5
    assert cost.prefill_chunk_cost(SH, 2, 9) == (
        2 * (2 * 516 + 40) + 144 + 192 + 144.0, 2 * 628 + 120.0)
    # at the cell's size: a wave of 16 lanes touches about 102 of 256
    # experts a layer, 2.57 GB of the 6.44 the kernel fetches; the wave's
    # least bytes at 10k positions a lane are 4.86 GB
    full = harness.shapes(_file())
    assert cost.experts_touched(full, 16) == pytest.approx(102.0, abs=0.1)
    touched = 4 * cost.experts_touched(full, 16) * 2 * cost.expert_weights(
        full)
    assert touched == pytest.approx(2.566e9, rel=1e-3)
    assert 4 * 256 * 2 * cost.expert_weights(full) == pytest.approx(
        6.442e9, rel=1e-3)
    _, wave_bytes = cost.decode_wave_cost(full, 16, 16 * 10000)
    assert 4.8e9 < wave_bytes < 4.9e9


def _ctx(snap0=None, snap1=None, kernels=None):
    rounds = [(1.0, 2.0, 2, 10, 0, 5), (2.0, 3.0, 2, 10, 1, 5),
              (3.0, 4.0, 0, 0, 1, 5), (4.0, 5.0, 0, 0, 0, 5)]
    return {"shapes": SH,
            "cell": {"programs": {"decode": "decode_wave",
                                  "prefill": "prefill_chunk"}},
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5},
            "trace": {"module_s": {"decode_wave": [0.04, 0.03, 0.02],
                                   "prefill_chunk": [0.05, 0.03]},
                      "kernel_s": {},
                      "kernel_by_module": {"decode_wave": kernels or {
                          "paged_attention": 0.009, "pallas_other": 0.03}},
                      "busy_s": 0.09},
            "trace_host": (0.5, 3.5),
            "obs": {"window": (0.0, 6.0), "rounds": rounds,
                    "snap0": snap0 or {}, "snap1": snap1 or {}}}


COUNTS = ({"prefill_chunks": 1, "prefill_tokens": 10, "prefill_keys": 100,
           "window_pages_held": 10, "window_lanes_held": 2,
           "phase_seconds": {"round": 1.0, "window.release": 0.001}},
          {"prefill_chunks": 5, "prefill_tokens": 18, "prefill_keys": 136,
           "window_pages_held": 76, "window_lanes_held": 4,
           "phase_seconds": {"round": 2.0, "window.release": 0.004}})


def test_readers_equal_the_hand_computed_numbers():
    ctx = _ctx(*COUNTS)
    # the traced rounds with a lane decoding: 2 lanes, 10 rows attended:
    # 1,464 bytes bind, 14.64 ms of the median wave's 30
    assert swa_wave_mfu.read(ctx) == pytest.approx(48.8)
    # the window's mean chunk: 2 tokens, 9 keys: 1,376 bytes bind, 13.76
    # ms of the median chunk's 40
    assert swa_chunk_mfu.read(ctx) == pytest.approx(34.4)
    # both kinds' K/V rows of a wave, 208 bytes: 2.08 ms of the kernel's
    # 3 ms a wave
    assert window_paged_attn_roofline.read(ctx) == pytest.approx(69.333,
                                                                 rel=1e-4)
    # two sparse layers of 312 bytes: 6.24 ms of the kernel's 10 a wave
    assert small_experts_roofline.read(ctx) == pytest.approx(62.4)
    # 3 ms of `window.release` over the window's 3 rounds with work
    assert window_host_ms_per_round.read(ctx) == pytest.approx(1.0)
    # 66 pages held over 2 lane-rounds
    assert window_pages_per_lane.read(ctx) == pytest.approx(33.0)


@pytest.mark.parametrize("reader,layer,source", [
    (swa_wave_mfu, "window_moe_model_step", "device_trace"),
    (swa_chunk_mfu, "window_moe_model_step", "device_trace"),
    (window_paged_attn_roofline, "paged_attention_core", "device_trace"),
    (small_experts_roofline, "moe_experts_kernel", "device_trace"),
    (window_host_ms_per_round, "block_pool", "program_counter"),
    (window_pages_per_lane, "block_pool", "program_counter")])
def test_readers_return_none_where_there_is_nothing_to_read(reader, layer,
                                                            source):
    """A program without the window pool's counters and phase (the parent
    of the PR that added them), an untraced run, a kind of cell without
    snapshots, a configuration without window layers: nothing, and no
    error."""
    assert (reader.LAYER, reader.SOURCE) == (layer, source)
    ctx = _ctx(*COUNTS)
    parent = ({"prefill_chunks": 1, "prefill_tokens": 10,
               "phase_seconds": {"round": 1.0}},
              {"prefill_chunks": 5, "prefill_tokens": 18,
               "phase_seconds": {"round": 2.0}})
    bare = {**ctx["trace"], "module_s": {}, "kernel_by_module": {}}
    cases = [{**_ctx(*parent), "trace": bare},
             {**ctx, "trace": None, "trace_host": None,
              "obs": {"window": (0.0, 6.0)}}]
    if source == "device_trace":
        no_window = {k: v for k, v in SH.items()
                     if k not in ("layer_types", "window")}
        cases += [{**ctx, "trace": None}, {**ctx, "shapes": no_window},
                  {**ctx, "shapes": {"layers": 2, "pattern": "ME"}}]
    else:
        cases += [_ctx(*parent)]
    for case in cases:
        assert reader.read(case) is None


def test_the_benchmark_holds_the_cell_and_its_entries():
    """Presence, not position: a later PR's cell may follow this one."""
    bench = harness.load_benchmark()
    cfg = harness.find_entry(bench["configs"], CONFIG, "config")
    assert cfg["reduced"] == list(CUT) == _file()["reduced"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = harness.find_entry(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "serve-agent-mix", 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    new = {"serve.swa_wave_mfu": "window_moe_model_step",
           "serve.swa_chunk_mfu": "window_moe_model_step",
           "serve.window_paged_attn_roofline": "paged_attention_core",
           "serve.small_experts_roofline": "moe_experts_kernel",
           "serve.window_host_ms_per_round": "block_pool",
           "serve.window_pages_per_lane": "block_pool"}
    for name, layer in new.items():
        m = mine[name]
        assert (m["layer"], m["moves"], m["workloads"]) == (
            layer, "serve_tokens_per_s", [CELL])
        reader = harness.load_module("layer_metrics",
                                     harness.reader_name(name))
        assert (reader.LAYER, reader.SOURCE) == (layer, m["source"])
    assert {"serve.sched_host_ms_per_round", "serve.round_ms_p50",
            "serve.decode_wave_device_ms", "serve.prefill_chunk_device_ms",
            "serve.pool_live_share", "serve.device_idle_share",
            "serve.compiles_in_window", "serve.host_unfed_ms_per_round",
            "serve.wave_host_ms", "serve.chunk_host_ms",
            "serve.sched_other_ms_per_round", "serve.host_busy_share",
            "serve.gc_pause_ms_per_round", "serve.dispatch_ahead_share",
            "serve.paged_attn_device_share", "serve.paged_visit_share",
            "serve.moe_experts_device_share", "setup.param_init_s",
            "setup.build_s", "setup.trace_lower_s", "setup.compile_s",
            "setup.cache_load_s", "setup.cache_hit_share",
            "setup.accounted_share"} <= set(mine)
    # what another model's shapes or a prefix cache read: not this cell's
    assert not {"serve.prefix_hit_share", "serve.wave_mfu",
                "serve.hybrid_wave_mfu", "serve.moe_experts_roofline",
                "serve.gated_experts_roofline",
                "serve.state_host_ms_per_round"} & set(mine)
    traffic = harness.read_json(
        f"{harness.BENCH_DIR}/workloads/{CELL}.json")
    assert traffic["kind"] == "serve_closed_routed"
    assert traffic["check"]["pad_to"] % harness.reference_for(
        _file()).QUERY_BLOCK == 0
    assert traffic["check"]["pad_to"] >= \
        traffic["load"]["prompt_len"]["max"] + traffic["check"]["max_tokens"]
    assert traffic["engine"]["max_len"] >= \
        traffic["load"]["prompt_len"]["max"] + \
        traffic["load"]["output_len"]["max"]


def test_the_cell_rehearses_green():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "4100000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    last = notes[-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert "serve_tokens_per_s" in last["end_to_end"]
    assert {"serve.window_host_ms_per_round",
            "serve.window_pages_per_lane"} <= set(last["per_layer"])
    check = next(n for n in notes if n["note"] == "check")
    assert check["tokens_checked"] > 0 and not check["faults"]
    referee = next(n for n in notes if n["note"] == "referee")
    assert referee["correct"] and referee["mean_gap_steps"] < 1.0
