"""The dense hybrid configuration's files: its reference against the
program at the `rehearse` sizes, the parameter count and the cell's memory
plan from the files' own keys, the chunk's and the wave's operations and
bytes and the new readers against numbers worked out by hand, where the
benchmark lists the cell, and the cell's rehearsal."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import flops_granite as cost, harness
from benchmark.layer_metrics import (ssm_chunk_mfu, ssm_scan_pad_share,
                                     ssm_step_live_share, ssm_wave_mfu)

CONFIG = "granite-4.0-h-micro"
CELL = "granite4hm-serve-longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _file():
    return harness.read_json(f"{harness.BENCH_DIR}/configs/{CONFIG}.json")


def _cell():
    return harness.read_json(f"{harness.BENCH_DIR}/workloads/{CELL}.json")


def _tiny():
    cfg = harness.overlay(_file(), _file()["rehearse"])
    cfg["dtype"] = "float32"
    cfg["program"] = harness.overlay(
        cfg["program"], {"kwargs": {"param_dtype": "float32"}})
    return cfg


def _parameters(cfg):
    """The count from the file's own keys, as `reduced_why` works it out."""
    h, d_inner = cfg["hidden_size"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    mamba = (h * (d_inner + conv_dim + cfg["mamba_n_heads"])
             + cfg["mamba_d_conv"] * conv_dim + conv_dim
             + 3 * cfg["mamba_n_heads"] + d_inner + d_inner * h)
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    attention = 2 * h * h + 2 * h * kv
    layer = 3 * h * cfg["shared_intermediate_size"] + 2 * h
    kinds = cfg["layer_types"]
    return (kinds.count("mamba") * (mamba + layer)
            + kinds.count("attention") * (attention + layer)
            + cfg["vocab_size"] * h + h)


def test_reference_equals_the_program_forward():
    """Both float32, on the benchmark's seeded weights at the `rehearse`
    sizes (one period of ten layers): the same function to rounding, 2e-5
    of the largest logit."""
    import jax.numpy as jnp
    cfg = _tiny()
    model, w = harness.build_model(cfg, seed=3)
    model.eval()
    assert sum(int(np.prod(a.shape)) for a in w.values()) == _parameters(cfg)
    ref = harness.reference_for(cfg)
    rw = ref.from_state_dict(w, harness.shapes(cfg)["layers"])
    ids = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 75)).astype(np.int32)
    want = np.asarray(ref.forward(rw, ids, cfg))
    params, buffers = model.functional_state()
    got = np.asarray(model.functional_call(params, buffers,
                                           jnp.asarray(ids))[0]._data)
    assert want.shape == got.shape == (2, 75, cfg["vocab_size"])
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max() + 1e-7
    rows = [0, 17, 74]
    np.testing.assert_allclose(
        np.asarray(ref.forward(rw, ids, cfg, rows=rows)), want[:, rows],
        atol=1e-6)
    # the referee's control is another function: 8-bit operands move it
    low = np.asarray(ref.forward(rw, ids, cfg, lower="float8_e4m3fn"))
    assert np.abs(low - want).max() > 1e-2 * np.abs(want).max()


def test_nothing_is_cut_and_the_count_is_the_files():
    cfg = _file()
    assert cfg["reduced"] == [] and cfg["dtype"] == "bfloat16"
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 40
    assert cfg["layer_types"].count("attention") == 4
    assert _parameters(cfg) == 3_191_396_096
    assert "3,191,396,096 parameters (3,191.4 M), 6.38 GB" in \
        cfg["reduced_why"]
    sh = harness.shapes(cfg)
    assert sh["pattern"] == "".join(
        "M" if t == "mamba" else "*" for t in cfg["layer_types"])
    assert sh["head_dim"] * sh["heads"] == sh["hidden"]
    assert sh["scan_chunk"] == 256 and sh["ffn"] == 8192 and sh["tied_head"]
    # what the byte counts call a matmul weight is every parameter but
    # the norms, the conv bias and the three vectors a head
    assert _parameters(cfg) - cost.matmul_weights(sh) == \
        36 * (4352 + 3 * 64 + 4096) + 40 * 2 * 2048 + 2048


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_key_is_in_the_file_as_published():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == CONFIG)
    cfg = _file()
    assert cfg["source"] == row["source_url"]
    assert {k: cfg[k] for k in row["config"]} == row["config"]


def test_the_cells_memory_plan_is_what_its_file_says():
    cfg, cell = _file(), _cell()
    eng, sh = cell["engine"], harness.shapes(_file())
    weights = 2 * _parameters(cfg)
    state, taps = (sh["mamba_heads"] * sh["mamba_head_dim"]
                   * sh["mamba_state"], 3 * (4096 + 2 * 128))
    record = 36 * (4 * state + 2 * taps)
    kv_position = 4 * 2 * sh["kv_heads"] * sh["head_dim"] * 2
    pool = eng["num_slots"] * eng["max_len"] * kv_position
    assert round(weights / 1e9, 2) == 6.38
    assert round(record / 1e6, 1) == 76.4 and kv_position == 8192
    assert round(eng["num_slots"] * record / 1e9, 2) == 2.45
    assert round(pool / 1e9, 2) == 4.56
    assert round((weights + eng["num_slots"] * record + pool) / 1e9, 1) \
        == 13.4
    for said in ("6.38 GB", "76.4 MB", "2.45 GB", "8,192 bytes", "4.56 GB",
                 "13.4 GB"):
        assert said in eng["why"], said
    # the table of ISSUE 35, letter for letter
    load = cell["load"]
    assert (eng["num_slots"], eng["max_len"], eng["block_size"],
            eng["chunk"]) == (32, 17408, 16, 512)
    assert eng["chunk"] == 2 * cfg["mamba_chunk_size"]
    assert load["arrivals"] == {"process": "closed", "clients": 64}
    assert load["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                  "sigma": 0.6, "min": 2048, "max": 16384}
    assert load["output_len"] == {"dist": "lognormal", "median": 320,
                                  "sigma": 0.6, "min": 64, "max": 896}
    assert load["requests"] == 2048 and load["preroll_max_s"] == 120
    assert "shape_seed" in load and "shared_prefix" not in load
    assert load["prompt_len"]["max"] + load["output_len"]["max"] \
        <= 17280 < eng["max_len"]
    assert cell["kind"] in ("serve_closed", "serve_closed_routed")
    assert cell["check"]["pad_to"] == eng["max_len"]
    assert cell["check"]["control"] == {"lower": "float8_e4m3fn"}


# ------------------------------------------------- operations and bytes
SH = {"pattern": "M*", "hidden": 4, "vocab": 10, "ffn": 6, "heads": 2,
      "kv_heads": 1, "head_dim": 2, "mamba_heads": 2, "mamba_head_dim": 4,
      "mamba_groups": 1, "mamba_state": 3, "conv_kernel": 4,
      "scan_chunk": 4}


def test_costs_equal_the_hand_counts():
    # one mixer: in_proj 4 x (8 + 14 + 2), taps 4 x 14, out_proj 8 x 4 =
    # 184; attention 4 x 8 + 4 x 4 = 48; two MLPs of 3 x 4 x 6; head 40
    assert cost.mlp_weights(SH) == 72
    assert cost.matmul_weights(SH) == 184 + 48 + 2 * 72 + 40 == 416
    # a token sees 2.5 positions of its scan chunk of 4: c.b^T 2 x 3 and
    # the sum of x 2 x 4 x 2 heads each; state in and out 4 x 2 x 4 x 3
    assert cost.scan_ops_per_token(SH) == 2.5 * (6 + 16) + 96 == 151
    # a chunk of 3 tokens attending 12 positions: 2 x 3 x 376 + the head
    # once 80 + 3 scans of 151 + q.k and p.v 4 x 4 x 12; bytes: 416
    # weights, one record read and written 2 x (4 x 24 + 2 x 42), K and V
    # rows of 4 x 2 bytes for the last query's 4 + 1.5 keys
    assert cost.prefill_chunk_cost(SH, 3, 12) == (
        2256 + 80 + 453 + 192, 832 + 360 + 44)
    # a wave of 2 lanes attending 7 positions in an engine of 4 slots:
    # 2 x 2 x 416 + 2 x 5 x 24 + 4 x 4 x 7; bytes: the weights, FOUR
    # records read and written, 7 K and V rows
    assert cost.decode_wave_cost(SH, 2, 7, 4) == (
        1664 + 240 + 112, 832 + 4 * 360 + 56)


def test_costs_at_the_published_sizes():
    """The issue's arithmetic: a wave reads 6.4 GB of weights and reads
    and writes 4.8 GB of state; a chunk of 512 is compute-bound."""
    sh = harness.shapes(_file())
    peaks = next(iter(harness.peaks_table().values()))
    assert round(2 * cost.matmul_weights(sh) / 1e9, 2) == 6.38
    ops, nbytes = cost.decode_wave_cost(sh, 29, 29 * 7500, 32)
    assert 12.9e9 < nbytes < 13.1e9
    assert nbytes / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops_per_s"]
    ops, nbytes = cost.prefill_chunk_cost(sh, 490, 490 * 4000)
    assert 6.1e9 < ops / 490 < 6.6e9
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]


# ------------------------------------------------------------- the readers
def _ctx(snap0=None, snap1=None):
    rounds = [(1.0, 2.0, 2, 6, 0, 5), (2.0, 3.0, 2, 8, 1, 5),
              (3.0, 4.0, 0, 0, 1, 5), (4.0, 5.0, 0, 0, 0, 5)]
    prompt = types.SimpleNamespace(planned=types.SimpleNamespace(
        prompt=list(range(7))))
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
                    "records": [prompt],
                    "engine": {"num_slots": 4, "chunk": 5},
                    "snap0": snap0 or {}, "snap1": snap1 or {}}}


COUNTS = ({"prefill_chunks": 1, "prefill_tokens": 4,
           "ssm_records_stepped": 8, "ssm_lanes_stepped": 2},
          {"prefill_chunks": 5, "prefill_tokens": 16,
           "ssm_records_stepped": 24, "ssm_lanes_stepped": 14})


def test_readers_equal_the_hand_computed_numbers():
    ctx = _ctx(*COUNTS)
    # the traced rounds with a lane decoding: 2 lanes, 7 positions, 4
    # slots: 2,016 operations, 2,328 bytes; memory binds, 23.28 ms of the
    # median wave's 30
    assert ssm_wave_mfu.read(ctx) == pytest.approx(77.6)
    # the window's mean chunk: 3 tokens; a prompt of 7 attends 28
    # positions, 4 a token, so 12: 2,981 operations, 1,236 bytes; memory
    # binds at these sizes, 12.36 ms of the median chunk's 40
    assert ssm_chunk_mfu.attended_per_token(ctx["obs"]["records"]) == 4.0
    assert ssm_chunk_mfu.read(ctx) == pytest.approx(30.9)
    # 4 chunks of 5 rows carried 12 tokens
    assert ssm_scan_pad_share.read(ctx) == pytest.approx(40.0)
    # 12 lanes decoded in waves that stepped 16 records
    assert ssm_step_live_share.read(ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("reader,layer,source", [
    (ssm_wave_mfu, "ssm_dense_model_step", "device_trace"),
    (ssm_chunk_mfu, "ssm_dense_model_step", "device_trace"),
    (ssm_scan_pad_share, "paged_engine", "program_counter"),
    (ssm_step_live_share, "paged_engine", "program_counter")])
def test_readers_return_none_where_there_is_nothing_to_read(reader, layer,
                                                            source):
    """A program without the counters (the parent of the PR that added
    them), an untraced run, a configuration without a scan, a kind of
    cell without snapshots: nothing, and no error."""
    assert (reader.LAYER, reader.SOURCE) == (layer, source)
    ctx = _ctx(*COUNTS)
    bare = {**ctx["trace"], "module_s": {}}
    empty = {**ctx, "obs": {"window": (0.0, 6.0)}}
    parent = ({"prefill_chunks": 0, "prefill_tokens": 0},) * 2
    cases = [{**ctx, "trace": bare, "obs": _ctx(*parent)["obs"]},
             {**empty, "trace": None, "trace_host": None}]
    if source == "device_trace":
        cases += [{**ctx, "trace": None},
                  {**ctx, "shapes": {"layers": 2, "pattern": "ME"}}]
    for case in cases:
        assert reader.read(case) is None


def test_the_benchmark_lists_the_cell_where_its_readers_apply():
    bench = harness.load_benchmark()
    entry = harness.find_entry(bench["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (CONFIG, "serve-longdoc", 1)
    assert harness.find_entry(bench["configs"], CONFIG,
                              "config")["reduced"] == []
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {"serve." + n for n in (
        "sched_host_ms_per_round", "round_ms_p50", "decode_wave_device_ms",
        "prefill_chunk_device_ms", "pool_live_share", "device_idle_share",
        "compiles_in_window", "host_unfed_ms_per_round", "wave_host_ms",
        "chunk_host_ms", "sched_other_ms_per_round", "host_busy_share",
        "state_host_ms_per_round", "paged_attn_device_share",
        "hybrid_paged_attn_roofline", "ssm_chunk_mfu", "ssm_wave_mfu",
        "ssm_scan_pad_share", "ssm_step_live_share")}
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [])] == ["serve_tokens_per_s"]
    # the accepted paged-attention roofline takes this configuration's
    # shapes as they are: it counts once for every `*` of the pattern
    from benchmark import flops_hybrid
    sh = harness.shapes(_file())
    assert flops_hybrid.paged_attention_cost(sh, 1000) == (
        4 * 4.0 * 2048 * 1000, 4 * 2.0 * 512 * 2 * 1000)


def test_the_cell_rehearses_green():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "3000000019", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    assert all("note" in n for n in notes)            # never a result line
    last = notes[-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert last["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    # the counters' readers find something on the CPU too; the device's
    # need a device
    assert {"serve.ssm_scan_pad_share", "serve.ssm_step_live_share",
            "serve.state_host_ms_per_round"} <= set(last["per_layer"])
    control = [n for n in notes if n["note"] == "control"]
    assert control and control[0]["forward"] == {"lower": "float8_e4m3fn"}
