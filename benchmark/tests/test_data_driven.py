"""A later PR adds files and BENCHMARK.json entries and edits nothing
that is there. Shown here: in a copy of the benchmark, a dummy
configuration, a dummy cell with traffic of its own, a dummy per-layer
metric, a dummy end-to-end metric and a dummy kind of cell are added as
new files and new entries only, and the one command runs the new cell."""
import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

KIND = '''
"""A kind of cell that serves nothing: opens and closes a window."""
import time


def run(ctx):
    t0 = ctx.open_window()
    time.sleep(0.05)
    t1 = ctx.close_window()
    return {"attempted": 1, "failed": 0, "correct": True,
            "memory_peak_bytes": None,
            "obs": {"kind": "noop", "window": (t0, t1),
                    "answer": ctx.cell["load"]["answer"]
                    * ctx.config["hidden_size"]}}
'''
READER = '''
"""Reads the dummy kind's one observation."""
LAYER, SOURCE = "nothing", "program_counter"


def read(ctx):
    return ctx["obs"].get("answer")
'''


def test_new_files_and_entries_are_enough(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_benchmark()
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("run.py", "harness.py", "loadgen.py",
                        "trace_reduce.py", "kinds/train.py")}

    (root / "benchmark/configs/dummy.json").write_text(json.dumps({
        "name": "dummy", "source": "https://example.org/dummy",
        "hidden_size": 6, "reduced": [], "shapes": {"hidden": "hidden_size"},
        "program": {}, "reference": "gpt2"}))
    (root / "benchmark/workloads/dummy-noop.json").write_text(json.dumps({
        "name": "dummy-noop", "config": "dummy", "traffic": "noop",
        "kind": "noop", "load": {"answer": 7}}))
    (root / "benchmark/kinds/noop.py").write_text(KIND)
    (root / "benchmark/layer_metrics/answer.py").write_text(READER)
    (root / "benchmark/e2e_metrics/answer_twice.py").write_text(
        READER.replace('.get("answer")', '["answer"] * 2'))
    bench["configs"].append({
        "name": "dummy", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy.json", "reduced": [], "why": "-"})
    bench["workloads"].append({
        "name": "dummy-noop", "config": "dummy", "traffic": "noop",
        "chips": 1, "why": "-"})
    bench["end_to_end"].append({
        "name": "answer_twice", "unit": "1", "better": "higher",
        "bound": 0.01, "source": "host_clock", "workloads": ["dummy-noop"]})
    bench["per_layer"].append({
        "name": "twice.answer", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "nothing",
        "moves": "answer_twice", "workloads": ["dummy-noop"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, PYTHONPATH=harness.ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "dummy-noop",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    assert all("note" in n for n in notes)            # never a result line
    last = notes[-1]
    assert last["note"] == "rehearsal" and last["ok"]
    assert last["end_to_end"] == ["answer_twice", "setup_s"]
    # nothing that was there was edited
    for p, content in before.items():
        assert (root / "benchmark" / p).read_bytes() == content
    # and the real cells are still found in the copy
    bench2 = harness.load_benchmark(str(root))
    for w in bench2["workloads"]:
        harness.load_cell(bench2, w["name"], root=str(root))


def test_every_name_in_benchmark_json_resolves_to_a_file():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        entry, cell, config = harness.load_cell(bench, w["name"])
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "kinds", cell["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "reference", config["reference"] + ".py"))
        assert set(config["reduced"]) == set(harness.find_entry(
            bench["configs"], w["config"], "config")["reduced"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "e2e_metrics", m["name"] + ".py"))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        reader = harness.load_module("layer_metrics",
                                     harness.reader_name(m["name"]))
        # the reader's own file says which layer it reads and from where
        assert (reader.LAYER, reader.SOURCE) == (m["layer"], m["source"])
        # a per-layer metric is reported only where the metric it moves is
        moved = harness.find_entry(bench["end_to_end"], m["moves"], "metric")
        assert set(m["workloads"]) <= set(moved.get(
            "workloads", [w["name"] for w in bench["workloads"]]))


def test_no_chip_no_result():
    """Here JAX is held to the CPU: the command must fail and print no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "gpt2s-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not [line for line in out.stdout.splitlines()
                if line.startswith("{") and '"metrics"' in line]
