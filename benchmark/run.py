"""Run one cell once: load, warm up, measure, print, exit.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object with the
keys `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` with --trace 1). With --trace 0 the metrics are the cell's
end-to-end metrics, with --trace 1 its per-layer metrics. Earlier lines
are notes (JSON objects with a `note` key) for whoever reads a log. The
result's last key, `check`, and the last lines of standard error give
every number that decided `correct` beside its limit.

Without a TPU, or with fewer chips than the cell needs, or on a device
kind that peaks.json does not hold, the exit code is not 0 and no result
is printed. `--rehearse` is the only way round that: tiny sizes on the
CPU (four virtual devices), notes only, never a metric, never the result
line, exit code 0 if the plumbing holds.
"""
import time

_T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import glob              # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

from . import harness    # noqa: E402  (imports no JAX)


class Context:
    """What a kind of cell gets from the harness: the cell's files, the
    clock of the run, spans and the traced window, notes."""

    def __init__(self, args, entry, cell, config, compiles):
        self.entry, self.cell, self.config = entry, cell, config
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.keep_trace = args.keep_trace
        self.compiles = compiles
        self.phases = {}
        self.window = None
        self.setup_s = None
        self.trace_summary = None
        self.trace_host = None
        self._trace_dir = None

    def note(self, what, **fields):
        print(json.dumps({"note": what, **fields}, default=str), flush=True)

    @contextlib.contextmanager
    def phase(self, name):
        """Host-clock seconds of one part of set-up (or of the check),
        printed in the `setup` note."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = round(
                self.phases.get(name, 0.0) + time.perf_counter() - t, 3)

    def span(self, name):
        """A host span on the profiler's clock; free when nothing traces."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def open_window(self):
        t0 = time.perf_counter()
        self.setup_s = t0 - _T_PROCESS
        self.window = [t0, None]
        self.note("setup", setup_s=round(self.setup_s, 3), **self.phases)
        return t0

    def close_window(self):
        self.window[1] = time.perf_counter()
        return self.window[1]

    @contextlib.contextmanager
    def traced_window(self):
        """Profile what runs inside. The Python tracer is off: it records
        every call and slows the host it is meant to observe. The trace is
        reduced by `reduce_trace`, once the window has closed: reducing it
        here held the interpreter for seconds of an open loop's window."""
        import jax
        from . import trace_reduce
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        ta = time.perf_counter()
        try:
            with self.span(trace_reduce.WINDOW_SPAN):
                yield
        finally:
            tb = time.perf_counter()
            jax.profiler.stop_trace()
        self.trace_host = (ta, tb)

    def reduce_trace(self):
        """The traced window's summary, if there was one; the profiler's
        files are removed."""
        from . import trace_reduce
        out, self._trace_dir = self._trace_dir, None
        if not out:
            return
        try:
            if not self.trace_host:
                return
            paths = sorted(glob.glob(os.path.join(
                out, "plugins", "profile", "*", "*.xplane.pb")))
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under {out}")
            t = time.perf_counter()
            self.trace_summary = trace_reduce.summarize(
                trace_reduce.load(paths[-1]))
            self.note("trace", bytes=os.path.getsize(paths[-1]),
                      reduce_s=round(time.perf_counter() - t, 2),
                      host_window_s=round(
                          self.trace_host[1] - self.trace_host[0], 3))
            if self.keep_trace:
                os.makedirs(self.keep_trace, exist_ok=True)
                shutil.copy(paths[-1], os.path.join(
                    self.keep_trace, self.entry["name"] + ".xplane.pb"))
        finally:
            shutil.rmtree(out, ignore_errors=True)


def _read_metrics(defs, package, workload, rctx):
    """{name: {"value", "unit"}} of the metrics in `defs` that exist in
    this cell and whose reader found something to read."""
    out = {}
    for m in defs:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = harness.load_module(
            package, harness.reader_name(m["name"])).read(rctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m benchmark.run",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; notes only, no result")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the raw .xplane.pb of a traced run to DIR")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = "--xla_force_host_platform_device_count=4"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()

    try:
        bench = harness.load_benchmark()
        entry, cell, config = harness.load_cell(bench, args.workload,
                                                rehearse=args.rehearse)
        import jax
        if args.rehearse:
            # the readers divide by peaks; a rehearsal prints no value
            device, peaks = None, next(iter(harness.peaks_table().values()))
            if len(jax.devices()) < int(entry["chips"]):
                raise harness.BenchmarkError(
                    f"rehearsal needs {entry['chips']} virtual devices")
        else:
            device, peaks = harness.device_info(int(entry["chips"]))
        cache = harness.enable_compile_cache()
        compiles = harness.CompileCounter()
        ctx = Context(args, entry, cell, config, compiles)
        ctx.note("start", workload=entry["name"], config=entry["config"],
                 seed=args.seed, seconds=args.seconds, trace=args.trace,
                 compile_cache=cache, rehearse=args.rehearse,
                 t_s=round(time.perf_counter() - _T_PROCESS, 3))
        try:
            result = harness.load_module("kinds", cell["kind"]).run(ctx)
        finally:
            ctx.reduce_trace()
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    t0, t1 = ctx.window
    in_window = compiles.since(t0, t1)
    rctx = {"entry": entry, "cell": cell, "config": config,
            "shapes": harness.shapes(config), "chips": int(entry["chips"]),
            "peaks": peaks, "obs": result["obs"], "setup_s": ctx.setup_s,
            "compiles_in_window": in_window, "trace": ctx.trace_summary,
            "trace_host": ctx.trace_host, "e2e": {}}
    e2e = _read_metrics(bench["end_to_end"], "e2e_metrics", entry["name"],
                        rctx)
    rctx["e2e"] = {k: v["value"] for k, v in e2e.items()}
    ctx.note("done", window_s=round(t1 - t0, 3), compiles_in_window=in_window,
             compiles_total=len(compiles.times), phases=ctx.phases,
             attempted=result["attempted"], failed=result["failed"],
             correct=result["correct"],
             total_s=round(time.perf_counter() - _T_PROCESS, 3))
    if args.rehearse:
        # counts only: a CPU time is never written under a metric's name
        ctx.note("rehearsal", platform=jax.devices()[0].platform,
                 end_to_end=sorted(e2e),
                 per_layer=sorted(_read_metrics(
                     bench["per_layer"], "layer_metrics", entry["name"],
                     rctx)) if ctx.trace_summary else None,
                 ok=bool(result["correct"] and in_window == 0))
        print("rehearsal on the CPU: not a chip run, no result line",
              file=sys.stderr)
        return 0 if result["correct"] and in_window == 0 else 1

    if args.trace:
        if not ctx.trace_summary:
            print("benchmark: the traced window was never reached",
                  file=sys.stderr)
            return 2
        metrics = _read_metrics(bench["per_layer"], "layer_metrics",
                                entry["name"], rctx)
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_summary["window_s"]
    else:
        metrics = e2e
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(result["correct"] and in_window == 0),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = ctx.trace_summary["breakdown"]
    # every number compared, beside its limit: the result's last key and
    # the last lines of standard error
    line["check"] = {
        name: [v if math.isfinite(v) else repr(v) for v in pair]
        for name, pair in {**result.get("checks", {}),
                           "compiles_in_window": [in_window, 0]}.items()}
    for name, (value, limit) in line["check"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
