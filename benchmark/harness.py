"""What every kind of cell shares: finding files by the names in
BENCHMARK.json, building the configuration's model through the program's
public constructor, the device check, the compile counter.

Nothing here knows a cell, a configuration or a metric by name: a later
PR adds files and BENCHMARK.json entries, and edits nothing that is here.
"""
import importlib
import json
import os
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(RuntimeError):
    """The run cannot produce a result line (no chip, bad name, ...)."""


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return read_json(os.path.join(root, "BENCHMARK.json"))


def find_entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(f"no {what} named {name!r} in BENCHMARK.json "
                         f"(have {[e['name'] for e in entries]})")


def load_cell(bench, name, root=ROOT, rehearse=False):
    """(workload entry, traffic file, configuration file) of one cell."""
    entry = find_entry(bench["workloads"], name, "workload")
    cell = read_json(os.path.join(root, bench["paths"][0], "workloads",
                                  name + ".json"))
    cfg_entry = find_entry(bench["configs"], entry["config"], "config")
    config = read_json(os.path.join(root, cfg_entry["file"]))
    if cell.get("config") != entry["config"] or \
            cell.get("traffic") != entry["traffic"]:
        raise BenchmarkError(
            f"workloads/{name}.json says config {cell.get('config')!r} "
            f"traffic {cell.get('traffic')!r}; BENCHMARK.json says "
            f"{entry['config']!r} {entry['traffic']!r}")
    if rehearse:
        cell = overlay(cell, cell.get("rehearse", {}))
        config = overlay(config, config.get("rehearse", {}))
    return entry, cell, config


def overlay(base, over):
    """`over` laid on `base`, nested groups merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = (overlay(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def shapes(config):
    """The configuration's `shapes` group with key references resolved:
    the sizes the FLOP and byte functions and the generator need."""
    out = {}
    for k, v in config["shapes"].items():
        out[k] = config[v] if isinstance(v, str) and v in config else v
    return out


def import_attr(path):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def load_module(package, name):
    """`benchmark.<package>.<name>`: kinds, readers and references are
    found by the name the data files give."""
    return importlib.import_module(f"{__package__}.{package}.{name}")


def reader_name(metric_name):
    """`tpot.decode_wave_device_ms` is read by `decode_wave_device_ms`:
    what stands before the last dot names the end-to-end metric the entry
    moves, so that one reader serves cells whose judged metric differs."""
    return metric_name.rpartition(".")[2]


# ------------------------------------------------------------------ device
def peaks_table():
    """{device_kind: published peaks}, benchmark/peaks.json."""
    return read_json(os.path.join(BENCH_DIR, "peaks.json"))["kinds"]


def device_info(chips):
    """(device dict for the result line, the peaks of its kind). Fails
    without a TPU, with fewer chips than the cell asks for, or on a kind
    the table does not hold: never a CPU fallback, never a default peak."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchmarkError(f"needs a TPU, JAX found {devs[0].platform!r} "
                             f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchmarkError(f"cell needs {chips} chips, JAX found "
                             f"{len(devs)}")
    table = peaks_table()
    kind = devs[0].device_kind
    if kind not in table:
        raise BenchmarkError(f"no published peak for device kind {kind!r} "
                             f"in peaks.json (have {sorted(table)})")
    return ({"platform": devs[0].platform, "kind": kind,
             "count": len(devs)}, table[kind])


def memory_peak_bytes(devices, temporaries=0):
    """Peak bytes on the fullest of `devices`: the runtime's own peak of
    live arrays, or what is live now plus `temporaries`, whichever is
    larger. The runtime counts live arrays and leaves a running program's
    temporaries out (it read 1.66 GB for a train step the compiler plans
    at 13.2 GB and refuses at twice the batch), so a kind of cell whose
    program needs much scratch passes what the compiler plans for it.
    None where the backend reports nothing."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(max(stats["peak_bytes_in_use"],
                             stats.get("bytes_in_use", 0) + temporaries))
    return max(peaks) if peaks else None


# ----------------------------------------------------------------- compiles
class CompileCounter:
    """Host-clock time of every backend compilation (or load from the
    persistent cache) this process makes. `since(t)` is what a window may
    not have."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def since(self, t0, t1=None):
        return sum(1 for t in self.times
                   if t >= t0 and (t1 is None or t <= t1))


def enable_compile_cache():
    """The program's one cache rule (JAX_COMPILATION_CACHE_DIR if set,
    else <checkout>/.jax_cache), with the thresholds lowered so that the
    serving path's small eager programs are kept as well: each of them is
    a compilation of its own in every new process otherwise."""
    import jax
    from paddle_tpu.utils import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# -------------------------------------------------------------------- model
def install_weights(model, config, seed):
    """Give `model` the benchmark's weights for `seed` (made on the
    device in one call, weights.py); returns {state_dict name: array}."""
    from .weights import make_weights
    named = list(model.named_parameters())
    wspec = config["weights"]
    std = wspec["std"]
    weights = make_weights(
        [(n, p.shape) for n, p in named], seed,
        std=config[std] if isinstance(std, str) else std,
        norm_scale=wspec["norm_scale"], bias=wspec["bias"],
        dtype=config["dtype"])
    for n, p in named:
        p.set_value(weights[n])
    return weights


def build_model(config, seed, phase=None):
    """The configuration's model through the program's public
    constructor, in the configuration's dtype, holding the benchmark's
    weights. Returns (model, {state_dict name: array}). `phase(name)`
    times the parts for the set-up note."""
    import contextlib
    import jax
    import jax.numpy as jnp
    phase = phase or (lambda name: contextlib.nullcontext())
    prog = config["program"]
    kwargs = {k: config[src] for k, src in prog["kwargs_from"].items()}
    kwargs.update(prog.get("kwargs", {}))
    with phase("model_ctor"):
        model = import_attr(prog["model"])(
            import_attr(prog["config"])(**kwargs))
    # The constructor has drawn float32 values on the host, which the
    # benchmark's weights replace. `set_value` keeps the dtype a parameter
    # already has, so `to` comes first, and on the host: on the default
    # device it would send every float32 value to the chip to cast it.
    with phase("model_to"), \
            jax.default_device(jax.local_devices(backend="cpu")[0]):
        model.to(dtype=jnp.dtype(config["dtype"]))
    with phase("model_weights"):
        weights = install_weights(model, config, seed)
        jax.block_until_ready(weights)
    return model, weights


def reference_for(config):
    """The module of the configuration's plain float32 forward
    (`from_state_dict`, `forward`)."""
    return load_module("reference", config["reference"])
