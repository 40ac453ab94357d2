"""Process-global framework state: device/place, RNG, flags, grad & functional modes.

TPU-native redesign of the reference's process-wide services:
  - Place taxonomy + DeviceContextPool (ref paddle/fluid/platform/place.h,
    device_context.h:691) -> a current-Place holder; JAX/PJRT owns streams.
  - gflags FLAGS_* (ref platform/flags.cc) -> a plain dict with set_flags/get_flags.
  - Generator RNG (ref framework/generator.h:93) -> a split-on-demand JAX PRNG key chain.
Grad mode (no_grad) and functional mode (tracing under jax.jit/jax.grad, where the
tape must NOT record) are contextvars so they compose with threads.
"""
import contextlib
import contextvars
import threading

import jax
import numpy as np

from .dtype import float32, convert_dtype

# --------------------------------------------------------------------------- places


class Place:
    """Device placement descriptor. TPU-native: maps onto a jax.Device."""

    def __init__(self, kind: str, device_id: int = 0):
        self.kind = kind
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def jax_device(self):
        # local_devices: in a multi-process job "device 0" must mean THIS
        # process's first device — global jax.devices()[0] belongs to rank 0
        # and is not addressable from other ranks
        devs = [d for d in jax.local_devices() if d.platform == self.kind]
        if not devs:  # no such device here: API parity off-chip, on host
            devs = [d for d in jax.local_devices()
                    if d.platform == "cpu"] or jax.devices("cpu")
        return devs[min(self.device_id, len(devs) - 1)]

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_tpu_place(self):
        return self.kind != "cpu"

    # reference-API aliases
    is_gpu_place = is_tpu_place


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id=0):
    return Place("tpu", device_id)


# Reference compat: CUDAPlace scripts run on the accelerator place.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


class _GlobalState(threading.local):
    pass


_state = _GlobalState()


def _detect_default_place():
    for d in jax.local_devices():
        if d.platform != "cpu":
            return Place(d.platform, 0)
    return Place("cpu", 0)


_current_place = None
_default_dtype = float32


def set_device(device):
    """paddle.set_device analog: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias of tpu)."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    device = str(device)
    if ":" in device:
        kind, idx = device.split(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("gpu", "cuda", "xpu", "npu", "tpu"):
        kind = "tpu"
    _current_place = Place(kind, idx)
    return _current_place


def get_device():
    p = get_place()
    return f"{p.kind}:{p.device_id}"


def get_place():
    global _current_place
    if _current_place is None:
        _current_place = _detect_default_place()
    return _current_place


def set_default_dtype(d):
    global _default_dtype
    _default_dtype = convert_dtype(d)


def get_default_dtype():
    return _default_dtype


# --------------------------------------------------------------------------- RNG


def host_device():
    """THIS process's host CPU jax device — cheap bookkeeping (PRNG splits,
    init) runs here instead of as eager dispatches to the accelerator.
    local_devices, not devices: in a multi-process job the global cpu[0]
    belongs to rank 0 and is unaddressable elsewhere."""
    return jax.local_devices(backend="cpu")[0]


class Generator:
    """Split-on-demand PRNG chain (ref framework/generator.h:93 kept functional:
    every draw advances the chain by splitting, so eager ops stay reproducible).
    Key management happens on host CPU — a split is 8 bytes of work and must
    not pay a device round-trip."""

    def __init__(self, seed=0):
        # Lazy: no JAX backend is touched until the first draw. Importing the
        # framework must never initialize a device (ref initializes devices
        # explicitly from bootstrap, platform/init.h:36 — not at link time);
        # a flaky TPU plugin must not make the package unimportable.
        self._seed = seed
        self._lock = threading.Lock()
        self._key = None

    def manual_seed(self, seed):
        with self._lock:
            self._seed = seed
            self._key = None
        return self

    def next_key(self):
        with self._lock:
            with jax.default_device(host_device()):
                if self._key is None:
                    self._key = jax.random.PRNGKey(self._seed)
                self._key, sub = jax.random.split(self._key)
            return sub

    @property
    def initial_seed(self):
        return self._seed


_default_generator = Generator(0)


def rng_state():
    """Snapshot of the default generator's split-on-demand chain — the
    EXACT point the chain is at, not just the seed. `key` is a host
    numpy copy of the current chain key (None before the first draw):
    restoring it via `set_rng_state` makes the next `next_rng_key()`
    return bitwise what an uninterrupted process would have drawn — the
    contract exact-resume checkpoints (utils/resume.py) rely on for
    dropout streams."""
    g = _default_generator
    with g._lock:
        key = None if g._key is None else np.asarray(g._key).copy()
        return {"seed": int(g._seed), "key": key}


def set_rng_state(st):
    """Restore a `rng_state()` snapshot into the default generator."""
    g = _default_generator
    with g._lock:
        if "seed" in st and st["seed"] is not None:
            g._seed = int(st["seed"])
        key = st.get("key")
        if key is None:
            g._key = None
        else:
            # uncommitted, exactly like Generator.next_key creates keys:
            # a device_put here would COMMIT the key, committedness
            # propagates through the compiled step to its outputs, and
            # the second post-resume call would cache-miss — one silent
            # recompile per resume (chaos_train's compile-once check
            # catches this)
            with jax.default_device(host_device()):
                g._key = jax.numpy.asarray(np.asarray(key))


def numpy_rng_state():
    """The global numpy RNG (MT19937) state as a picklable dict — the
    data-order half of exact resume: DataLoader shuffle permutations
    and per-item numpy transforms draw from it. Checkpoints record both
    the CURRENT state and the state at the start of the in-progress
    epoch (the latter is what a resume fast-forward replays)."""
    alg, keys, pos, has_gauss, cached = np.random.get_state()
    return {"alg": str(alg), "keys": np.asarray(keys).copy(),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached_gaussian": float(cached)}


def set_numpy_rng_state(st):
    """Restore a `numpy_rng_state()` snapshot into the global numpy RNG."""
    np.random.set_state((st["alg"], np.asarray(st["keys"]), int(st["pos"]),
                         int(st["has_gauss"]), float(st["cached_gaussian"])))


def seed(s):
    """paddle.seed analog."""
    _default_generator.manual_seed(int(s))
    np.random.seed(int(s) % (2 ** 32))
    return _default_generator


def default_generator():
    return _default_generator


def next_rng_key():
    traced = _functional_rng.get()
    if traced is not None:
        return traced.next_key()
    return _default_generator.next_key()


# --------------------------------------------------------------------------- flags

_FLAGS = {
    "FLAGS_check_nan_inf": False,           # ref platform/flags.cc:44
    "FLAGS_unused_var_check": False,        # ref framework/unused_var_check.cc
    "FLAGS_sort_sum_gradient": False,       # ref platform/flags.cc:527
    "FLAGS_cudnn_deterministic": True,      # XLA is deterministic by default
    "FLAGS_matmul_precision": "default",    # TPU knob: default|high|highest
    "FLAGS_eager_op_cache": True,
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_use_donated_buffers": True,
}


def _bootstrap_env_flags():
    """Parse FLAGS_* env vars at import (ref python/paddle/fluid/__init__.py
    __bootstrap__ passing env gflags to core.init_gflags)."""
    import os
    for key, default in list(_FLAGS.items()):
        raw = os.environ.get(key)
        if raw is None:
            continue
        try:
            if isinstance(default, bool):
                _FLAGS[key] = raw.lower() in ("1", "true", "yes", "on")
            elif isinstance(default, int):
                _FLAGS[key] = int(raw)
            elif isinstance(default, float):
                _FLAGS[key] = float(raw)
            else:
                _FLAGS[key] = raw
        except ValueError:
            import warnings
            warnings.warn(
                f"ignoring malformed env var {key}={raw!r}; keeping "
                f"default {default!r}")


_bootstrap_env_flags()


def set_flags(flags: dict):
    for k, v in flags.items():
        _FLAGS[k] = v


def get_flags(keys=None):
    if keys is None:
        return dict(_FLAGS)
    if isinstance(keys, str):
        keys = [keys]
    return {k: _FLAGS.get(k) for k in keys}


def get_flag(key, default=None):
    return _FLAGS.get(key, default)


# --------------------------------------------------------------------------- modes

_grad_enabled = contextvars.ContextVar("grad_enabled", default=True)
_functional_mode = contextvars.ContextVar("functional_mode", default=False)
_functional_rng = contextvars.ContextVar("functional_rng", default=None)
_static_recorder = contextvars.ContextVar("static_recorder", default=None)


def get_static_recorder():
    """Active ProgramDesc recorder (static/program.py) or None. When set,
    ops/dispatch.apply records every op into the current Program's desc
    (ref imperative/tracer.cc:132 TraceOp writing OpDesc in static mode)."""
    return _static_recorder.get()


@contextlib.contextmanager
def static_recorder_ctx(rec):
    tok = _static_recorder.set(rec)
    try:
        yield
    finally:
        _static_recorder.reset(tok)


class _TracedRng:
    """Split-on-demand chain over a traced PRNG key — lets dropout etc. draw
    fresh randomness inside jit'd train steps (the key is a step input, so each
    compiled step gets a new mask instead of a baked-in constant)."""

    def __init__(self, key):
        self._key = key

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub


@contextlib.contextmanager
def functional_rng_ctx(key):
    tok = _functional_rng.set(_TracedRng(key))
    try:
        yield
    finally:
        _functional_rng.reset(tok)


def is_grad_enabled():
    return _grad_enabled.get()


def is_functional_mode():
    return _functional_mode.get()


@contextlib.contextmanager
def no_grad_ctx():
    tok = _grad_enabled.set(False)
    try:
        yield
    finally:
        _grad_enabled.reset(tok)


@contextlib.contextmanager
def enable_grad_ctx():
    tok = _grad_enabled.set(True)
    try:
        yield
    finally:
        _grad_enabled.reset(tok)


@contextlib.contextmanager
def functional_mode_ctx():
    """Active while tracing a pure function under jax.jit/grad: the eager tape is
    bypassed and autodiff is delegated to JAX (the performance path)."""
    tok = _functional_mode.set(True)
    try:
        yield
    finally:
        _functional_mode.reset(tok)


_amp_state = contextvars.ContextVar("amp_state", default=None)


def get_amp_state():
    return _amp_state.get()


@contextlib.contextmanager
def amp_guard_ctx(cfg):
    tok = _amp_state.set(cfg)
    try:
        yield
    finally:
        _amp_state.reset(tok)


class no_grad:
    """Usable as decorator and context manager, like paddle.no_grad."""

    def __enter__(self):
        self._tok = _grad_enabled.set(False)
        return self

    def __exit__(self, *exc):
        _grad_enabled.reset(self._tok)
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with no_grad_ctx():
                return fn(*a, **k)

        return wrapper

