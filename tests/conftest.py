"""Test config: force an 8-device virtual CPU mesh so SPMD/collective tests run
without TPU hardware (SURVEY.md §4 implication (b): the reference simulates
clusters with multiprocess-localhost; the XLA analog is
--xla_force_host_platform_device_count)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax

# Tests run on the CPU whatever the machine holds: they need the eight
# virtual devices above, Pallas kernels run in interpret mode, and a chip
# belongs to one process while xdist runs several. The chip is reached
# only through chip_smoke.py / bench.py. Must come before any backend use.
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: XLA:CPU compiles dominate suite wall time;
# warm re-runs skip them (measured ~35% off the heavy files). The one
# cache rule lives in paddle_tpu/utils/compile_cache.py. Disable with
# PT_NO_COMPILE_CACHE=1 when debugging compiler issues.
if not os.environ.get("PT_NO_COMPILE_CACHE"):
    from paddle_tpu.utils import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy non-parity permutations excluded from the tier-1 "
        "budgeted run (selected with -m 'not slow')")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    pt.seed(1234)
    yield


@pytest.fixture(scope="session")
def chaos_train():
    """scripts/chaos_train.py loaded ONCE per pytest session: the
    kill/resume parity harness caches its per-(mesh, zero_stage) golden
    trajectories inside the module, so test_resume / test_chaos /
    test_sharded_resume share one set of golden runs instead of each
    file recomputing them (the goldens are several full training fits —
    real tier-1 wall time)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "chaos_train.py")
    spec = importlib.util.spec_from_file_location("_t1_chaos_train", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
