"""The benchmark's own tests run on the CPU, like the repository's: the
chip is reached through `python -m benchmark.run` alone.

    python -m pytest benchmark/tests -q
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
