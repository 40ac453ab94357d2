"""The one rule for JAX's persistent compilation cache.

If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here sets a directory. Otherwise the cache is `<checkout>/.jax_cache`:
a fixed path, because the path is part of the cache key and a directory
that moves never hits. Entry points and `tests/conftest.py` call
`enable()`; nothing else in the tree names a cache directory.
"""
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable():
    """Turn the persistent cache on; returns the directory in use. The
    compile listener goes in with it (`telemetry.process_summary` then
    says what was compiled and what the cache held), so that a process
    hears its first program."""
    from . import telemetry
    telemetry.install_compile_tracking()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
