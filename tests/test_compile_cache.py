"""The one compile-cache rule (utils/compile_cache.py): where
JAX_COMPILATION_CACHE_DIR is set no code sets a directory; unset, the
cache is <checkout>/.jax_cache."""
import os

import jax
import pytest

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# spelled in pieces so that a grep for the option finds the helper alone
OPTION = "_".join(("jax", "compilation", "cache", "dir"))


@pytest.fixture
def restore_cache_dir():
    was = getattr(jax.config, OPTION)
    yield
    jax.config.update(OPTION, was)


def test_unset_env_means_the_checkouts_own_directory(monkeypatch,
                                                     restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update(OPTION, None)
    assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
    assert getattr(jax.config, OPTION) == os.path.join(REPO, ".jax_cache")


def test_set_env_is_left_alone(monkeypatch, restore_cache_dir, tmp_path):
    """JAX reads the variable itself at import; the helper must not
    overwrite whatever directory is configured."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update(OPTION, "sentinel")
    assert compile_cache.enable() == str(tmp_path)
    assert getattr(jax.config, OPTION) == "sentinel"


def test_no_other_file_names_a_cache_directory():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for f in files:
            if f.endswith((".py", ".sh")):
                with open(os.path.join(root, f), errors="ignore") as fh:
                    if OPTION in fh.read():
                        hits.append(os.path.relpath(
                            os.path.join(root, f), REPO))
    assert hits == ["paddle_tpu/utils/compile_cache.py"]
