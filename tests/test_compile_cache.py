"""Where the persistent compile cache goes, and what may choose it."""

import os
import pathlib
import subprocess
import sys

import pytest

from repro import compile_cache
from repro.compile_cache import CHECKOUT_CACHE, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_checkout_cache_is_a_fixed_path_in_the_checkout():
    assert CHECKOUT_CACHE == pathlib.Path(REPO) / ".jax_cache"


def _probe(env_dir, min_time=None):
    """enable_compile_cache in a fresh interpreter; JAX's settings after it."""
    code = (
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    if min_time is not None:
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = min_time
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=120, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("outside", [None, "/placed/from/outside"],
                         ids=["unset", "set"])
def test_enable_compile_cache(outside):
    returned, configured, min_time = _probe(outside)
    if outside is None:
        # nothing placed it: the fixed checkout path, never a temp name
        assert returned == configured == str(CHECKOUT_CACHE)
    else:
        # placed from outside: JAX's own reading of the variable stands
        assert returned == configured == outside
    # every compile is cached, not only those over JAX's 1 s default
    assert float(min_time) == 0.0


def test_outside_min_compile_time_stands():
    _, _, min_time = _probe("/placed/from/outside", min_time="2.5")
    assert float(min_time) == 2.5


def test_enable_before_jax_import_sets_the_variables(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    monkeypatch.delitem(sys.modules, "jax", raising=False)  # not imported yet
    assert enable_compile_cache() == str(CHECKOUT_CACHE)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(CHECKOUT_CACHE)
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"


def test_outside_a_checkout_the_variable_is_required(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setattr(compile_cache, "CHECKOUT", tmp_path / "site-packages")
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        enable_compile_cache()
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
