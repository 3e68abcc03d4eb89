"""Where JAX keeps its persistent compilation cache.

A cold run on a chip host pays for every compile again; the cache lets
the next run read them back.  Entries are only found again in the same
directory, so it never moves: ``JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself), otherwise ``<checkout>/.jax_cache``.  The
second needs a source checkout (``PYTHONPATH=src`` or an editable
install); anywhere else the variable has to be set.
"""

from __future__ import annotations

import os
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
CHECKOUT_CACHE = CHECKOUT / ".jax_cache"


def _set_default(env: str, option: str, value) -> None:
    """``option`` to ``value`` unless ``env`` was set from outside."""
    if os.environ.get(env):
        return
    jax = sys.modules.get("jax")
    if jax is None:
        os.environ[env] = str(value)  # read by JAX when it is imported
    else:
        jax.config.update(option, value)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its fixed place; returns it.

    Leaves an outside ``JAX_COMPILATION_CACHE_DIR`` alone.  Caches every
    compile, however quick (JAX's default keeps only those over 1 s),
    unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
    Does not import JAX.  Raises outside a source checkout when the
    variable is unset, rather than write a cache beside the installed
    package.
    """
    _set_default(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
        "jax_persistent_cache_min_compile_time_secs",
        0,
    )
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    if not (CHECKOUT / "pyproject.toml").is_file():
        raise RuntimeError(
            f"{__name__} is not in a source checkout ({CHECKOUT}); "
            "set JAX_COMPILATION_CACHE_DIR to place the compile cache"
        )
    _set_default("JAX_COMPILATION_CACHE_DIR", "jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
