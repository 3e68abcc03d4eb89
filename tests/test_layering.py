"""The package graph points one way.

The served path (configs, models, ``Server``) loads no profiler or
training code; the kernel modules load only the profiler's leaf
modules (spec types, tile geometry, trace buffers); the profiler loads
no serving or training runtime; the fault primitives load nothing of
the package.  Each case imports its modules in a fresh interpreter and
reads ``sys.modules``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parent.parent / "src"

KERNEL_MODULES = (
    "flash", "gemm", "gmm", "ssd", "paged_attn", "ragged_flash", "spmv",
    "ttm", "histogram", "gramschm", "cases",
)

TRAINING = ("repro.optim", "repro.runtime.train_loop",
            "repro.parallel.compression", "repro.parallel.pipeline")


def _loaded(modules):
    """The ``repro`` modules a fresh interpreter holds after the imports."""
    code = (
        "import importlib, json, sys\n"
        f"for m in {list(modules)!r}:\n"
        "    importlib.import_module(m)\n"
        "print(json.dumps(sorted(k for k in sys.modules"
        " if k == 'repro' or k.startswith('repro.'))))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=60, check=True,
    )
    return set(json.loads(out.stdout.strip().splitlines()[-1]))


def _under(loaded, *prefixes):
    return sorted(
        m for m in loaded
        if any(m == p or m.startswith(p + ".") for p in prefixes)
    )


def _served(loaded):
    return _under(loaded, "repro.core", "repro.kernels", *TRAINING)


def _kernels(loaded):
    core = set(_under(loaded, "repro.core")) - {
        "repro.core", "repro.core.spec", "repro.core.tiles",
        "repro.core.trace",
    }
    return sorted(core) + _under(loaded, "repro.models", "repro.runtime")


def _profiler(loaded):
    return _under(loaded, "repro.runtime", "repro.optim")


def _fault(loaded):
    return sorted(loaded - {"repro", "repro.fault"})


CASES = {
    "served": (("repro.configs", "repro.models", "repro.runtime"), _served),
    "kernels": (
        tuple(f"repro.kernels.{m}" for m in KERNEL_MODULES), _kernels
    ),
    "profiler": (
        ("repro.core.tuner", "repro.core.model_profile",
         "repro.core.collector"),
        _profiler,
    ),
    "fault": (("repro.fault",), _fault),
}


@pytest.mark.parametrize("case", list(CASES))
def test_imports_point_one_way(case):
    modules, forbidden = CASES[case]
    loaded = _loaded(modules)
    assert set(modules) <= loaded
    assert forbidden(loaded) == [], (
        f"importing {', '.join(modules)} loads modules it must not"
    )
