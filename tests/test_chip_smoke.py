"""``chip_smoke.py`` fails loudly: off the chip, alone, and when the
profiler's worker pool only got through by recovering from faults."""

import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_clean_pool_passes(smoke, tmp_path):
    smoke.profile_serial_and_pooled(str(tmp_path / "sess"))


def test_recovered_worker_crash_fails(smoke, tmp_path):
    # the pool survives the planted crash and the maps still agree, so
    # only the recorded fault can tell
    with pytest.raises(AssertionError, match="worker-crash"):
        smoke.profile_serial_and_pooled(
            str(tmp_path / "sess"), "--inject-faults", "seed=7,timeouts=0"
        )


@pytest.mark.parametrize("where", ["cpu", "alone"])
def test_exits_nonzero_without_chip_or_repo(where, tmp_path):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_serve_phase_on_cpu(smoke, capsys):
    # the chip's serve phase, at smoke size: every request answered, one
    # compile of each step, the final step against a forward pass
    from repro.configs import get_config

    smoke.serve(get_config("granite-3-2b", smoke=True), seed=0, prompt_len=8)
    assert "decode ticks" in capsys.readouterr().out
