"""Extensions coverage: 2-axis EP, cache writes, constraint context, render."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- seq-buffer writes (the decode cache path) --------------------------------


def test_update_seq_buffer_onehot_matches_dus():
    from repro.models.attention import update_seq_buffer

    buf = jnp.zeros((2, 8, 3, 4))
    new = jnp.ones((2, 1, 3, 4)) * 7
    for idx in (0, 3, 7):
        got = update_seq_buffer(buf, new, jnp.asarray(idx))
        want = jax.lax.dynamic_update_slice(buf, new, (0, idx, 0, 0))
        np.testing.assert_array_equal(got, want)


def test_update_seq_buffer_full_replace():
    from repro.models.attention import update_seq_buffer

    buf = jnp.zeros((2, 4, 3))
    new = jnp.ones((2, 4, 3))
    got = update_seq_buffer(buf, new, jnp.asarray(0))
    np.testing.assert_array_equal(got, new)


def test_update_seq_buffer_partial_dus_fallback():
    from repro.models.attention import update_seq_buffer

    buf = jnp.zeros((1, 8, 2))
    new = jnp.ones((1, 3, 2))
    got = update_seq_buffer(buf, new, jnp.asarray(2))
    assert float(got[0, 1].sum()) == 0 and float(got[0, 2].sum()) == 2
    assert float(got[0, 4].sum()) == 2 and float(got[0, 5].sum()) == 0


# -- constraint context ---------------------------------------------------------


def test_constrain_logical_noop_without_rules():
    from repro.parallel.context import constrain_logical

    x = jnp.ones((4, 4))
    assert constrain_logical(x, ("act_batch", None)) is x


def test_constrain_logical_annotates_under_mesh():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.launch.mesh import make_mesh
from repro.parallel.context import use_rules, constrain_logical
from repro.parallel.sharding import make_rules
mesh = make_mesh((2, 4), ("data", "model"))
rules = make_rules()
with mesh, use_rules(rules, mesh):
    def f(x):
        return constrain_logical(x, ("act_batch", None, "vocab")) * 2
    txt = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 4, 64), jnp.float32)).as_text()
print(json.dumps({"annotated": ("sdy.sharding" in txt) or ("mhlo.sharding" in txt)
                 or ("Sharding" in txt)}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["annotated"]


def test_ep_two_axis_expert_sharding_parity():
    """Experts over ("model","data") — device-local experts — must match
    the dense oracle exactly."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.launch.mesh import make_mesh
from repro.models.moe import MoEConfig, moe_defs, moe_apply_ep, moe_ref
from repro.models.params import init_params
from repro.parallel.context import use_rules
from repro.parallel.sharding import make_rules
cfg = MoEConfig(d_model=16, d_ff=32, n_experts=8, top_k=2,
                capacity_factor=8.0, moe_impl="ep")
params = init_params(moe_defs(cfg), jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (4, 8, 16))
y_ref, _ = moe_ref(params, x, cfg)
mesh = make_mesh((2, 4), ("data", "model"))
rules = make_rules(expert_axes=("model", "data"))  # 8 experts over 8 chips
with mesh, use_rules(rules, mesh):
    y, aux = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg))(params, x)
print(json.dumps({"diff": float(jnp.abs(y - y_ref).max())}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["diff"] < 1e-4


# -- renderers -------------------------------------------------------------------


def _toy_heatmap():
    from repro.core.collector import analyze
    from repro.core.trace import GridSampler
    from repro.kernels.gemm import gemm_v00_spec

    return analyze(gemm_v00_spec(256, 256, 256), GridSampler((0,), window=32))


def test_render_csv_roundtrip_counts():
    from repro.core.render import render_csv

    hm = _toy_heatmap()
    text = render_csv(hm, compress=True)
    rows = [l for l in text.splitlines() if l and not l.startswith("region,")]
    # sum of repeats per region == touched sectors
    per_region = {}
    for row in rows:
        parts = row.split(",")
        per_region[parts[0]] = per_region.get(parts[0], 0) + int(parts[2])
    for rh in hm.regions:
        assert per_region[rh.region.name] == rh.touched_sectors


def test_render_html_and_ascii():
    from repro.core.render import render_ascii, render_html

    hm = _toy_heatmap()
    html = render_html(hm)
    assert "<table>" in html and hm.kernel in html
    ascii_ = render_ascii(hm, color=True, max_rows_per_region=4)
    assert "region A" in ascii_ and "sect" in ascii_


def test_save_heatmap(tmp_path):
    from repro.core.render import save

    hm = _toy_heatmap()
    save(hm, str(tmp_path / "hm.html"))
    save(hm, str(tmp_path / "hm.csv"))
    assert (tmp_path / "hm.html").stat().st_size > 100
    assert (tmp_path / "hm.csv").stat().st_size > 100


# -- sampler window ---------------------------------------------------------------


def test_grid_sampler_window_semantics():
    from repro.core.trace import GridSampler, sampled_grid

    s = GridSampler((0,), window=4)
    assert list(sampled_grid((16,), s)) == [(0,), (1,), (2,), (3,)]
    s1 = GridSampler((1,), window=4)
    assert list(sampled_grid((16,), s1)) == [(4,), (5,), (6,), (7,)]
    # 2-D: window applies to the last pinned coordinate
    s2 = GridSampler((0,), window=2)
    assert list(sampled_grid((4, 2), s2)) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    assert "x4" in GridSampler((0,), window=4).describe()


# -- api facade --------------------------------------------------------------------


def test_api_report_and_actions():
    from repro.core import api
    from repro.core.trace import GridSampler
    from repro.kernels.gemm import gemm_v00_spec

    spec = gemm_v00_spec(256, 256, 256)
    rep = api.report(spec, GridSampler((0,), window=32))
    assert "thermo report" in rep and "false-sharing" in rep
    acts = api.actions(spec, GridSampler((0,), window=32))
    assert acts and acts[0].est_transaction_saving > 0
