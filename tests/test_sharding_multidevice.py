"""Sharding rules + multi-device correctness (subprocess: 8 CPU devices).

The in-process tests cover the rules/spec machinery; the subprocess tests
prove REAL distributed execution: a sharded train step on an 8-device
mesh matching the single-device result, EP MoE all-to-all parity, and the
gpipe pipeline.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.sharding import Rules, fixup_specs, make_rules, specs_from_logical

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rules_lookup_and_dedup():
    rules = make_rules(data_axes=("pod", "data"), fsdp=True,
                       fsdp_axes=("pod", "data"))
    assert rules.get("batch") == ("pod", "data")
    assert rules.get("mlp") == ("model",)
    assert rules.get("layer") == ()
    # duplicate axis use across dims is deduped (first dim wins)
    spec = rules.spec(("embed", "mlp"))
    assert spec == P(("pod", "data"), "model")
    spec = rules.spec(("mlp", "mlp"))
    assert spec == P("model", None)


def test_extra_rules_take_precedence():
    rules = make_rules(extra=(("act_seq", ("model",)),))
    assert rules.get("act_seq") == ("model",)


def test_fixup_drops_nondivisible():
    mesh = make_mesh((1,), ("model",))
    # fake a 16-wide model axis via a Mesh-like shim
    class FakeMesh:
        shape = {"model": 16, "data": 16}

    spec = P(None, "model", None)
    shaped = jax.ShapeDtypeStruct((64, 8, 128), np.float32)  # 8 % 16 != 0
    fixed = fixup_specs(spec, shaped, FakeMesh())
    assert fixed == P(None, None, None)
    shaped_ok = jax.ShapeDtypeStruct((64, 32, 128), np.float32)
    assert fixup_specs(spec, shaped_ok, FakeMesh()) == P(None, "model", None)


_SUBPROCESS_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
"""


def _run_sub(body: str) -> dict:
    code = _SUBPROCESS_PRELUDE + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    res = _run_sub("""
    from repro.models import ModelConfig, build_model
    from repro.optim import adamw, constant
    from repro.runtime.train_loop import TrainConfig, build_train_step, init_state
    from repro.parallel.sharding import make_rules, specs_from_logical, fixup_specs

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                      dtype=jnp.float32)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
    labs = jnp.roll(toks, -1, 1)
    opt = adamw(constant(1e-2))
    tc = TrainConfig()

    # single-device reference
    st = init_state(params, opt, tc)
    step = build_train_step(lambda p,t,l: m.loss(p,t,l), opt, tc, donate=False)
    st1, met1 = step(st, toks, labs)

    # 8-device (2 data x 4 model) mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules()
    pspecs = fixup_specs(specs_from_logical(m.logical_specs(), rules), params, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.tree.map(jax.device_put, params, psh)
    st = init_state(params_sh, opt, tc)
    with mesh:
        st2, met2 = step(st, toks, labs)
    diff = max(float(jnp.abs(jax.device_get(a) - jax.device_get(b)).max())
               for a, b in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st2.params)))
    print(json.dumps({"loss1": float(met1["loss"]), "loss2": float(met2["loss"]),
                      "param_diff": diff}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 1e-4
    assert res["param_diff"] < 1e-3


def test_ep_moe_matches_reference_on_mesh():
    res = _run_sub("""
    from repro.models.moe import MoEConfig, moe_defs, moe_apply_ep, moe_ref
    from repro.models.params import init_params
    from repro.parallel.context import use_rules
    from repro.parallel.sharding import make_rules

    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=8, top_k=2,
                    capacity_factor=8.0, moe_impl="ep")
    params = init_params(moe_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 8, 16))
    y_ref, aux_ref = moe_ref(params, x, cfg)

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules()
    with mesh, use_rules(rules, mesh):
        y, aux = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg))(params, x)
    diff = float(jnp.abs(y - y_ref).max())
    print(json.dumps({"diff": diff, "aux": float(aux), "aux_ref": float(aux_ref)}))
    """)
    assert res["diff"] < 1e-4


def test_pipeline_parallel_matches_sequential():
    res = _run_sub("""
    from repro.parallel.pipeline import pipeline, bubble_fraction

    mesh = make_mesh((4,), ("stage",))
    n_stages, n_micro, dim = 4, 8, 16
    ws = jax.random.normal(jax.random.key(0), (n_stages, dim, dim)) * 0.3
    mbs = jax.random.normal(jax.random.key(1), (n_micro, 4, dim))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    # sequential reference
    ref = mbs
    for i in range(n_stages):
        ref = jax.vmap(lambda x: stage_fn(ws[i], x))(ref)

    fn = pipeline(stage_fn, mesh, axis="stage")
    with mesh:
        out = jax.jit(fn)(ws, mbs)
    print(json.dumps({"diff": float(jnp.abs(out - ref).max()),
                      "bubble": bubble_fraction(n_stages, n_micro)}))
    """)
    assert res["diff"] < 1e-5
    assert 0 < res["bubble"] < 0.5
