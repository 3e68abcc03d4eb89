"""Compile-only lowering for a described TPU v5e chip (no chip attached).

Every ``pallas_call`` compiles with ``interpret=False`` at the registry's
default shapes, which catches what interpret mode cannot: blocks that
break Mosaic's (8, 128) rule, primitives Mosaic cannot lower, kernels
over the fast-memory budget.  The TPU compiler's HLO also goes through
the cost parser.  The topology is described inside fixtures, never at
import: only the worker that runs this file loads the TPU library.
"""

import functools
import os

import numpy as np
import pytest

from repro.kernels.cases import CASES


@pytest.fixture(scope="module")
def no_persistent_cache():
    # compiles for a described chip cannot be read back without one
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, arrays):
    import jax

    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding) for a in arrays]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, one_chip):
    import jax

    args = _on(one_chip, case.inputs(np.random.default_rng(0)))
    fn = jax.jit(functools.partial(case.run, interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "name,result_tiling",
    # gramschm's result is a single row, which XLA tiles by rows of one
    [("gemm_v00", "T(8,128)"), ("hist_opt", "T(8,128)"), ("gramschm_naive", "T(1,128)")],
)
def test_row_kernels_read_tiles_in_place(name, result_tiling, one_chip):
    """The kernels that use one row or column of a tile fetch the tile
    itself: their arrays keep the tiled layout the profiler models, with
    no relayout copy around the kernel."""
    import jax

    (case,) = [c for c in CASES if c.name == name]
    args = _on(one_chip, case.inputs(np.random.default_rng(0)))
    text = jax.jit(functools.partial(case.run, interpret=False)).lower(*args).compile().as_text()
    (call,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert result_tiling in call.split("custom-call(")[0]
    assert " copy(" not in text and "copy-start(" not in text


def test_cost_parser_reads_tpu_compiled_model(one_chip):
    """The TPU backend writes the model's dots as convolutions; their
    FLOPs must come out as on the CPU backend, which keeps dots."""
    import jax
    import jax.numpy as jnp

    from repro.core import hlo_cost
    from repro.models import build_model
    from repro.core.model_kernels import MODELS

    entry = MODELS["transformer-tiny"]
    model = build_model(entry.config)
    params = model.abstract_params()
    toks = jax.ShapeDtypeStruct((entry.batch, entry.seq), jnp.int32)

    def fwd(p, t):
        return model.apply(p, t)[0]

    cpu = hlo_cost.analyze(jax.jit(fwd).lower(params, toks).compile().as_text())
    tpu_text = (
        jax.jit(fwd)
        .lower(jax.tree.map(lambda s: _on(one_chip, [s])[0], params), _on(one_chip, [toks])[0])
        .compile()
        .as_text()
    )
    assert "convolution(" in tpu_text
    tpu = hlo_cost.analyze(tpu_text)
    assert abs(tpu.flops - cpu.flops) / cpu.flops < 0.05


def test_gqa_decode_reads_cache_once_per_kv_head(one_chip):
    """One GQA layer's one-token step on a (16, 768, 8, 64) bfloat16 cache
    with 32 query heads: K and V are not repeated to the query heads, the
    cache is not copied to float32, and both products are MXU
    convolutions, not VPU multiply-reduces over the repeated cache."""
    import jax
    import jax.numpy as jnp

    from repro.models.attention import AttnConfig, abstract_cache, attn_apply, attn_defs
    from repro.models.params import abstract_params

    cfg = AttnConfig(d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64)
    params = abstract_params(attn_defs(cfg), jnp.bfloat16)
    cache = abstract_cache(16, 768, 8, 64)
    x = jax.ShapeDtypeStruct((16, 1, 2048), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((16, 1), jnp.int32)
    args = [jax.tree.map(lambda a: _on(one_chip, [a])[0], t) for t in (params, x, pos, cache)]
    fn = jax.jit(lambda p, x, pos, c: attn_apply(p, x, pos, cfg, c))
    text = fn.lower(*args).compile().as_text()
    lines = text.splitlines()
    assert len([ln for ln in lines if "convolution(" in ln and "/core/" in ln]) == 2
    assert "f32[16,768,8,4,64]" not in text and "f32[16,768,8,64]" not in text
    assert not [ln for ln in lines if "multiply_reduce" in ln and "f32[16,768,32]" in ln]
