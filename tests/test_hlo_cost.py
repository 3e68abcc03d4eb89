"""Trip-count-aware HLO cost model: validated against XLA on loop-free
modules and against analytic counts on scan loops."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_cost


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def _xla_cost(co):
    ca = co.cost_analysis()
    return dict(ca[0] if isinstance(ca, (list, tuple)) else ca)


def test_loopfree_matches_xla():
    def g(a, b):
        return jnp.tanh(a @ b) @ b

    co = _compile(g, jax.ShapeDtypeStruct((256, 512), jnp.float32),
                  jax.ShapeDtypeStruct((512, 512), jnp.float32))
    want = _xla_cost(co)
    got = hlo_cost.analyze(co.as_text())
    assert abs(got.flops - want["flops"]) / want["flops"] < 0.01
    assert abs(got.bytes - want["bytes accessed"]) / want["bytes accessed"] < 0.05


def test_scan_multiplies_body_by_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, None, length=10)
        return y.sum()

    co = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                  jax.ShapeDtypeStruct((128, 128), jnp.float32))
    got = hlo_cost.analyze(co.as_text())
    expect = 2 * 128**3 * 10
    assert abs(got.flops - expect) / expect < 0.05
    # XLA's own analysis single-counts (documents why hlo_cost exists)
    assert _xla_cost(co)["flops"] < expect / 5


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None

            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None

        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y.sum()

    co = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((64, 64), jnp.float32))
    got = hlo_cost.analyze(co.as_text())
    expect = 2 * 64**3 * 12
    assert abs(got.flops - expect) / expect < 0.1


def test_dynamic_slice_counts_slice_not_buffer():
    # scanning over a big stacked operand must not charge the full stack
    # per iteration
    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    co = _compile(f, jax.ShapeDtypeStruct((20, 128, 128), jnp.float32),
                  jax.ShapeDtypeStruct((8, 128), jnp.float32))
    got = hlo_cost.analyze(co.as_text())
    stack_bytes = 20 * 128 * 128 * 4
    # total bytes must be ~ O(stack read once), NOT 20x the stack
    assert got.bytes < 6 * stack_bytes


def test_parse_tuple_shaped_while():
    text = """
HloModule m, entry_computation_layout={()->f32[]}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c1 = s32[] constant(1)
  %a = s32[] add(%g0, %c1)
  %g1 = f32[4] get-tuple-element(%p), index=1
  %e = f32[4] exponential(%g1)
  ROOT %t = (s32[], f32[4]) tuple(%a, %e)
}

%cond (p2: (s32[], f32[4])) -> pred[] {
  %p2 = (s32[], f32[4]) parameter(0)
  %g = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%g, %n), direction=LT
}

ENTRY %main () -> f32[] {
  %z = s32[] constant(0)
  %x = f32[4] constant({1,2,3,4})
  %t0 = (s32[], f32[4]) tuple(%z, %x)
  %w = (s32[], f32[4]) while(%t0), condition=%cond, body=%body
  %o = f32[4] get-tuple-element(%w), index=1
  ROOT %r = f32[] reduce-something(%o)
}
"""
    model = hlo_cost.HloCostModel(text)
    assert "body" in model.comps and "main" in model.comps
    assert model.trip_count("cond") == 7
    cost = model.entry_cost()
    # exponential: 4 elements x 7 trips (+ reduce etc.)
    assert cost.flops >= 28


def test_collectives_in_loops_scaled():
    text = """
HloModule m

%body (p: (s32[], f32[1024])) -> (s32[], f32[1024]) {
  %p = (s32[], f32[1024]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %c1 = s32[] constant(1)
  %a = s32[] add(%g0, %c1)
  %g1 = f32[1024] get-tuple-element(%p), index=1
  %ag = f32[1024] all-reduce(%g1), replica_groups=[4,2]<=[8], to_apply=%sum
  ROOT %t = (s32[], f32[1024]) tuple(%a, %ag)
}

%cond (p2: (s32[], f32[1024])) -> pred[] {
  %p2 = (s32[], f32[1024]) parameter(0)
  %g = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%g, %n), direction=LT
}

ENTRY %main (x: f32[1024]) -> f32[1024] {
  %z = s32[] constant(0)
  %x = f32[1024] parameter(0)
  %t0 = (s32[], f32[1024]) tuple(%z, %x)
  %w = (s32[], f32[1024]) while(%t0), condition=%cond, body=%body
  ROOT %o = f32[1024] get-tuple-element(%w), index=1
}
"""
    cost = hlo_cost.analyze(text, total_devices=8)
    # all-reduce: 2*(g-1)/g*B with g=2, B=4096 bytes -> 4096/iter x 5 iters
    assert cost.wire_bytes == pytest.approx(5 * 4096, rel=0.01)


# -- TPU-compiled HLO: dots written as convolutions, tiled layouts ----------

_TPU_CONV_MODULE = """HloModule m

ENTRY %main.1 (p0: f32[2,64,128], p1: f32[512,128,1]) -> f32[2,64,512] {
  %p0 = f32[2,64,128]{2,1,0:T(8,128)} parameter(0)
  %p1 = f32[512,128,1]{1,0,2:T(8,128)} parameter(1)
  ROOT %convolution.24 = f32[2,64,512]{2,1,0:T(8,128)S(1)} convolution(%p0, %p1), window={size=1}, dim_labels=0bf_oi0->0bf
}
"""

_TPU_PADDED_CONV_MODULE = """HloModule m

ENTRY %main.1 (p0: f32[1,4,8], p1: f32[4,8,16]) -> f32[1,4,16] {
  %p0 = f32[1,4,8]{2,1,0:T(8,128)} parameter(0)
  %p1 = f32[4,8,16]{2,1,0:T(8,128)} parameter(1)
  ROOT %convolution.1 = f32[1,4,16]{2,1,0:T(8,128)} convolution(%p0, %p1), window={size=4 pad=3_0}, dim_labels=b0f_0io->b0f
}
"""


@pytest.mark.parametrize(
    "text,flops",
    [
        # a (128 x 128) @ (128 x 512) dot: 2 * 128 * 128 * 512
        (_TPU_CONV_MODULE, 2 * 128 * 128 * 512),
        # causal window of 4 over 4 positions: 1+2+3+4 = 10 real taps of
        # 8 input features for each of 16 output features
        (_TPU_PADDED_CONV_MODULE, 2 * 16 * 8 * 10),
    ],
    ids=["dot-as-conv", "padded-window"],
)
def test_tpu_convolution_flops(text, flops):
    assert hlo_cost.analyze(text).flops == flops


def test_thermo_reads_tuple_shapes_with_tiled_layouts():
    from repro.core.hlo_thermo import analyze_hlo

    line = (
        "  %all-reduce.1 = (f32[8]{0:T(256)}, bf16[4,128]{1,0:T(8,128)(2,1)}) "
        "all-reduce(%a, %b), replica_groups={{0,1}}, to_apply=%add"
    )
    heat = analyze_hlo(line)
    assert heat.collective_count == 1
    # 8 f32 + 512 bf16 = 1056 bytes; a 2-way all-reduce moves 2 * 1/2 of it
    assert heat.collective_bytes == 1056.0
