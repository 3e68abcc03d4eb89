"""Every ``pallas_call`` in the package at the registry's default shapes.

Each case pairs a kernel with its pure-jnp oracle and draws seeded values
for it.  Shapes and dtypes come from the registry entry's spec
(``repro.kernels.build``), and the serving contexts and expert ids are
the registry's own, so the kernels see the geometry the profiler walks.
The compile-only lowering tests compile each case for a described TPU;
``chip_smoke.py`` runs each on the chip against its oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.collector import OperandSpec

from . import (
    _gmm_ids, build, flash, gemm, gmm, gramschm, histogram, paged_attn,
    ragged_flash, ref, spmv, ssd, ttm,
)

# spmv_ell has no registry entry (the registry profiles the CSR layout):
# it runs over the CSR rows, each padded to this many slots
ELL_WIDTH = 16


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel launch: inputs from a seed, the kernel, its oracle."""

    name: str
    inputs: Callable[[np.random.Generator], Tuple[np.ndarray, ...]]
    run: Callable[..., object]  # run(*inputs, interpret=...) -> array(s)
    oracle: Callable[..., object]  # oracle(*inputs) -> array(s)
    exact: bool = False  # integer-valued output: compared without tolerance


def _operands(ref_name: str) -> Dict[str, OperandSpec]:
    spec, _ = build(ref_name)
    return {o.name: o for o in spec.operands}


def _normal(rng: np.random.Generator, op: OperandSpec, shape=None) -> np.ndarray:
    return rng.standard_normal(shape or op.shape).astype(op.dtype)


def _normals(ref_name: str, *names: str):
    """Standard normals for the named operands of a registry spec."""
    ops = _operands(ref_name)
    return lambda rng: tuple(_normal(rng, ops[n]) for n in names)


def _ssd_inputs(rng):
    ops = _operands("ssd:chunk")
    bh, c, _, l = ops["A"].shape  # the spec's log-decays carry the unit axis
    return (
        _normal(rng, ops["X"]),
        -np.abs(_normal(rng, ops["A"], (bh, c, l))) * 0.1,  # log-decays: a <= 0
        _normal(rng, ops["B"]),
        _normal(rng, ops["C"]),
    )


def _spmv_inputs(rng):
    ops = _operands("spmv:csr")
    rows = ops["rowOffsets"].shape[0] - 1
    return tuple(_normal(rng, ops["x"], (rows, ELL_WIDTH)) for _ in range(2))


def _spmv_block_rows() -> int:
    return _operands("spmv:csr")["rowOffsets"].block_shape[0]


def _hist_inputs(rng):
    ops = _operands("histogram:naive")
    n_bins = ops["cell_count"].shape[0]
    return (rng.integers(0, n_bins, size=ops["cells"].shape).astype(ops["cells"].dtype),)


def _hist_bins() -> int:
    return _operands("histogram:naive")["cell_count"].shape[0]


def _gramschm():
    """(NI, NK) of q and the column k the registry's kernel reads."""
    spec, _ = build("gramschm:naive")
    ops = {o.name: o for o in spec.operands}
    ni = ops["a"].shape[0]
    walk = dict(spec.dynamic)["q"]  # q[i * NK + k] for i = 0..NI-1
    return (ni, ops["q"].shape[0] // ni), walk((0,))[0]


def _gramschm_inputs(rng):
    (ni, nk), _ = _gramschm()
    a = _operands("gramschm:naive")["a"]
    return _normal(rng, a, (ni, nk)), _normal(rng, a)


def _gmm_inputs(rng):
    ops = _operands("gmm:default")
    return _normal(rng, ops["X"]), _normal(rng, ops["W"]), _gmm_ids().astype(np.int32)


def _ragged_inputs(rng):
    ctx = ragged_flash.ragged_context()
    return _normals("ragged_flash:decode", "Q", "K", "V")(rng) + (ctx["starts"], ctx["ends"])


def _paged_inputs(rng):
    ops = _operands("paged_attn:decode-paged")
    ctx = paged_attn.paged_context()
    pages = (1,) + ops["Kcache"].shape  # one KV head
    return (
        _normal(rng, ops["Q"]), _normal(rng, ops["Kcache"], pages),
        _normal(rng, ops["Vcache"], pages), ctx["block_tables"], ctx["context_lens"],
    )


GS_K = _gramschm()[1]


CASES: Tuple[KernelCase, ...] = (
    KernelCase("gemm_v00", _normals("gemm:v00", "A", "B"), gemm.gemm_v00, ref.gemm_ref),
    KernelCase("gemm_v01", _normals("gemm:v01", "A", "B"), gemm.gemm_v01, ref.gemm_ref),
    KernelCase("gemm_v02", _normals("gemm:v02", "A", "B"), gemm.gemm_v02, ref.gemm_ref),
    KernelCase(
        "flash", _normals("flash:default", "Q", "K", "V"),
        flash.flash_attention, ref.flash_ref,
    ),
    KernelCase("ssd_chunk", _ssd_inputs, ssd.ssd_chunk, ref.ssd_chunk_ref),
    KernelCase(
        "spmv_ell", _spmv_inputs,
        lambda v, x, interpret: spmv.spmv_ell(v, x, br=_spmv_block_rows(), interpret=interpret),
        ref.spmv_ref,
    ),
    KernelCase(
        "ttm_scratch", _normals("ttm:scratch", "vals", "Urows"),
        lambda v, u, interpret: ttm.ttm(v, u, use_scratch=True, interpret=interpret),
        ref.ttm_ref,
    ),
    KernelCase(
        "ttm_fused", _normals("ttm:fused", "vals", "Urows"),
        lambda v, u, interpret: ttm.ttm(v, u, use_scratch=False, interpret=interpret),
        ref.ttm_ref,
    ),
    KernelCase(
        "gramschm_naive", _gramschm_inputs,
        lambda q, a, interpret: gramschm.gramschm_k3_naive(q, a, GS_K, interpret=interpret),
        lambda q, a: ref.gramschm_k3_ref(q, a, GS_K),
    ),
    KernelCase(
        "gramschm_opt", _gramschm_inputs,
        lambda q, a, interpret: gramschm.gramschm_k3_opt(q.T, a, GS_K, interpret=interpret),
        lambda q, a: ref.gramschm_k3_ref(q, a, GS_K),
    ),
    KernelCase(
        "hist_naive", _hist_inputs,
        lambda c, interpret: histogram.hist_naive(c, _hist_bins(), interpret=interpret),
        lambda c: ref.hist_ref(c, _hist_bins()), exact=True,
    ),
    KernelCase(
        "hist_opt", _hist_inputs,
        lambda c, interpret: histogram.hist_opt(c, _hist_bins(), interpret=interpret),
        lambda c: ref.hist_ref(c, _hist_bins()), exact=True,
    ),
    KernelCase(
        "hist_opt2", _hist_inputs,
        lambda c, interpret: histogram.hist_opt2(c, _hist_bins(), interpret=interpret),
        lambda c: ref.hist_ref(c, _hist_bins()), exact=True,
    ),
    KernelCase(
        "gmm", _gmm_inputs, gmm.gmm,
        lambda x, w, ids: gmm.gmm_ref(x, w, jnp.asarray(ids)),
    ),
    KernelCase(
        "ragged_decode", _ragged_inputs,
        ragged_flash.ragged_decode_attention,
        ragged_flash.ragged_decode_reference,
    ),
    KernelCase(
        "paged_decode", _paged_inputs,
        paged_attn.paged_decode_attention,
        paged_attn.paged_decode_reference,
    ),
)
