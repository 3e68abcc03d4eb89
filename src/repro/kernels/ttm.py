"""Sparse TTM (PASTA) + cuSZp-style kernels — the scratch-abuse studies.

PASTA §VI-B: ``spt_TTMRankRBNnzKernelSM`` parks per-thread partial sums
in shared memory (``Y_shr``) although nothing is shared -> the paper
replaces SMEM with registers for a 1.6x speedup.

TPU analogue: a VMEM *scratch* buffer holding program-local partials that
could live in VREGs (i.e. stay fused in the kernel body).  The abuse
variant stages the products into scratch, barrier-style, then reduces;
the optimized variant accumulates in registers (a single fused reduce).
Both produce identical outputs; the profiler flags only the former
(every scratch word has distinct-program temperature 1).

cuSZp §VI-C: SMEM used to broadcast per-warp scalars (exclusive prefix
sums).  TPU analogue: a scratch buffer holding one scalar per program —
``cuszp_like_spec`` — fix: keep the scalar in a VREG (fused cumsum).

Tensor layout (RB = rank-blocked, TPU-friendly): fibers padded to NF
nonzeros; U rows pre-gathered (XLA gather), kernel does the blocked
multiply-accumulate over R columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spec import KernelSpec, OperandSpec, ScratchSpec


def _ttm_scratch_kernel(vals_ref, urows_ref, y_ref, y_shr):
    # vals: (BF, NF); urows: (BF, NF, R); y: (BF, R); y_shr: (BF, R) scratch
    # ABUSE: stage per-fiber partials into scratch, then copy out.
    prod = vals_ref[...][..., None].astype(jnp.float32) * urows_ref[...].astype(
        jnp.float32
    )  # (BF, NF, R)
    y_shr[...] = jnp.sum(prod, axis=1)  # park in scratch (program-local!)
    y_ref[...] = y_shr[...].astype(y_ref.dtype)  # read back + store


def _ttm_fused_kernel(vals_ref, urows_ref, y_ref):
    prod = vals_ref[...][..., None].astype(jnp.float32) * urows_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = jnp.sum(prod, axis=1).astype(y_ref.dtype)  # VREG accumulate


def ttm(
    vals: jax.Array,  # (F, NF)
    urows: jax.Array,  # (F, NF, R) pre-gathered U rows
    bf: int = 8,
    use_scratch: bool = False,
    interpret: bool = False,
) -> jax.Array:
    f, nf = vals.shape
    r = urows.shape[-1]
    assert f % bf == 0
    common = dict(
        grid=(f // bf,),
        in_specs=[
            pl.BlockSpec((bf, nf), lambda i: (i, 0)),
            pl.BlockSpec((bf, nf, r), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bf, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f, r), jnp.float32),
        interpret=interpret,
    )
    if use_scratch:
        return pl.pallas_call(
            _ttm_scratch_kernel,
            scratch_shapes=[pltpu.VMEM((bf, r), jnp.float32)],
            **common,
        )(vals, urows)
    return pl.pallas_call(_ttm_fused_kernel, **common)(vals, urows)


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def ttm_scratch_spec(
    f: int, nf: int, r: int, bf: int = 8, dtype=np.float32
) -> KernelSpec:
    """Abuse variant: Y_shr holds per-PROGRAM partials — each program owns
    a disjoint row block of the (shared-lifetime) scratch, exactly the
    paper's per-thread Y_shr slices.  Word temps stay 1 -> abuse."""

    n_programs = f // bf

    def scratch_access(pid):
        (i,) = pid
        return [(i * bf, (i + 1) * bf, 0, r)]  # program-owned disjoint rows

    return KernelSpec(
        name="spt_TTMRankRBNnzKernelSM",
        grid=(n_programs,),
        operands=(
            OperandSpec("vals", (f, nf), dtype, (bf, nf), lambda i: (i, 0)),
            OperandSpec("Urows", (f, nf, r), dtype, (bf, nf, r), lambda i: (i, 0, 0)),
            OperandSpec("Y", (f, r), np.float32, (bf, r), lambda i: (i, 0), kind="store"),
        ),
        scratch=(
            ScratchSpec("Y_shr", (f, r), np.float32, access_model=scratch_access),
        ),
    )


def ttm_fused_spec(f: int, nf: int, r: int, bf: int = 8, dtype=np.float32) -> KernelSpec:
    return KernelSpec(
        name="spt_TTMRankRBNnzKernel_reg",
        grid=(f // bf,),
        operands=(
            OperandSpec("vals", (f, nf), dtype, (bf, nf), lambda i: (i, 0)),
            OperandSpec("Urows", (f, nf, r), dtype, (bf, nf, r), lambda i: (i, 0, 0)),
            OperandSpec("Y", (f, r), np.float32, (bf, r), lambda i: (i, 0), kind="store"),
        ),
    )


def cuszp_like_spec(n_blocks: int, dtype=np.float32) -> KernelSpec:
    """cuSZp-style: scratch holds ONE scalar per program (exclusive sum
    broadcast) — warp-local data in shared space."""
    return KernelSpec(
        name="cuszp_compress_like",
        grid=(n_blocks,),
        operands=(
            OperandSpec("data", (n_blocks * 1024,), dtype, (1024,), lambda i: (i,)),
            OperandSpec(
                "cmp_bytes", (n_blocks * 1024,), np.int8, (1024,),
                lambda i: (i,), kind="store",
            ),
        ),
        scratch=(
            # one scalar slot per program (warp-local broadcast values)
            ScratchSpec(
                "exel_sum", (n_blocks, 128), np.float32,
                access_model=lambda pid: [(pid[0], pid[0] + 1, 0, 1)],
            ),
            ScratchSpec(
                "base_idx", (n_blocks, 128), np.int32,
                access_model=lambda pid: [(pid[0], pid[0] + 1, 0, 1)],
            ),
        ),
    )
