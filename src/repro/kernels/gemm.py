"""GEMM kernels — the paper's §VI-A case study, TPU-native.

Three variants mirror the paper's optimization ladder:

  v00  row-per-program: each grid program computes ONE sublane row (1,128)
       of C.  Eight programs therefore own the eight sublanes of every C
       tile — the paper's *false sharing* (8 tile transfers where 1 would
       do) — and every program re-fetches all of B — *hot spot*.
  v01  tile-per-program: block (8,128) — one program owns whole C tiles
       (the paper's coalescing fix: swap thread indices -> re-tile).
  v02  blocked (bm,bn,bk) matmul with a VMEM accumulator and the K axis
       innermost in the grid — the classic MXU-aligned tiling; kills the
       residual B hot spot of v01 by reusing each B tile across the bm
       axis positions and accumulating in scratch.

Each variant has a real ``pl.pallas_call`` implementation (TPU target,
validated with interpret=True) and a ``kernel_spec`` builder that hands
the SAME grid/BlockSpec geometry to the Level-1 profiler — the
instrumentation path of the CUTHERMO reproduction.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.collector import KernelSpec, OperandSpec, ScratchSpec
from repro.core.tiles import tile_rows
from repro.kernels.mxu import dot_precision


# ---------------------------------------------------------------------------
# v00: one sublane row of C per program (false sharing on C, hot B)
# ---------------------------------------------------------------------------


def _gemm_v00_kernel(a_ref, b_ref, c_ref, *, rows: int):
    # a_ref: (rows, K) tile row holding row i, b_ref: (K, N), c_ref: (rows, N);
    # program i reads and writes only sublane i % rows of its tiles
    r = pl.program_id(0) % rows
    a = a_ref[pl.ds(r, 1), :]
    c_ref[pl.ds(r, 1), :] = jnp.dot(
        a, b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a.dtype),
    ).astype(c_ref.dtype)


def gemm_v00(a: jax.Array, b: jax.Array, interpret: bool = False) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    # A one-row block breaks Mosaic's (8, 128) block rule, and a DMA moves
    # whole tiles anyway: each program gets the tile row that holds its
    # row and works on one sublane of it, so `rows` programs share each
    # A and C tile.
    rows = tile_rows(m, np.dtype(a.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_gemm_v00_kernel, rows=rows),
        grid=(m,),
        in_specs=[
            pl.BlockSpec((rows, k), lambda i: (i // rows, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, n), lambda i: (i // rows, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, b)


def gemm_v00_spec(m: int, n: int, k: int, dtype=np.float32) -> KernelSpec:
    # gemm_v00 fetches the tile row that holds row i; the spec keeps the
    # (1, K) row the program uses.  A tile walk charges whole tiles, so
    # both touch the same tiles, and the row view is where the tuner's
    # re-tile reads its sublane dim from.
    return KernelSpec(
        name="gemm_v00",
        grid=(m,),
        operands=(
            OperandSpec("A", (m, k), dtype, (1, k), lambda i: (i, 0)),
            OperandSpec("B", (k, n), dtype, (k, n), lambda i: (0, 0)),
            OperandSpec("C", (m, n), dtype, (1, n), lambda i: (i, 0), kind="store"),
        ),
    )


# ---------------------------------------------------------------------------
# v01: one (8,128)-multiple tile of C per program (coalesced)
# ---------------------------------------------------------------------------


def _gemm_v01_kernel(a_ref, b_ref, c_ref):
    c_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a_ref.dtype),
    ).astype(c_ref.dtype)


def gemm_v01(
    a: jax.Array, b: jax.Array, bm: int = 8, interpret: bool = False
) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    assert m % bm == 0
    return pl.pallas_call(
        _gemm_v01_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, b)


def gemm_v01_spec(m: int, n: int, k: int, bm: int = 8, dtype=np.float32) -> KernelSpec:
    return KernelSpec(
        name="gemm_v01",
        grid=(m // bm,),
        operands=(
            OperandSpec("A", (m, k), dtype, (bm, k), lambda i: (i, 0)),
            OperandSpec("B", (k, n), dtype, (k, n), lambda i: (0, 0)),
            OperandSpec("C", (m, n), dtype, (bm, n), lambda i: (i, 0), kind="store"),
        ),
    )


# ---------------------------------------------------------------------------
# v02: blocked (bm, bn, bk) with VMEM accumulator, K innermost
# ---------------------------------------------------------------------------


def _gemm_v02_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=dot_precision(a_ref.dtype),
    )

    @pl.when(ki == n_k - 1)
    def _store():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def gemm_v02(
    a: jax.Array,
    b: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    kernel = functools.partial(_gemm_v02_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=_acc_scratch(bm, bn),
        interpret=interpret,
    )(a, b)


def _acc_scratch(bm: int, bn: int):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((bm, bn), jnp.float32)]


def gemm_v02_spec(
    m: int, n: int, k: int, bm: int = 128, bn: int = 128, bk: int = 128,
    dtype=np.float32,
) -> KernelSpec:
    return KernelSpec(
        name="gemm_v02",
        grid=(m // bm, n // bn, k // bk),
        operands=(
            OperandSpec("A", (m, k), dtype, (bm, bk), lambda i, j, ki: (i, ki)),
            OperandSpec("B", (k, n), dtype, (bk, bn), lambda i, j, ki: (ki, j)),
            OperandSpec(
                "C", (m, n), dtype, (bm, bn), lambda i, j, ki: (i, j), kind="store"
            ),
        ),
        scratch=(
            ScratchSpec(
                "acc",
                (bm, bn),
                np.float32,
                # every program in the same (i, j) column reuses the whole
                # accumulator: proper shared use of scratch (not abuse)
                access_model=None,
            ),
        ),
    )
