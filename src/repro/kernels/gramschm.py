"""Gram-Schmidt kernels (PolybenchGPU GRAMSCHM) — the strided case study.

§VI-D: ``kernel3`` reads ``q[i*NJ + k]`` — a stride-NJ walk of the flat
address space.  On TPU the same walk shows up two ways:

  * Level-1 (block geometry): the naive kernel pulls a (NI, 1) column
    block of ``q`` — every (8,128) tile of the tile-column crosses the
    HBM boundary for 1/128th of its lanes (the transaction model shows
    NI/8 tiles per program where the transposed kernel needs NI/128).
  * Level-2 (flat address trace): the stride-NJ element stream touches
    the same word offsets across consecutive tiles while neighbours stay
    cold — the paper's strided heat signature, detected by
    ``detect_strided`` on the dynamic trace.

Fix (identical to the paper): transpose ``q`` so the strided axis is the
minor/lane dimension -> contiguous (1, NI) row loads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.collector import KernelSpec, OperandSpec
from repro.core.tiles import tile_rows
from repro.kernels.mxu import dot_precision


def _k3_naive_kernel(q_ref, a_ref, r_ref, *, col: int):
    # q: (NI, LANE_W) — the lane tile holding column k; a: (NI, BJ); r: (1, BJ)
    qcol = q_ref[:, col : col + 1].astype(jnp.float32)  # (NI, 1)
    r_ref[...] = jnp.sum(qcol * a_ref[...].astype(jnp.float32), axis=0, keepdims=True).astype(
        r_ref.dtype
    )


def _lane_width(nk: int) -> int:
    """Width of the lane tile a strided column read fetches from q."""
    lane_w = min(128, nk)
    assert nk % lane_w == 0
    return lane_w


def gramschm_k3_naive(
    q: jax.Array,  # (NI, NK)
    a: jax.Array,  # (NI, NJ)
    k: int,
    bj: int = 128,
    interpret: bool = False,
) -> jax.Array:
    ni, nk = q.shape
    _, nj = a.shape
    assert nj % bj == 0
    # the strided column read: a TPU DMA moves whole lane tiles, so the
    # block is the (NI, 128) tile column holding column k, sliced in-kernel
    lane_w = _lane_width(nk)
    return pl.pallas_call(
        functools.partial(_k3_naive_kernel, col=k % lane_w),
        grid=(nj // bj,),
        in_specs=[
            pl.BlockSpec((ni, lane_w), lambda j: (0, k // lane_w)),
            pl.BlockSpec((ni, bj), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, nj), jnp.float32),
        interpret=interpret,
    )(q, a)[0]


def _k3_opt_kernel(qt_ref, a_ref, r_ref, *, row: int):
    # qt: (ROWS, NI) tile row holding row k; a: (NI, BJ)
    qrow = qt_ref[row : row + 1, :].astype(jnp.float32)  # (1, NI) contiguous
    r_ref[...] = jnp.dot(
        qrow, a_ref[...].astype(jnp.float32), precision=dot_precision(jnp.float32)
    ).astype(r_ref.dtype)


def gramschm_k3_opt(
    qt: jax.Array,  # (NK, NI) — q transposed
    a: jax.Array,  # (NI, NJ)
    k: int,
    bj: int = 128,
    interpret: bool = False,
) -> jax.Array:
    nk, ni = qt.shape
    _, nj = a.shape
    # a one-row block breaks Mosaic's (8, 128) block rule: fetch the tile
    # row that holds row k, whose sublane k % rows is the contiguous row
    rows = tile_rows(nk, np.dtype(qt.dtype).itemsize)
    return pl.pallas_call(
        functools.partial(_k3_opt_kernel, row=k % rows),
        grid=(nj // bj,),
        in_specs=[
            pl.BlockSpec((rows, ni), lambda j: (k // rows, 0)),  # contiguous row read
            pl.BlockSpec((ni, bj), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, nj), jnp.float32),
        interpret=interpret,
    )(qt, a)[0]


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def k3_naive_spec(ni: int, nj: int, nk: int, k: int = 0, bj: int = 128) -> KernelSpec:
    """Flat-address dynamic trace of the stride-NJ q walk + block geometry."""

    def q_stride_walk(pid, **_):
        # program j reads q[i*NJ + k] for all i — the paper's exact stream
        return [i * nk + k for i in range(ni)]

    return KernelSpec(
        name="gramschmidt_kernel3",
        grid=(nj // bj,),
        operands=(
            OperandSpec("q", (ni * nk,), np.float32, (ni * nk,), lambda j: (0,)),
            OperandSpec("a", (ni, nj), np.float32, (ni, bj), lambda j: (0, j)),
            OperandSpec("r", (1, nj), np.float32, (1, bj), lambda j: (0, j), kind="store"),
        ),
        dynamic=(("q", q_stride_walk),),
    )


def k3_naive_block_spec(ni: int, nj: int, nk: int, k: int = 0, bj: int = 128) -> KernelSpec:
    """2-D block geometry of the naive kernel (transaction model).

    The kernel fetches the (NI, 128) lane tile that holds column k; the
    spec keeps the one-column block it uses.  A tile walk charges whole
    sublane rows, so both touch the same words of the same tiles, and
    the column view is what the linter's stride rule and the tuner's
    transpose read.
    """
    return KernelSpec(
        name="gramschmidt_kernel3_blocks",
        grid=(nj // bj,),
        operands=(
            OperandSpec("q", (ni, nk), np.float32, (ni, 1), lambda j: (0, k)),
            OperandSpec("a", (ni, nj), np.float32, (ni, bj), lambda j: (0, j)),
            OperandSpec("r", (1, nj), np.float32, (1, bj), lambda j: (0, j), kind="store"),
        ),
    )


def k3_opt_spec(ni: int, nj: int, nk: int, k: int = 0, bj: int = 128) -> KernelSpec:
    def q_contig_walk(pid, **_):
        # transposed: program j reads qT[k*NI + i] — contiguous
        return [k * ni + i for i in range(ni)]

    return KernelSpec(
        name="gramschmidt_kernel3_opt",
        grid=(nj // bj,),
        operands=(
            OperandSpec("qT", (nk * ni,), np.float32, (nk * ni,), lambda j: (0,)),
            OperandSpec("a", (ni, nj), np.float32, (ni, bj), lambda j: (0, j)),
            OperandSpec("r", (1, nj), np.float32, (1, bj), lambda j: (0, j), kind="store"),
        ),
        dynamic=(("qT", q_contig_walk),),
    )
