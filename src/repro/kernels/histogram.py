"""Cell-count histogram (GPUMD ``find_cell_counts``) — strided + false
sharing case study (§V Table I).

GPU story: every thread atomically increments ``cell_count[cell[i]]`` —
scattered single-word RMWs across warps: false sharing + strided.

TPU story: there are no global atomics; the idiomatic translation is a
one-hot dense accumulation.  Two variants:

  * naive  — every grid program read-modify-writes the WHOLE global
    histogram (output block = the full array, constant index_map).  The
    heat map shows every histogram tile touched by every program (hot)
    and, with per-program disjoint cells, sector temps far above word
    temps (false sharing economics: one RMW transfer per program).
  * opt    — each program accumulates a private partial histogram
    (per-program output row), reduced once by XLA afterwards: one
    transfer per program over its OWN row, no cross-program tiles.
    Residual inefficiency: each (1, n_bins) partial row is one sublane of
    an (8,128) tile -> 8 programs still share each partials tile (the
    kernel writes its sublane of that tile row; the profiler correctly
    flags residual false sharing on the stores).
  * opt2   — VMEM-scratch accumulator across the sequential grid, ONE
    final store at the last program: the pattern-free end state (TPU's
    sequential-grid analogue of the paper's privatization fix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.collector import KernelSpec, OperandSpec
from repro.core.tiles import tile_rows


def _hist_naive_kernel(cells_ref, hist_ref, *, n_bins: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    cells = cells_ref[...]  # (1, BLOCK) int32
    onehot = (
        cells[0][:, None] == jax.lax.broadcasted_iota(jnp.int32, (cells.shape[1], n_bins), 1)
    ).astype(jnp.float32)
    hist_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).astype(hist_ref.dtype)


def hist_naive(
    cells: jax.Array,  # (N,) int32 cell ids
    n_bins: int,
    block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    n = cells.shape[0]
    assert n % block == 0
    kernel = functools.partial(_hist_naive_kernel, n_bins=n_bins)
    out = pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),  # shared RMW
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        interpret=interpret,
    )(cells[None, :])
    return out[0]


def _hist_opt_kernel(cells_ref, part_ref, *, n_bins: int, rows: int):
    # part_ref: (rows, n_bins), the tile row holding this program's partial
    cells = cells_ref[...]
    onehot = (
        cells[0][:, None] == jax.lax.broadcasted_iota(jnp.int32, (cells.shape[1], n_bins), 1)
    ).astype(jnp.float32)
    part_ref[pl.ds(pl.program_id(0) % rows, 1), :] = jnp.sum(
        onehot, axis=0, keepdims=True
    ).astype(part_ref.dtype)


def hist_opt(
    cells: jax.Array, n_bins: int, block: int = 1024, interpret: bool = False
) -> jax.Array:
    n = cells.shape[0]
    assert n % block == 0
    n_blocks = n // block
    # a one-row block breaks Mosaic's (8, 128) block rule: each program
    # gets the tile row that holds its partial row and writes one sublane
    rows = tile_rows(n_blocks, np.dtype(np.float32).itemsize)
    kernel = functools.partial(_hist_opt_kernel, n_bins=n_bins, rows=rows)
    parts = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, n_bins), lambda i: (i // rows, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, n_bins), jnp.float32),
        interpret=interpret,
    )(cells[None, :])
    return jnp.sum(parts, axis=0)  # XLA tree-reduce


def _hist_opt2_kernel(cells_ref, hist_ref, acc_ref, *, n_bins: int, n_blocks: int):
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cells = cells_ref[...]
    onehot = (
        cells[0][:, None] == jax.lax.broadcasted_iota(jnp.int32, (cells.shape[1], n_bins), 1)
    ).astype(jnp.float32)
    acc_ref[...] += jnp.sum(onehot, axis=0, keepdims=True)

    @pl.when(pid == n_blocks - 1)
    def _store():
        hist_ref[...] = acc_ref[...].astype(hist_ref.dtype)


def hist_opt2(
    cells: jax.Array, n_bins: int, block: int = 1024, interpret: bool = False
) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    n = cells.shape[0]
    assert n % block == 0
    n_blocks = n // block
    kernel = functools.partial(_hist_opt2_kernel, n_bins=n_bins, n_blocks=n_blocks)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, n_bins), jnp.float32)],
        interpret=interpret,
    )(cells[None, :])
    return out[0]


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def hist_naive_spec(n: int, n_bins: int, block: int = 1024) -> KernelSpec:
    def scatter_walk(pid, cells=None, **_):
        (i,) = pid
        if cells is None:
            return []
        return [int(c) for c in cells[i * block : (i + 1) * block]]

    return KernelSpec(
        name="find_cell_counts",
        grid=(n // block,),
        operands=(
            OperandSpec("cells", (n,), np.int32, (block,), lambda i: (i,)),
            OperandSpec(
                "cell_count", (n_bins,), np.float32, (n_bins,), lambda i: (0,),
                kind="store",
            ),
        ),
        dynamic=(("cell_count", scatter_walk),),
    )


def hist_opt_spec(n: int, n_bins: int, block: int = 1024) -> KernelSpec:
    # hist_opt writes one sublane of the tile row that holds its partial;
    # the spec keeps the (1, n_bins) row, which touches the same tiles
    n_blocks = n // block
    return KernelSpec(
        name="find_cell_counts_opt",
        grid=(n_blocks,),
        operands=(
            OperandSpec("cells", (n,), np.int32, (block,), lambda i: (i,)),
            OperandSpec(
                "partials", (n_blocks, n_bins), np.float32, (1, n_bins),
                lambda i: (i, 0), kind="store",
            ),
        ),
    )


def hist_opt2_spec(n: int, n_bins: int, block: int = 1024) -> KernelSpec:
    from repro.core.collector import ScratchSpec

    n_blocks = n // block
    return KernelSpec(
        name="find_cell_counts_opt2",
        grid=(n_blocks,),
        operands=(
            OperandSpec("cells", (n,), np.int32, (block,), lambda i: (i,)),
            # single final store by the last program only — modeled as one
            # program's transfer via the index_map constant + store kind
            OperandSpec(
                "cell_count", (n_bins,), np.float32, (n_bins,),
                lambda i: (0,), kind="store", once=True,
            ),
        ),
        scratch=(
            # every program accumulates into the SAME scratch accumulator —
            # shared use (temps == n_programs), not abuse
            ScratchSpec("acc", (1, n_bins), np.float32, kind="accum"),
        ),
    )
