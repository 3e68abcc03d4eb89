"""SpMV (CSR) — the paper's §VI-E misalignment case study, TPU-native.

GPU story: reading ``rowOffsets[r+1]`` shifts a warp's 128 B load by 4
bytes -> 5 sectors instead of 4 (25 % extra transactions).  TPU story:
a block of the offsets array read at element offset +1 straddles one
extra (1,128) sublane row per tile — 9 words across 2 tiles instead of
8 in 1 — the identical economics, captured by ``OperandSpec.origin``.

The paper's fix (zigzag-duplicated offsets enabling vectorized loads)
becomes: store offsets as aligned (row_start, row_end) PAIRS so each
block reads a single aligned region — implemented in ``spmv_zigzag``.

The compute kernel uses a TPU-idiomatic ELL-style layout: per-row-block
pre-gathered x values (gathers are XLA's job on TPU; the kernel does the
MXU/VPU-friendly multiply-reduce).  ``x``'s data-dependent gather
footprint is profiled via Level-2 dynamic tracing (hot-random pattern).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.spec import KernelSpec, OperandSpec


def _spmv_kernel(vals_ref, xg_ref, y_ref):
    # vals, xg: (BR, K); y: (BR, 1)
    y_ref[...] = jnp.sum(
        vals_ref[...].astype(jnp.float32) * xg_ref[...].astype(jnp.float32),
        axis=1,
        keepdims=True,
    ).astype(y_ref.dtype)


def spmv_ell(
    vals: jax.Array,  # (R, K) padded per-row values
    xg: jax.Array,  # (R, K) pre-gathered x[colIndices]
    br: int = 8,
    interpret: bool = False,
) -> jax.Array:
    r, k = vals.shape
    assert r % br == 0
    out = pl.pallas_call(
        _spmv_kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        interpret=interpret,
    )(vals, xg)
    return out[:, 0]


def csr_to_ell(
    row_offsets: np.ndarray, col_indices: np.ndarray, values: np.ndarray, n_rows: int
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR -> padded ELL (indices, values); pad uses index 0 / value 0."""
    counts = np.diff(row_offsets[: n_rows + 1])
    k = max(1, int(counts.max()))
    idx = np.zeros((n_rows, k), np.int32)
    val = np.zeros((n_rows, k), values.dtype)
    for r in range(n_rows):
        s, e = row_offsets[r], row_offsets[r + 1]
        idx[r, : e - s] = col_indices[s:e]
        val[r, : e - s] = values[s:e]
    return idx, val


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def spmv_csr_spec(
    n_rows: int, n_cols: int, block_rows: int = 1024, dtype=np.float32
) -> KernelSpec:
    """The FAITHFUL INEFFICIENT variant: each program reads a block of
    rowOffsets TWICE — once aligned (r) and once shifted by one element
    (r+1), the paper's misaligned load — plus a data-dependent x gather."""
    n_blocks = (n_rows + block_rows - 1) // block_rows

    def x_gather(pid, col_indices=None, **_):
        (i,) = pid
        if col_indices is None:
            return []
        rows = col_indices[i * block_rows : (i + 1) * block_rows]
        return [int(c) for c in rows.reshape(-1)]

    return KernelSpec(
        name="spmv_csr",
        grid=(n_blocks,),
        operands=(
            OperandSpec(
                "rowOffsets", (n_rows + 1,), np.int32, (block_rows,),
                lambda i: (i,),
            ),
            OperandSpec(
                "rowOffsets_shift1", (n_rows + 1,), np.int32, (block_rows,),
                lambda i: (i,), origin=(0, 1),  # the +1 misaligned view
            ),
            OperandSpec("x", (n_cols,), dtype, (n_cols,), lambda i: (0,)),
        ),
        dynamic=(("x", x_gather),),
    )


def spmv_zigzag_spec(
    n_rows: int, n_cols: int, block_rows: int = 1024, dtype=np.float32
) -> KernelSpec:
    """The OPTIMIZED variant: zigzag-duplicated (start,end) pairs — one
    aligned load per block, no shifted view (paper's ldg.s32.v2 fix)."""
    n_blocks = (n_rows + block_rows - 1) // block_rows

    def x_gather(pid, col_indices=None, **_):
        (i,) = pid
        if col_indices is None:
            return []
        rows = col_indices[i * block_rows : (i + 1) * block_rows]
        return [int(c) for c in rows.reshape(-1)]

    return KernelSpec(
        name="spmv_zigzag",
        grid=(n_blocks,),
        operands=(
            # (R, 2) pairs flattened: 2*block_rows elements, tile-aligned
            OperandSpec(
                "rowPairs", (2 * n_rows,), np.int32, (2 * block_rows,),
                lambda i: (i,),
            ),
            OperandSpec("x", (n_cols,), dtype, (n_cols,), lambda i: (0,)),
        ),
        dynamic=(("x", x_gather),),
    )
