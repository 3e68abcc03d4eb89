"""SSD intra-chunk Pallas kernel (Mamba2 hot spot).

Computes, per (batch*head, chunk) grid cell, the *diagonal-block* term of
the SSD dual form:

    Y_diag[c] = ((C_c B_c^T) . L_c) X_c        L_c = exp(segsum(a_c))

plus the per-chunk end state  S_c = B_c^T (decay . X_c) — the two
matmul-dominated pieces that dominate Mamba2 runtime.  The O(chunks)
inter-chunk recurrence stays in XLA (it is tiny and sequential).

Layouts are chosen for the MXU: chunk length L is the sublane axis and
head_dim P / state N the lane axis; L=P=N multiples of 8/128 hit native
tiles.  (On the assigned mamba2-2.7b: P=64, N=128, L=chunk=256.)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spec import KernelSpec, OperandSpec
from repro.kernels.mxu import dot_precision


def _ssd_chunk_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, s_ref):
    # blocks: x (1, 1, L, P), a (1, 1, 1, L), b (1, 1, L, N), c (1, 1, L, N)
    # outputs: y (1, 1, L, P), s (1, 1, P, N)  — per-chunk end state
    x = x_ref[0, 0]  # (L, P)
    a = a_ref[0, 0].astype(jnp.float32)  # (1, L) lane row
    bm = b_ref[0, 0]  # (L, N)
    cm = c_ref[0, 0]  # (L, N)
    l = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    # inclusive prefix sums as a column, cum[i] = sum_{k<=i} a[k]: Mosaic
    # has no cumsum, and a masked row reduction stays exact in f32
    cum = jnp.sum(jnp.where(jj <= ii, a, 0.0), axis=1, keepdims=True)  # (L, 1)
    rows = jnp.broadcast_to(cum, (l, l))
    # decay matrix L[i,j] = exp(cum_i - cum_j) for j <= i
    seg = rows - rows.T
    dec = jnp.where(jj <= ii, jnp.exp(seg), 0.0)  # (L, L)
    # scores = (C B^T) . dec
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=dot_precision(cm.dtype),
    ) * dec  # (L, L)
    y = jax.lax.dot_general(
        scores.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=dot_precision(x.dtype),
    )  # (L, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # chunk end state: sum_t exp(cum_L - cum_t) * x_t (outer) b_t -> (P, N)
    w = jnp.exp(jnp.sum(a, axis=1, keepdims=True) - cum)  # (L, 1)
    xw = (x.astype(jnp.float32) * w).astype(x.dtype)
    s = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=dot_precision(xw.dtype),
    )  # (P, N)
    s_ref[0, 0] = s.astype(s_ref.dtype)


def ssd_chunk(
    x: jax.Array,  # (BH, C, L, P) dt-scaled inputs
    a: jax.Array,  # (BH, C, L) log-decays
    bmat: jax.Array,  # (BH, C, L, N)
    cmat: jax.Array,  # (BH, C, L, N)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y_diag (BH,C,L,P), chunk_states (BH,C,P,N))."""
    bh, c, l, p = x.shape
    n = bmat.shape[-1]
    grid = (bh, c)
    # the log-decays go in as (BH, C, 1, L): one chunk's lane row then
    # equals the array's last two dims, as Mosaic requires
    y, s = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, c, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, c, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, a[:, :, None, :], bmat, cmat)
    return y, s


def ssd_chunk_spec(
    bh: int, c: int, l: int, p: int, n: int, dtype=np.float32
) -> KernelSpec:
    return KernelSpec(
        name="ssd_chunk",
        grid=(bh, c),
        operands=(
            OperandSpec("X", (bh, c, l, p), dtype, (1, 1, l, p),
                        lambda i, j: (i, j, 0, 0)),
            OperandSpec("A", (bh, c, 1, l), dtype, (1, 1, 1, l),
                        lambda i, j: (i, j, 0, 0)),
            OperandSpec("B", (bh, c, l, n), dtype, (1, 1, l, n),
                        lambda i, j: (i, j, 0, 0)),
            OperandSpec("C", (bh, c, l, n), dtype, (1, 1, l, n),
                        lambda i, j: (i, j, 0, 0)),
            OperandSpec("Y", (bh, c, l, p), np.float32, (1, 1, l, p),
                        lambda i, j: (i, j, 0, 0), kind="store"),
            OperandSpec("S", (bh, c, p, n), np.float32, (1, 1, p, n),
                        lambda i, j: (i, j, 0, 0), kind="store"),
        ),
    )
