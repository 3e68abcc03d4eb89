"""Grouped matmul (megablox-lite) — the MoE expert-FFN hot path.

Dropless MoE sorts tokens by expert and multiplies each contiguous group
by its expert's weights.  The TPU trick (megablox): pad each group to a
multiple of the m-tile, precompute *which expert owns each m-tile*, and
pass that map as a PREFETCHED SCALAR so the weight BlockSpec's index_map
can select the expert weight block per tile — no gather, no dynamic
shapes, full MXU utilization.

``group_ids`` (n_tiles,) comes from ``plan_groups``; the XLA fallback is
``jax.lax.ragged_dot`` (see repro.models.moe).
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


def plan_groups(group_sizes: np.ndarray, bm: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad groups to bm multiples.

    Returns (row_map, tile_expert_ids, padded_rows): ``row_map[padded_i]``
    is the source row (or -1 for padding); ``tile_expert_ids[t]`` is the
    expert owning m-tile t.
    """
    row_map = []
    tile_ids = []
    src = 0
    for e, g in enumerate(group_sizes):
        g = int(g)
        rows = list(range(src, src + g))
        pad = (-g) % bm
        rows += [-1] * pad
        row_map += rows
        tile_ids += [e] * ((g + pad) // bm)
        src += g
    return np.asarray(row_map, np.int32), np.asarray(tile_ids, np.int32), len(row_map)


def _gmm_kernel(ids_ref, x_ref, w_ref, o_ref):
    # ids_ref: prefetched scalars (unused in body; consumed by index_map)
    o_ref[...] = jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32,
        precision=dot_precision(x_ref.dtype),
    ).astype(o_ref.dtype)


def gmm(
    x: jax.Array,  # (M_padded, K) — rows grouped by expert, bm-padded
    w: jax.Array,  # (E, K, N)
    tile_expert_ids: jax.Array,  # (M_padded // bm,) int32
    bm: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    e, _, n = w.shape
    assert m % bm == 0
    n_tiles = m // bm
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, ids: (i, 0)),
            pl.BlockSpec((1, k, n), lambda i, ids: (ids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i, ids: (i, 0)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(tile_expert_ids, x, w)


def gmm_ref(x: jax.Array, w: jax.Array, tile_expert_ids: jax.Array, bm: int = 128):
    """Pure-jnp oracle: per-tile dense matmul with the mapped expert."""
    m, k = x.shape
    n = w.shape[-1]
    n_tiles = m // bm
    xt = x.reshape(n_tiles, bm, k)
    wt = w[tile_expert_ids]  # (n_tiles, K, N)
    return jnp.einsum("tbk,tkn->tbn", xt, wt).reshape(m, n).astype(x.dtype)


def expert_ids(n_tiles: int, n_experts: int) -> np.ndarray:
    """Seeded sorted expert id per row tile: the profiler's uniform routing."""
    rng = np.random.default_rng(0)
    return np.sort(rng.integers(0, n_experts, size=n_tiles)).astype(np.int64)


def gmm_spec(
    m: int, k: int, n: int, e: int, tile_expert_ids: np.ndarray, bm: int = 128,
    dtype=np.float32,
) -> KernelSpec:
    ids = np.asarray(tile_expert_ids)
    return KernelSpec(
        name="gmm",
        grid=(m // bm,),
        operands=(
            OperandSpec("X", (m, k), dtype, (bm, k), lambda i: (i, 0)),
            OperandSpec(
                "W", (e, k, n), dtype, (1, k, n), lambda i: (int(ids[i]), 0, 0)
            ),
            OperandSpec("O", (m, n), dtype, (bm, n), lambda i: (i, 0), kind="store"),
        ),
    )
