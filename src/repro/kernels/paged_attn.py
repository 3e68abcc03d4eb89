"""Paged KV-cache attention — the vLLM-Pallas-style serving kernel.

Serving engines store the KV cache as fixed-size *pages* shared across a
batch: ``k_pages``/``v_pages`` of shape ``(kv_heads, num_pages,
page_size, head_dim)``, a per-sequence ``block_tables`` mapping logical
page slots to physical pages, and ``context_lens`` bounding each row's
live prefix (vLLM's ``PallasAttentionBackend`` layout).  The kernel
prefetches the table and lengths as scalars and resolves the physical
page inside the BlockSpec index map — the gather IS the index map.

Profiler story: the *baseline* rung models the pre-paging allocation —
a contiguous max-length cache swept densely per sequence (static,
affine); the *optimized* rung models the paged gather as a Level-2
dynamic access over the seeded ``block_tables``/``context_lens``
context, touching only the pages a row's live prefix occupies.  The
transfer delta is the paging saving the tuner can accept.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spec import KernelSpec, OperandSpec, ScratchSpec
from repro.kernels.mxu import dot_precision

NEG_INF = -1e30

# registry default shapes (CI-sized): 4 sequences of up to 8 pages x 64
# tokens over a 64-page physical pool, MQA (one KV head)
DEF_B, DEF_H, DEF_D = 4, 8, 128
DEF_PAGE, DEF_PAGES, DEF_SLOTS = 64, 64, 8


def _paged_decode_kernel(
    bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, page: int, n_slots: int, scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = cl_ref[b]

    @pl.when(j * page < ctx)
    def _run():
        q = q_ref[0]  # (H, D)
        k = k_ref[0, 0]  # (page, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=dot_precision(q.dtype),
        ) * scale  # (H, page)
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        )
        s = jnp.where(pos < ctx, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=dot_precision(v_ref.dtype),
        )
        m_scr[...] = m_new

    @pl.when(j == n_slots - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, H, D)
    k_pages: jax.Array,  # (1, P, page, D) — MQA: one KV head
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, n_slots) int32 physical page ids
    context_lens: jax.Array,  # (B,) int32
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    _, _, page, _ = k_pages.shape
    n_slots = block_tables.shape[1]
    kernel = functools.partial(
        _paged_decode_kernel,
        page=page, n_slots=n_slots, scale=1.0 / float(np.sqrt(d)),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_slots),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, j, bt, cl: (bi, 0, 0)),
            # the paged gather: the physical page comes from the table
            pl.BlockSpec(
                (1, 1, page, d), lambda bi, j, bt, cl: (0, bt[bi, j], 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, page, d), lambda bi, j, bt, cl: (0, bt[bi, j], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, j, bt, cl: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
        q, k_pages, v_pages,
    )


def paged_decode_reference(q, k_pages, v_pages, block_tables, context_lens):
    """Pure-jnp oracle: gather each row's pages, mask, softmax."""
    b, h, d = q.shape
    page = k_pages.shape[2]
    n_slots = block_tables.shape[1]
    k = k_pages[0][block_tables].reshape(b, n_slots * page, d)
    v = v_pages[0][block_tables].reshape(b, n_slots * page, d)
    s = jnp.einsum("bhd,bsd->bhs", q, k) / np.sqrt(d)
    pos = jnp.arange(n_slots * page)[None, :]
    s = jnp.where((pos < context_lens[:, None])[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsd->bhd", p, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# seeded serving context (page tables + live prefix lengths)
# ---------------------------------------------------------------------------


def paged_context(
    b: int = DEF_B, pages: int = DEF_PAGES, slots: int = DEF_SLOTS,
    page: int = DEF_PAGE,
) -> Dict[str, np.ndarray]:
    """Deterministic page tables: distinct physical pages per slot, and
    context lengths landing strictly inside the max ``slots * page``."""
    rng = np.random.default_rng(0)
    perm = rng.permutation(pages)[: b * slots]
    tables = perm.reshape(b, slots).astype(np.int32)
    lens = rng.integers(page + 1, slots * page // 2, size=b).astype(np.int32)
    return {"block_tables": tables, "context_lens": lens}


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def _table_operands(b: int, slots: int) -> tuple:
    return (
        OperandSpec("block_tables", (b, slots), np.int32, (b, slots),
                    lambda *pid: (0, 0)),
        OperandSpec("context_lens", (b,), np.int32, (b,),
                    lambda *pid: (0,)),
    )


def paged_decode_spec(
    b: int = DEF_B, h: int = DEF_H, d: int = DEF_D, page: int = DEF_PAGE,
    slots: int = DEF_SLOTS, dtype=np.float32,
) -> KernelSpec:
    """BASELINE: the pre-paging contiguous cache — every sequence owns a
    max-length ``slots * page`` row swept densely (affine maps)."""
    s = slots * page
    return KernelSpec(
        name="paged_decode_dense",
        grid=(b, slots),
        operands=(
            OperandSpec("Q", (b, h, d), dtype, (1, h, d),
                        lambda bi, j: (bi, 0, 0)),
            OperandSpec("Kcache", (b, s, d), dtype, (1, page, d),
                        lambda bi, j: (bi, j, 0)),
            OperandSpec("Vcache", (b, s, d), dtype, (1, page, d),
                        lambda bi, j: (bi, j, 0)),
            *_table_operands(b, slots),
            OperandSpec("O", (b, h, d), dtype, (1, h, d),
                        lambda bi, j: (bi, 0, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (h, d), np.float32),),
    )


def _paged_kv_touch(pages: int, page: int, d: int):
    """Level-2 model of the paged gather: program (b, j) touches the
    physical page ``block_tables[b, j]``, clamped to the live prefix."""

    def touch(pid, block_tables=None, context_lens=None, **_):
        bi, j = pid
        if block_tables is None or context_lens is None:
            return []
        ctx = int(context_lens[bi])
        live = min(page, ctx - j * page)
        if live <= 0:
            return []
        phys = int(block_tables[bi, j])
        base = phys * page * d
        return range(base, base + live * d)

    return touch


def paged_decode_paged_spec(
    b: int = DEF_B, h: int = DEF_H, d: int = DEF_D, page: int = DEF_PAGE,
    pages: int = DEF_PAGES, slots: int = DEF_SLOTS, dtype=np.float32,
) -> KernelSpec:
    """OPTIMIZED: the paged cache — K/V touches follow the block table
    and stop at ``context_lens`` (data-dependent, Level-2)."""
    touch = _paged_kv_touch(pages, page, d)
    return KernelSpec(
        name="paged_decode",
        grid=(b, slots),
        operands=(
            OperandSpec("Q", (b, h, d), dtype, (1, h, d),
                        lambda bi, j: (bi, 0, 0)),
            OperandSpec("Kcache", (pages, page, d), dtype, (1, page, d),
                        lambda bi, j: (0, 0, 0)),
            OperandSpec("Vcache", (pages, page, d), dtype, (1, page, d),
                        lambda bi, j: (0, 0, 0)),
            *_table_operands(b, slots),
            OperandSpec("O", (b, h, d), dtype, (1, h, d),
                        lambda bi, j: (bi, 0, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (h, d), np.float32),),
        dynamic=(("Kcache", touch), ("Vcache", touch)),
    )


def paged_prefill_spec(
    b: int = DEF_B, sq: int = DEF_SLOTS * DEF_PAGE, d: int = DEF_D,
    page: int = DEF_PAGE, slots: int = DEF_SLOTS, bq: int = 128,
    dtype=np.float32,
) -> KernelSpec:
    """BASELINE prefill: dense causal sweep over the contiguous cache."""
    s = slots * page
    return KernelSpec(
        name="paged_prefill_dense",
        grid=(b, sq // bq, slots),
        operands=(
            OperandSpec("Q", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, j: (bi, qi, 0)),
            OperandSpec("Kcache", (b, s, d), dtype, (1, page, d),
                        lambda bi, qi, j: (bi, j, 0)),
            OperandSpec("Vcache", (b, s, d), dtype, (1, page, d),
                        lambda bi, qi, j: (bi, j, 0)),
            *_table_operands(b, slots),
            OperandSpec("O", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, j: (bi, qi, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (bq, d), np.float32),),
    )


def paged_prefill_paged_spec(
    b: int = DEF_B, sq: int = DEF_SLOTS * DEF_PAGE, d: int = DEF_D,
    page: int = DEF_PAGE, pages: int = DEF_PAGES, slots: int = DEF_SLOTS,
    bq: int = 128, dtype=np.float32,
) -> KernelSpec:
    """OPTIMIZED prefill: paged gather + causal clamp on the KV walk."""

    def touch(pid, block_tables=None, context_lens=None, **_):
        bi, qi, j = pid
        if block_tables is None or context_lens is None:
            return []
        ctx = int(context_lens[bi])
        causal_hi = qi * bq + bq  # last kv row the diagonal admits
        live = min(page, ctx - j * page, causal_hi - j * page)
        if live <= 0:
            return []
        phys = int(block_tables[bi, j])
        base = phys * page * d
        return range(base, base + live * d)

    return KernelSpec(
        name="paged_prefill",
        grid=(b, sq // bq, slots),
        operands=(
            OperandSpec("Q", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, j: (bi, qi, 0)),
            OperandSpec("Kcache", (pages, page, d), dtype, (1, page, d),
                        lambda bi, qi, j: (0, 0, 0)),
            OperandSpec("Vcache", (pages, page, d), dtype, (1, page, d),
                        lambda bi, qi, j: (0, 0, 0)),
            *_table_operands(b, slots),
            OperandSpec("O", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, j: (bi, qi, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (bq, d), np.float32),),
        dynamic=(("Kcache", touch), ("Vcache", touch)),
    )
