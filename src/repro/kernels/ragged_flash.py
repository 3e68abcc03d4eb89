"""Ragged flash attention — the serving-shaped EasyDeL-style kernel.

A decode batch packs sequences of very different lengths: each row ``b``
attends only to KV positions in ``[starts[b], ends[b])``.  The dense
kernel sweeps every KV block for every sequence; the ragged kernel
prefetches the bounds as scalars (``PrefetchScalarGridSpec``) and skips
blocks wholly outside the row's live range with ``pl.when`` — the
standard serving trick (EasyDeL's ``ragged_flash_attention_kernel``).

Profiler story: the dense sweep is the *baseline* rung (static, affine
index maps — the Level-1 walker and the lint static model cover it
exactly); the ragged skip is the *optimized* rung whose K/V footprint is
data-dependent, modeled as a Level-2 dynamic access over the seeded
``starts``/``ends`` context.  The transfer delta between the rungs IS
the blocks-skipped saving, which is what lets ``cuthermo tune`` accept
the ragged rung on real numbers.

Decode shapes: Q ``(B, H, D)`` (one query per sequence, MQA — one KV
head shared by all H query heads), K/V ``(B, S, D)``.  Prefill shapes:
Q ``(B, Sq, D)`` with causal masking.
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

# registry default shapes (CI-sized; see ragged_context for the bounds)
DEF_B, DEF_H, DEF_S, DEF_D, DEF_BKV = 4, 8, 512, 128, 128


def _ragged_decode_kernel(
    s_ref, e_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bkv: int, n_kv: int, scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = s_ref[b]
    end = e_ref[b]
    block_start = i * bkv

    @pl.when((block_start < end) & (block_start + bkv > start))
    def _run():
        q = q_ref[0]  # (H, D)
        k = k_ref[0]  # (bkv, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=dot_precision(q.dtype),
        ) * scale  # (H, bkv)
        kpos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1
        )
        s = jnp.where((kpos >= start) & (kpos < end), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=dot_precision(v_ref.dtype),
        )
        m_scr[...] = m_new

    @pl.when(i == n_kv - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def ragged_decode_attention(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, S, D) — MQA: one KV head
    v: jax.Array,
    starts: jax.Array,  # (B,) int32
    ends: jax.Array,  # (B,) int32
    bkv: int = DEF_BKV,
    interpret: bool = False,
) -> jax.Array:
    b, h, d = q.shape
    s = k.shape[1]
    bkv = min(bkv, s)
    assert s % bkv == 0
    n_kv = s // bkv
    kernel = functools.partial(
        _ragged_decode_kernel,
        bkv=bkv, n_kv=n_kv, scale=1.0 / float(np.sqrt(d)),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_kv),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, i, *_: (bi, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda bi, i, *_: (bi, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda bi, i, *_: (bi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, i, *_: (bi, 0, 0)),
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
    )(starts.astype(jnp.int32), ends.astype(jnp.int32), q, k, v)


def ragged_decode_reference(q, k, v, starts, ends):
    """Pure-jnp oracle for ``ragged_decode_attention``."""
    d = q.shape[-1]
    s = jnp.einsum("bhd,bsd->bhs", q, k) / np.sqrt(d)
    pos = jnp.arange(k.shape[1])[None, :]
    mask = (pos >= starts[:, None]) & (pos < ends[:, None])  # (B, S)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsd->bhd", p, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# seeded serving context (the ragged bounds the dynamic walkers replay)
# ---------------------------------------------------------------------------


def ragged_context(b: int = DEF_B, s: int = DEF_S) -> Dict[str, np.ndarray]:
    """Deterministic ragged bounds: starts near 0, ends well short of S."""
    rng = np.random.default_rng(0)
    starts = rng.integers(0, s // 8, size=b).astype(np.int32)
    ends = (starts + rng.integers(s // 8, s // 2, size=b)).astype(np.int32)
    return {"starts": starts, "ends": np.minimum(ends, s).astype(np.int32)}


# ---------------------------------------------------------------------------
# profiler specs
# ---------------------------------------------------------------------------


def _bounds_operands(b: int) -> tuple:
    return (
        OperandSpec("starts", (b,), np.int32, (b,), lambda *pid: (0,)),
        OperandSpec("ends", (b,), np.int32, (b,), lambda *pid: (0,)),
    )


def ragged_decode_spec(
    b: int = DEF_B, h: int = DEF_H, s: int = DEF_S, d: int = DEF_D,
    bkv: int = DEF_BKV, dtype=np.float32,
) -> KernelSpec:
    """BASELINE: dense decode sweep — every program loads its KV block
    whether or not the row's ragged range reaches it (affine maps)."""
    n_kv = s // bkv
    return KernelSpec(
        name="ragged_decode_dense",
        grid=(b, n_kv),
        operands=(
            OperandSpec("Q", (b, h, d), dtype, (1, h, d),
                        lambda bi, i: (bi, 0, 0)),
            OperandSpec("K", (b, s, d), dtype, (1, bkv, d),
                        lambda bi, i: (bi, i, 0)),
            OperandSpec("V", (b, s, d), dtype, (1, bkv, d),
                        lambda bi, i: (bi, i, 0)),
            *_bounds_operands(b),
            OperandSpec("O", (b, h, d), dtype, (1, h, d),
                        lambda bi, i: (bi, 0, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (h, d), np.float32),),
    )


def _ragged_kv_touch(s: int, d: int, bkv: int):
    """Level-2 model of the ``pl.when`` block-skip gate: program (b, i)
    touches only the rows of block i inside ``[starts[b], ends[b])``."""

    def touch(pid, starts=None, ends=None, **_):
        bi, i = pid
        if starts is None or ends is None:
            return []
        lo = max(i * bkv, int(starts[bi]))
        hi = min((i + 1) * bkv, int(ends[bi]))
        if lo >= hi:
            return []
        base = bi * s * d
        return range(base + lo * d, base + hi * d)

    return touch


def ragged_decode_ragged_spec(
    b: int = DEF_B, h: int = DEF_H, s: int = DEF_S, d: int = DEF_D,
    bkv: int = DEF_BKV, dtype=np.float32,
) -> KernelSpec:
    """OPTIMIZED: the ragged skip — K/V touches clamp to the live range."""
    touch = _ragged_kv_touch(s, d, bkv)
    spec = ragged_decode_spec(b, h, s, d, bkv, dtype)
    return KernelSpec(
        name="ragged_decode",
        grid=spec.grid,
        operands=spec.operands,
        scratch=spec.scratch,
        dynamic=(("K", touch), ("V", touch)),
    )


def ragged_prefill_spec(
    b: int = DEF_B, sq: int = DEF_S, s: int = DEF_S, d: int = DEF_D,
    bq: int = DEF_BKV, bkv: int = DEF_BKV, dtype=np.float32,
) -> KernelSpec:
    """BASELINE prefill: dense causal sweep over (q block, kv block)."""
    return KernelSpec(
        name="ragged_prefill_dense",
        grid=(b, sq // bq, s // bkv),
        operands=(
            OperandSpec("Q", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, ki: (bi, qi, 0)),
            OperandSpec("K", (b, s, d), dtype, (1, bkv, d),
                        lambda bi, qi, ki: (bi, ki, 0)),
            OperandSpec("V", (b, s, d), dtype, (1, bkv, d),
                        lambda bi, qi, ki: (bi, ki, 0)),
            *_bounds_operands(b),
            OperandSpec("O", (b, sq, d), dtype, (1, bq, d),
                        lambda bi, qi, ki: (bi, qi, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (bq, d), np.float32),),
    )


def ragged_prefill_ragged_spec(
    b: int = DEF_B, sq: int = DEF_S, s: int = DEF_S, d: int = DEF_D,
    bq: int = DEF_BKV, bkv: int = DEF_BKV, dtype=np.float32,
) -> KernelSpec:
    """OPTIMIZED prefill: causal + ragged clamp on the KV walk."""

    def touch(pid, starts=None, ends=None, **_):
        bi, qi, ki = pid
        if starts is None or ends is None:
            return []
        causal_hi = qi * bq + bq  # last kv row the diagonal admits
        lo = max(ki * bkv, int(starts[bi]))
        hi = min((ki + 1) * bkv, int(ends[bi]), causal_hi)
        if lo >= hi:
            return []
        base = bi * s * d
        return range(base + lo * d, base + hi * d)

    spec = ragged_prefill_spec(b, sq, s, d, bq, bkv, dtype)
    return KernelSpec(
        name="ragged_prefill",
        grid=spec.grid,
        operands=spec.operands,
        scratch=spec.scratch,
        dynamic=(("K", touch), ("V", touch)),
    )
