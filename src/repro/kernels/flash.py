"""Flash attention Pallas kernel (TPU target, interpret-validated).

Grid: (batch*kv_heads*q_groups, q_blocks, kv_blocks) with the KV axis
innermost; online-softmax running (m, l, acc) lives in VMEM scratch and
persists across the kv_blocks axis (grid axes iterate sequentially per
core on TPU, so scratch carries state between kv steps of the same q
block — the standard TPU flash formulation).

Causal masking skips fully-masked kv blocks via ``pl.when``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.collector import KernelSpec, OperandSpec, ScratchSpec
from repro.kernels.mxu import dot_precision

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, bq: int, bkv: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=dot_precision(q.dtype),
        ) * scale  # (bq, bkv)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=dot_precision(v_ref.dtype),
        )
        m_ref[...] = m_new

    if causal:
        # skip kv blocks entirely above the diagonal
        pl.when(ki * bkv <= qi * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (BH, Sq, D) — batch*heads flattened
    k: jax.Array,  # (BH, Skv, D) — kv heads already broadcast to q heads
    v: jax.Array,
    causal: bool = True,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    n_kv = skv // bkv
    scale = 1.0 / float(np.sqrt(d))
    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, bq=bq, bkv=bkv, causal=causal, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, qi, ki: (h, ki, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, qi, ki: (h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def flash_spec(
    bh: int, sq: int, skv: int, d: int, bq: int = 128, bkv: int = 128,
    dtype=np.float32,
) -> KernelSpec:
    """Level-1 profiler geometry of the flash kernel."""
    return KernelSpec(
        name="flash_attention",
        grid=(bh, sq // bq, skv // bkv),
        operands=(
            OperandSpec("Q", (bh, sq, d), dtype, (1, bq, d),
                        lambda h, qi, ki: (h, qi, 0)),
            OperandSpec("K", (bh, skv, d), dtype, (1, bkv, d),
                        lambda h, qi, ki: (h, ki, 0)),
            OperandSpec("V", (bh, skv, d), dtype, (1, bkv, d),
                        lambda h, qi, ki: (h, ki, 0)),
            OperandSpec("O", (bh, sq, d), dtype, (1, bq, d),
                        lambda h, qi, ki: (h, qi, 0), kind="store"),
        ),
        scratch=(ScratchSpec("acc", (bq, d), np.float32),),
    )
