"""Attention: GQA/MHA (chunked flash-in-XLA), KV caches, sliding window, MLA.

One execution path, ``flash_xla``: an online-softmax scan over KV chunks
with a custom VJP.  Same memory behaviour class as flash attention (O(S)
live activations instead of O(S^2)), pure XLA, on every backend.  The
Pallas flash kernel in ``repro.kernels.flash`` is not called from here;
the profiler models it for each attention layer (``repro.core.model_kernels``).

KV caches are plain dicts of arrays + a scalar length; decode updates are
``dynamic_update_slice`` so a serve step compiles to a fixed shape.

MLA (DeepSeek-V2/V3 multi-head latent attention) caches the 512-d latent
+ 64-d rope key only; decode uses the *absorbed* formulation (q projected
into latent space) so per-token cost is O(S * kv_lora) instead of
O(S * heads * head_dim).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import apply_mrope, apply_rope, rmsnorm, rmsnorm_defs
from .params import ParamDef

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    causal: bool = True
    use_rope: bool = True
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl
    sliding_window: Optional[int] = None
    chunk: int = 512  # kv chunk for the xla flash path
    scale: Optional[float] = None  # score scale; None: 1/sqrt(head_dim)

    @property
    def q_groups(self) -> int:
        return self.n_heads // self.n_kv_heads


# ---------------------------------------------------------------------------
# parameter defs
# ---------------------------------------------------------------------------


def attn_defs(cfg: AttnConfig) -> Dict[str, ParamDef]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "kv")),
        "wk": ParamDef((d, kv, hd), ("embed", "heads", "kv")),
        "wv": ParamDef((d, kv, hd), ("embed", "heads", "kv")),
        "wo": ParamDef((h, hd, d), ("heads", "kv", "embed"), init="out_proj"),
    }


# ---------------------------------------------------------------------------
# chunked online-softmax attention (flash-in-XLA)
# ---------------------------------------------------------------------------


def _chunk_mask(kpos, qpos, skv, causal, window, kv_length):
    mask = kpos < skv  # padding tail
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_length is not None:
        mask &= kpos < kv_length
    return mask


def _flash_fwd_scan(q5, kcs, vcs, qpos, skv, causal, window, kv_length, chunk):
    """Online-softmax forward. Returns (out5, lse) in the 5-D layout."""

    def body(carry, xs):
        m, l, acc = carry
        kcb, vcb, c0 = xs
        s = jnp.einsum(
            "bkgqd,bckd->bkgqc", q5, kcb, preferred_element_type=jnp.float32
        )
        kpos = (c0 + jnp.arange(chunk))[None, None, None, None, :]
        mask = _chunk_mask(kpos, qpos, skv, causal, window, kv_length)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, -0.5e30)  # fully-masked row guard
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(vcb.dtype), vcb,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[..., None] + pv
        return (m_safe, l_new, acc_new), None

    b, kvh, g, sq, d = q5.shape
    n_chunks = kcs.shape[0]
    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, d), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kcs, vcs, starts))
    l = jnp.maximum(l, 1e-30)
    out5 = acc / l[..., None]
    lse = m + jnp.log(l)  # logsumexp row stats for the backward
    return out5, lse


def _flash_core(q, k, v, q_positions, kv_length, causal, window, chunk, scale):
    """Layout plumbing shared by fwd/bwd. Returns 5-D tensors + meta."""
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    chunk = min(chunk, skv)
    n_chunks = (skv + chunk - 1) // chunk
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q5 = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4) * scale
    kcs = k.reshape(b, n_chunks, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vcs = v.reshape(b, n_chunks, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    qpos = q_positions[:, None, None, :, None]
    return q5, kcs, vcs, qpos, (b, sq, h, d, skv, kvh, g, scale, chunk, n_chunks, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_xla(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KV, D)
    v: jax.Array,  # (B, Skv, KV, D)
    q_positions: jax.Array,  # (B, Sq) int32
    kv_length: Optional[jax.Array] = None,  # scalar int32: valid cache length
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 512,
    scale: Optional[float] = None,  # score scale; None: 1/sqrt(D)
) -> jax.Array:
    """Online-softmax attention scanned over KV chunks (flash-in-XLA).

    Exact; O(chunk) live memory.  The custom VJP recomputes per-chunk
    probabilities in the backward (true flash backward) instead of
    letting scan-AD stash every chunk's p-matrix — measured 2.1 GiB/layer
    of backward residuals on granite-8b train_4k without it.
    """
    out, _ = _flash_fwd(q, k, v, q_positions, kv_length, causal, window, chunk, scale)
    return out


def _flash_fwd(q, k, v, q_positions, kv_length, causal, window, chunk, scale):
    q5, kcs, vcs, qpos, meta = _flash_core(
        q, k, v, q_positions, kv_length, causal, window, chunk, scale
    )
    b, sq, h, d, skv, kvh, g, scale, chunk_, n_chunks, pad = meta
    out5, lse = _flash_fwd_scan(
        q5, kcs, vcs, qpos, skv, causal, window, kv_length, chunk_
    )
    out = out5.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)
    res = (q, k, v, q_positions, kv_length, out5, lse)
    return out, res


def _flash_bwd(causal, window, chunk, scale, res, dout):
    q, k, v, q_positions, kv_length, out5, lse = res
    q5, kcs, vcs, qpos, meta = _flash_core(
        q, k, v, q_positions, kv_length, causal, window, chunk, scale
    )
    b, sq, h, d, skv, kvh, g, scale, chunk_, n_chunks, pad = meta
    do5 = (
        dout.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
    )
    # delta = rowsum(dO * O)
    delta = jnp.sum(do5 * out5, axis=-1)  # (B,KV,G,Sq)

    def body(dq_acc, xs):
        kcb, vcb, c0 = xs
        s = jnp.einsum(
            "bkgqd,bckd->bkgqc", q5, kcb, preferred_element_type=jnp.float32
        )
        kpos = (c0 + jnp.arange(chunk_))[None, None, None, None, :]
        mask = _chunk_mask(kpos, qpos, skv, causal, window, kv_length)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # exact probabilities, recomputed
        dv_c = jnp.einsum(
            "bkgqc,bkgqd->bckd", p, do5, preferred_element_type=jnp.float32
        )
        dp = jnp.einsum(
            "bkgqd,bckd->bkgqc", do5, vcb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[..., None])  # d(scaled scores)
        dq_c = jnp.einsum(
            "bkgqc,bckd->bkgqd", ds, kcb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        dk_c = jnp.einsum(
            "bkgqc,bkgqd->bckd", ds, q5, preferred_element_type=jnp.float32
        )
        return dq_acc + dq_c, (dk_c, dv_c)

    dq0 = jnp.zeros(q5.shape, jnp.float32)
    starts = jnp.arange(n_chunks) * chunk_
    dq5, (dkc, dvc) = jax.lax.scan(body, dq0, (kcs, vcs, starts))
    dq = (dq5 * scale).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)
    skv_p = n_chunks * chunk_
    dk = dkc.transpose(1, 0, 2, 3, 4).reshape(b, skv_p, kvh, d)[:, : k.shape[1]]
    dv = dvc.transpose(1, 0, 2, 3, 4).reshape(b, skv_p, kvh, d)[:, : v.shape[1]]
    if pad:
        dk = dk[:, : skv]
        dv = dv[:, : skv]
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)
    dpos = jnp.zeros(q_positions.shape, jax.dtypes.float0)
    dlen = (
        None
        if kv_length is None
        else jnp.zeros(jnp.shape(kv_length), jax.dtypes.float0)
    )
    return dq, dk, dv, dpos, dlen


flash_xla.defvjp(_flash_fwd, _flash_bwd)


def attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_positions: jax.Array,
    kv_length: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,  # score scale; None: 1/sqrt(D)
) -> jax.Array:
    """Naive O(S^2) oracle (tests + tiny decode)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / math.sqrt(d) if scale is None else s * scale
    kpos = jnp.arange(k.shape[1])[None, None, None, :]
    qpos = q_positions[:, None, :, None]
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_length is not None:
        mask &= kpos < kv_length
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_cache(
    batch: int, max_seq: int, n_kv: int, head_dim: int, dtype: Any = jnp.bfloat16
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, n_kv, head_dim), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def abstract_cache(
    batch: int, max_seq: int, n_kv: int, head_dim: int, dtype: Any = jnp.bfloat16
) -> Dict[str, jax.ShapeDtypeStruct]:
    return {
        "k": jax.ShapeDtypeStruct((batch, max_seq, n_kv, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, max_seq, n_kv, head_dim), dtype),
        "length": jax.ShapeDtypeStruct((), jnp.int32),
    }


def update_seq_buffer(buf: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write ``new`` into ``buf`` along axis 1 at position ``idx``.

    Sharding-aware: a one-token write uses a one-hot select (elementwise —
    partitions cleanly when the seq dim is model-sharded, where a
    dynamic-update-slice makes GSPMD materialize the whole buffer); a
    full-length write replaces the buffer; other cases fall back to DUS.
    """
    s = new.shape[1]
    cap = buf.shape[1]
    new = new.astype(buf.dtype)
    if s == cap:
        return new
    if s == 1:
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, cap) + (1,) * (buf.ndim - 2), 1)
        hit = pos == jnp.reshape(idx, (1,) * buf.ndim)
        return jnp.where(hit, new, buf)
    start = (0, idx) + (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, new, start)


def cache_update(
    cache: Dict[str, jax.Array], k_new: jax.Array, v_new: jax.Array
) -> Dict[str, jax.Array]:
    """Append (B, s, KV, D) at the current length (decode: s == 1)."""
    idx = cache["length"]
    k = update_seq_buffer(cache["k"], k_new, idx)
    v = update_seq_buffer(cache["v"], v_new, idx)
    return {"k": k, "v": v, "length": idx + k_new.shape[1]}


# ---------------------------------------------------------------------------
# GQA attention block apply
# ---------------------------------------------------------------------------


def attn_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d_model)
    positions: jax.Array,  # (B, S) int32, or (B, S, 3) for m-rope
    cfg: AttnConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
    use_flash: bool = True,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))

    if cfg.use_rope:
        if cfg.mrope_sections is not None:
            q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
            qpos1d = positions[..., 0]
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            qpos1d = positions
    else:
        qpos1d = positions if positions.ndim == 2 else positions[..., 0]

    new_cache = None
    if cache is not None:
        new_cache = cache_update(cache, k, v)
        k_all, v_all = new_cache["k"], new_cache["v"]
        kv_len = new_cache["length"]
        if s == 1:
            out = attention_ref(
                q, k_all.astype(q.dtype), v_all.astype(q.dtype), qpos1d,
                kv_length=kv_len, causal=False, window=cfg.sliding_window,
                scale=cfg.scale,
            )
        else:
            out = flash_xla(
                q, k_all.astype(q.dtype), v_all.astype(q.dtype), qpos1d,
                kv_len, cfg.causal, cfg.sliding_window, cfg.chunk, cfg.scale,
            )
    else:
        # NOTE on GQA + TP: when n_kv_heads < model-axis size, flash's
        # (B,KV,G,Sq,D) layout leaves attention head-REPLICATED across the
        # model axis (~2.2 TB/device f32 score traffic on granite-8b
        # train_4k).  Expanding KV to query heads + re-constraining on
        # heads was tried and measured WORSE (seq-gather x head-scatter
        # per layer in both directions: memory 5.9->11.6 s, wire 5.6->18.3
        # s) — see EXPERIMENTS.md §Perf-3.  The real fix is a 2-D
        # (heads x seq) context-parallel attention layout or an 8-way
        # model axis for kv=8 archs; left as the documented next lever.
        if use_flash:
            out = flash_xla(
                q, k, v, qpos1d, None, cfg.causal, cfg.sliding_window, cfg.chunk,
                cfg.scale,
            )
        else:
            out = attention_ref(
                q, k, v, qpos1d, causal=cfg.causal, window=cfg.sliding_window,
                scale=cfg.scale,
            )

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d) decoder states
    enc: jax.Array,  # (B, S_enc, d) encoder states
    cfg: AttnConfig,
) -> jax.Array:
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", enc, params["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc, params["wv"].astype(enc.dtype))
    qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    out = flash_xla(q, k, v, qpos, None, False, None, cfg.chunk)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    chunk: int = 512

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_defs(cfg: MLAConfig) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq_a": ParamDef((d, cfg.q_lora_rank), ("embed", None)),
        "q_norm": rmsnorm_defs(cfg.q_lora_rank)["scale"],
        "wq_b": ParamDef((cfg.q_lora_rank, h, cfg.qk_head_dim), (None, "heads", "kv")),
        "wkv_a": ParamDef((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), ("embed", None)),
        "kv_norm": rmsnorm_defs(cfg.kv_lora_rank)["scale"],
        "wk_b": ParamDef((cfg.kv_lora_rank, h, cfg.qk_nope_head_dim), (None, "heads", "kv")),
        "wv_b": ParamDef((cfg.kv_lora_rank, h, cfg.v_head_dim), (None, "heads", "kv")),
        "wo": ParamDef((h, cfg.v_head_dim, d), ("heads", "kv", "embed"), init="out_proj"),
    }


def init_mla_cache(
    batch: int, max_seq: int, cfg: MLAConfig, dtype: Any = jnp.bfloat16
) -> Dict[str, jax.Array]:
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def abstract_mla_cache(
    batch: int, max_seq: int, cfg: MLAConfig, dtype: Any = jnp.bfloat16
) -> Dict[str, jax.ShapeDtypeStruct]:
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct((batch, max_seq, cfg.qk_rope_head_dim), dtype),
        "length": jax.ShapeDtypeStruct((), jnp.int32),
    }


def _mla_qkv_latent(params, x, positions, cfg: MLAConfig):
    """Shared front: q heads (nope+rope) and the (c_kv, k_rope) latents."""
    # queries through the low-rank bottleneck
    q_lat = x @ params["wq_a"].astype(x.dtype)
    q_lat = rmsnorm({"scale": params["q_norm"]}, q_lat)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["wq_b"].astype(x.dtype))
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim :], positions, cfg.rope_theta)
    # kv latent + shared rope key
    kv = x @ params["wkv_a"].astype(x.dtype)
    c_kv = rmsnorm({"scale": params["kv_norm"]}, kv[..., : cfg.kv_lora_rank])
    k_rope = apply_rope(
        kv[..., cfg.kv_lora_rank :][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,
    positions: jax.Array,
    cfg: MLAConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """MLA attention in the ABSORBED ("MLA-as-MQA") form for every path.

    q_nope is absorbed through wk_b into the latent space, so flash
    attention runs with a single shared 576-d K (= [c_kv ; k_rope]) and a
    512-d latent V — kv_heads == 1, exactly MQA.  The expanded per-head
    K/V (B,S,128,192 — 3.2 GiB/device/layer on deepseek train_4k, whose
    backward psum'd 1.4 TB/device over the SP axis) is never materialized;
    score FLOPs grow 3x (576 vs 192 contraction) but attention is a small
    slice of the MoE-dominated total.  Decode gets the same absorbed math
    on the latent cache (O(S*r) per token).
    """
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(params, x, positions, cfg)
    new_cache = None
    kv_len = None
    if cache is not None:
        idx = cache["length"]
        c_all = update_seq_buffer(cache["c_kv"], c_kv, idx)
        r_all = update_seq_buffer(cache["k_rope"], k_rope, idx)
        new_cache = {"c_kv": c_all, "k_rope": r_all, "length": idx + x.shape[1]}
        if x.shape[1] == 1:
            y = _mla_absorbed_decode(params, q_nope, q_rope, new_cache, cfg, x.dtype)
            out = jnp.einsum("bshk,hkd->bsd", y, params["wo"].astype(x.dtype))
            return out, new_cache
        c_kv, k_rope = c_all.astype(x.dtype), r_all.astype(x.dtype)
        kv_len = new_cache["length"]

    # absorb q into the latent: q_lat[h] = q_nope[h] @ wk_b[h]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wk_b"].astype(x.dtype))
    q_all = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,S,H,R+P)
    k_all = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]  # MQA K
    v_lat = c_kv[:, :, None, :]  # (B,S,1,R)
    r, p = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    # flash scales by 1/sqrt(R+P); MLA wants 1/sqrt(qk_head_dim)
    q_all = q_all * math.sqrt((r + p) / cfg.qk_head_dim)
    vpad = jnp.pad(v_lat, ((0, 0), (0, 0), (0, 0), (0, p)))
    out_lat = flash_xla(q_all, k_all, vpad, positions, kv_len, True, None,
                        cfg.chunk)[..., :r]
    out = jnp.einsum("bshr,rhk->bshk", out_lat, params["wv_b"].astype(x.dtype))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, new_cache


def _mla_absorbed_decode(params, q_nope, q_rope, cache, cfg: MLAConfig, dtype):
    """Absorbed decode: score/attend directly in the 512-d latent space.

    q_lat[h] = q_nope[h] @ wk_b[h]^T  — the weight absorption — so scores
    are q_lat . c_kv + q_rope . k_rope and the attended value is a latent
    vector later expanded through wv_b.  Per-token cost O(S * kv_lora)
    instead of O(S * heads * qk_head_dim).
    """
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    c_kv = cache["c_kv"].astype(dtype)  # (B, S, R)
    k_rope = cache["k_rope"].astype(dtype)  # (B, S, P)
    kv_len = cache["length"]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wk_b"].astype(dtype))
    s_nope = jnp.einsum("bshr,btr->bhst", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshp,btp->bhst", q_rope, k_rope,
                        preferred_element_type=jnp.float32)
    s = (s_nope + s_rope) * scale
    tpos = jnp.arange(c_kv.shape[1])[None, None, None, :]
    s = jnp.where(tpos < kv_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    lat = jnp.einsum("bhst,btr->bshr", p.astype(dtype), c_kv,
                     preferred_element_type=jnp.float32).astype(dtype)
    return jnp.einsum("bshr,rhk->bshk", lat, params["wv_b"].astype(dtype))
