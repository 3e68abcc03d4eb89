"""Mamba2 (SSD — state-space duality) layer: chunked scan + O(1) decode.

The SSD algorithm (Dao & Gu, arXiv:2405.21060) computes the selective
state-space recurrence

    h_t = exp(A dt_t) h_{t-1} + dt_t * B_t x_t^T ,   y_t = C_t . h_t + D x_t

by splitting the sequence into chunks: an intra-chunk quadratic
(attention-like) term plus an inter-chunk state recurrence.  The chunked
form is matmul-dominated (MXU-friendly); the per-token recurrent form is
used for decode (O(1) state: the reason `long_500k` runs on SSM archs).

``ssd_ref`` is the pure-jnp oracle; ``repro.kernels.ssd`` holds the
Pallas TPU kernel for the intra-chunk term.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import rmsnorm
from .params import ParamDef


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_state: int = 128  # N
    head_dim: int = 64  # P
    expand: int = 2
    n_groups: int = 1  # G (B/C groups, GQA-like)
    conv_kernel: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1
    norm_eps: float = 1e-6  # of the gated RMSNorm

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def mamba_defs(cfg: SSMConfig) -> Dict[str, ParamDef]:
    d, di, g, n, h = cfg.d_model, cfg.d_inner, cfg.n_groups, cfg.d_state, cfg.n_heads
    in_dim = 2 * di + 2 * g * n + h  # z, x, B, C, dt
    return {
        "w_in": ParamDef((d, in_dim), ("embed", "mlp")),
        "conv_w": ParamDef((cfg.conv_kernel, cfg.conv_dim), (None, "mlp"), scale=1.0),
        "conv_b": ParamDef((cfg.conv_dim,), ("mlp",), init="zeros"),
        "A_log": ParamDef((h,), ("heads",), init="zeros"),  # A = -exp(A_log)-init below
        "D": ParamDef((h,), ("heads",), init="ones"),
        "dt_bias": ParamDef((h,), ("heads",), init="zeros"),
        "norm_scale": ParamDef((di,), ("mlp",), init="ones"),
        "w_out": ParamDef((di, d), ("mlp", "embed"), init="out_proj"),
    }


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """Lower-triangular segment sums: out[..., i, j] = sum_{j<k<=i} x[..., k]."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_ref(
    x: jax.Array,  # (B, S, H, P) — already dt-scaled inputs (dt * x)
    a: jax.Array,  # (B, S, H)   — log decay per step (A * dt, negative)
    bmat: jax.Array,  # (B, S, H, N)
    cmat: jax.Array,  # (B, S, H, N)
    chunk: int = 64,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD; returns (y (B,S,H,P), final_state (B,H,P,N))."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    c = s // chunk
    xr = x.reshape(b, c, chunk, h, p)
    ar = a.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)  # (B,H,C,L)
    br = bmat.reshape(b, c, chunk, h, n)
    cr = cmat.reshape(b, c, chunk, h, n)

    a_cum = jnp.cumsum(ar, axis=-1)  # (B,H,C,L)

    # 1. intra-chunk (diagonal blocks): attention-like with decay mask
    ll = jnp.exp(_segsum(ar))  # (B,H,C,L,L)
    y_diag = jnp.einsum(
        "bclhn,bcshn,bhcls,bcshp->bclhp", cr, br, ll, xr,
        preferred_element_type=jnp.float32,
    )

    # 2. per-chunk final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # (B,H,C,L)
    states = jnp.einsum(
        "bclhn,bhcl,bclhp->bchpn", br, decay_states, xr,
        preferred_element_type=jnp.float32,
    )

    # 3. inter-chunk recurrence over chunk states
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    states = jnp.concatenate([initial_state[:, None], states], axis=1)  # (B,C+1,H,P,N)
    chunk_decay = a_cum[..., -1]  # (B,H,C)
    padded = jnp.pad(chunk_decay, ((0, 0), (0, 0), (1, 0)))
    dmat = jnp.exp(_segsum(padded))  # (B,H,C+1,C+1)
    dmat = jnp.where(jnp.isfinite(dmat), dmat, 0.0)
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", dmat, states)
    prev_states, final_state = new_states[:, :-1], new_states[:, -1]

    # 4. state -> output
    out_decay = jnp.exp(a_cum)  # (B,H,C,L)
    y_off = jnp.einsum(
        "bclhn,bchpn,bhcl->bclhp", cr, prev_states, out_decay,
        preferred_element_type=jnp.float32,
    )
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final_state


def ssd_decode_step(
    state: jax.Array,  # (B, H, P, N) f32
    x_t: jax.Array,  # (B, H, P) — dt-scaled input
    a_t: jax.Array,  # (B, H) — log decay
    b_t: jax.Array,  # (B, H, N)
    c_t: jax.Array,  # (B, H, N)
) -> Tuple[jax.Array, jax.Array]:
    """One recurrent step. Returns (y_t (B,H,P), new_state)."""
    decay = jnp.exp(a_t)[..., None, None]  # (B,H,1,1)
    upd = jnp.einsum("bhp,bhn->bhpn", x_t, b_t)
    new_state = decay * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, c_t)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# causal depthwise conv (kernel k): 4 shifted adds, decode uses a k-1 cache
# ---------------------------------------------------------------------------


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, S, C), w: (k, C), b: (C,). Causal depthwise conv + silu."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = jnp.zeros_like(x, dtype=jnp.float32)
    s = x.shape[1]
    for i in range(k):
        y = y + xp[:, i : i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
    y = y + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def causal_conv_step(
    conv_state: jax.Array,  # (B, k-1, C) most recent inputs, oldest first
    x_t: jax.Array,  # (B, C)
    w: jax.Array,
    b: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    k = w.shape[0]
    window = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # (B, k, C)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
    y = jax.nn.silu(y + b.astype(jnp.float32)).astype(x_t.dtype)
    new_state = window[:, 1:]
    return y, new_state


# ---------------------------------------------------------------------------
# full layer
# ---------------------------------------------------------------------------


def _split_in(proj: jax.Array, cfg: SSMConfig):
    di, g, n, h = cfg.d_inner, cfg.n_groups, cfg.d_state, cfg.n_heads
    z = proj[..., :di]
    xbc = proj[..., di : di + cfg.conv_dim]
    dt = proj[..., di + cfg.conv_dim :]  # (.., h)
    return z, xbc, dt


def _split_xbc(xbc: jax.Array, cfg: SSMConfig):
    di, g, n = cfg.d_inner, cfg.n_groups, cfg.d_state
    x = xbc[..., :di]
    bm = xbc[..., di : di + g * n]
    cm = xbc[..., di + g * n :]
    return x, bm, cm


def _broadcast_groups(m: jax.Array, cfg: SSMConfig) -> jax.Array:
    """(B, S, G*N) -> (B, S, H, N) by repeating each group over its heads."""
    b, s = m.shape[:2]
    m = m.reshape(b, s, cfg.n_groups, cfg.d_state)
    reps = cfg.n_heads // cfg.n_groups
    return jnp.repeat(m, reps, axis=2)


def init_mamba_cache(batch: int, cfg: SSMConfig, dtype: Any = jnp.bfloat16):
    return {
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.head_dim, cfg.d_state), jnp.float32),
    }


def abstract_mamba_cache(batch: int, cfg: SSMConfig, dtype: Any = jnp.bfloat16):
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
        "ssm": jax.ShapeDtypeStruct(
            (batch, cfg.n_heads, cfg.head_dim, cfg.d_state), jnp.float32
        ),
    }


def mamba_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d_model)
    cfg: SSMConfig,
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    b, s, _ = x.shape
    proj = x @ params["w_in"].astype(x.dtype)
    z, xbc, dt_raw = _split_in(proj, cfg)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (B,S,H)
    a_neg = -jnp.exp(params["A_log"].astype(jnp.float32))  # (H,) negative

    if cache is not None and s == 1:
        xbc_t, conv_state = causal_conv_step(
            cache["conv"], xbc[:, 0], params["conv_w"], params["conv_b"]
        )
        xs, bm, cm = _split_xbc(xbc_t[:, None], cfg)
        xh = xs.reshape(b, 1, cfg.n_heads, cfg.head_dim)[:, 0]
        bh = _broadcast_groups(bm, cfg)[:, 0]
        ch = _broadcast_groups(cm, cfg)[:, 0]
        dt_t = dt[:, 0]  # (B,H)
        with jax.named_scope("scan"):  # the SSD recurrence, apart from the projections
            y_t, ssm_state = ssd_decode_step(
                cache["ssm"],
                (xh * dt_t[..., None]).astype(jnp.float32),
                a_neg[None] * dt_t,
                bh.astype(jnp.float32),
                ch.astype(jnp.float32),
            )
        y_t = y_t + params["D"].astype(jnp.float32)[None, :, None] * xh
        y = y_t.reshape(b, 1, cfg.d_inner).astype(x.dtype)
        new_cache = {"conv": conv_state, "ssm": ssm_state}
    else:
        xbc_c = causal_conv(xbc, params["conv_w"], params["conv_b"])
        xs, bm, cm = _split_xbc(xbc_c, cfg)
        xh = xs.reshape(b, s, cfg.n_heads, cfg.head_dim)
        bh = _broadcast_groups(bm, cfg)
        ch = _broadcast_groups(cm, cfg)
        with jax.named_scope("scan"):
            y4, final_state = ssd_ref(
                (xh * dt[..., None]).astype(jnp.float32),
                a_neg[None, None] * dt,
                bh.astype(jnp.float32),
                ch.astype(jnp.float32),
                chunk=min(cfg.chunk, s),
            )
        y4 = y4 + params["D"].astype(jnp.float32)[None, None, :, None] * xh
        y = y4.reshape(b, s, cfg.d_inner).astype(x.dtype)
        new_cache = None
        if cache is not None:  # prefill: fill conv + ssm states
            conv_in = xbc[:, -(cfg.conv_kernel - 1) :]
            new_cache = {"conv": conv_in.astype(cache["conv"].dtype), "ssm": final_state}

    # gated RMSNorm (mamba2's norm(y * silu(z)))
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = rmsnorm({"scale": params["norm_scale"]}, y, cfg.norm_eps)
    return y @ params["w_out"].astype(x.dtype), new_cache


def ssd_naive_ref(
    x: jax.Array, a: jax.Array, bmat: jax.Array, cmat: jax.Array,
    initial_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pure sequential recurrence — the ground-truth oracle for ssd_ref."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    state = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state
    )

    def step(state, t):
        y, state = ssd_decode_step(
            state, x[:, t].astype(jnp.float32), a[:, t], bmat[:, t], cmat[:, t]
        )
        return state, y

    state, ys = jax.lax.scan(step, state, jnp.arange(s))
    return ys.transpose(1, 0, 2, 3), state
