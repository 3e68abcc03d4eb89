"""Plain reference of a dense decoder with grouped-query attention.

The llama block that granite-3.0 uses: pre-norm RMSNorm, rotary position
embedding (rotate-half), grouped-query attention with a causal mask,
SwiGLU feed-forward, a final RMSNorm and a tied unembedding.  The
config's ``attention_multiplier``, ``embedding_multiplier``,
``residual_multiplier`` and ``logits_scaling`` are applied as granite
defines them.

float32 throughout and every product at HIGHEST precision.  It imports
nothing of the program: it reads the benchmark's weight tree by name and
casts each layer to float32 as it goes.  One sequence at a time, whole
(no cache): ``logits_fn(cfg)`` gives a jitted
``(weights, tokens (S,), rows (M,)) -> (M, V)`` with the logits that
predict the token after each position in ``rows``.

``fmt="fp8"`` is the control: every operand of every product quantized
to float8 e4m3 (scaled per row or output channel) and multiplied in
float32, the lower precision a later change could be tempted to serve in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

INIT = {
    "embedding": ("embedding", 0.02),
    "scale": ("ones",),
    "wq": ("fan_in", 1, 2),
    "wk": ("fan_in", 1, 2),
    "wv": ("fan_in", 1, 2),
    "wo": ("fan_in", 2, 1),
    "w_gate": ("fan_in", 1, 1),
    "w_up": ("fan_in", 1, 1),
    "w_down": ("fan_in", 1, 1),
}


def qdq(x, axes, fmt):
    """``x`` rounded to ``fmt`` with one scale per slice over ``axes``."""
    if fmt is None:
        return x
    if fmt != "fp8":
        raise ValueError(f"unknown control format {fmt!r}")
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotate-half rotary embedding of ``x`` (S, heads, hd) at positions 0..S-1."""
    s, hd = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits_fn(cfg, fmt=None):
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    att = cfg["attention_multiplier"]
    emb_mult = cfg["embedding_multiplier"]
    res = cfg["residual_multiplier"]
    logit_div = cfg["logits_scaling"]
    f32 = jnp.float32

    def mm(spec, a, a_axes, b, b_axes):
        return jnp.einsum(spec, qdq(a, a_axes, fmt), qdq(b, b_axes, fmt),
                          precision=HIGHEST, preferred_element_type=f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda w: w.astype(f32), lw)
        a = lw["attn"]
        t = rms(x, lw["norm_mixer"]["scale"], eps)
        q = rope(mm("sd,dhk->shk", t, -1, a["wq"], 0), theta)
        k = rope(mm("sd,dhk->shk", t, -1, a["wk"], 0), theta)
        v = mm("sd,dhk->shk", t, -1, a["wv"], 0)
        k = jnp.repeat(k, h // kv, axis=1)  # query head j reads KV head j // (h/kv)
        v = jnp.repeat(v, h // kv, axis=1)
        s = mm("qhk,thk->hqt", q, -1, k, -1) * att
        n = s.shape[-1]
        causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = mm("hqt,thk->qhk", p, -1, v, 0)
        x = x + res * mm("qhk,hkd->qd", o, (-2, -1), a["wo"], (0, 1))
        m = lw["mlp"]
        t = rms(x, lw["norm_ffn"]["scale"], eps)
        g = mm("sd,df->sf", t, -1, m["w_gate"], 0)
        u = mm("sd,df->sf", t, -1, m["w_up"], 0)
        x = x + res * mm("sf,fd->sd", jax.nn.silu(g) * u, -1, m["w_down"], 0)
        return x, None

    @jax.jit
    def fn(weights, tokens, rows):
        emb = weights["embed"]["embedding"]
        x = emb[tokens].astype(f32) * emb_mult
        x, _ = jax.lax.scan(layer, x, weights["stack"]["seg0"])
        x = rms(x[rows], weights["final_norm"]["scale"].astype(f32), eps)
        return mm("md,vd->mv", x, -1, emb.astype(f32), -1) / logit_div

    return fn
