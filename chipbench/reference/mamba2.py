"""Plain reference of a Mamba2 (SSD) decoder, as arXiv:2405.21060 defines it.

Each layer: pre-norm RMSNorm; one input projection to (z, x, B, C, dt);
a causal depthwise convolution with SiLU over (x, B, C);
dt = softplus(dt + dt_bias), A = -exp(A_log); the selective state-space
recurrence

    h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

computed in its quadratic (attention-like) dual form,
y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<k<=t} A dt_k) dt_s x_s + D x_t,
over blocks of query rows; the gate RMSNorm(y * silu(z)); the output
projection and the residual add.  No MLP; a final RMSNorm and a tied
unembedding.

float32 throughout and every product at HIGHEST precision.  It imports
nothing of the program and reads the benchmark's weight tree by name.
``logits_fn(cfg)`` gives a jitted ``(weights, tokens (S,), rows (M,)) ->
(M, V)``.  ``fmt="fp8"`` is the control, as in ``dense_gqa``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import HIGHEST, qdq, rms

INIT = {
    "embedding": ("embedding", 0.02),
    "scale": ("ones",),
    "w_in": ("fan_in", 1, 1),
    "conv_w": ("uniform", -0.5, 0.5),
    "conv_b": ("zeros",),
    "A_log": ("log_uniform", 1.0, 16.0),
    "D": ("ones",),
    "dt_bias": ("inv_softplus_log_uniform", 1e-3, 1e-1),
    "norm_scale": ("ones",),
    "w_out": ("fan_in", 1, 1),
}

QUERY_BLOCK = 256


def logits_fn(cfg, fmt=None):
    d = cfg["d_model"]
    di = cfg["expand"] * d
    p, n, g, k = cfg["headdim"], cfg["d_state"], cfg["ngroups"], cfg["d_conv"]
    nh = di // p
    eps = cfg["norm_epsilon"]
    f32 = jnp.float32

    def mm(spec, a, a_axes, b, b_axes):
        return jnp.einsum(spec, qdq(a, a_axes, fmt), qdq(b, b_axes, fmt),
                          precision=HIGHEST, preferred_element_type=f32)

    def ssd(xs, dt, a, bm, cm):
        """y (S, H, P) of the recurrence, by blocks of query rows."""
        s = xs.shape[0]
        cs = jnp.cumsum(a, axis=0)  # (S, H)
        u = xs * dt[..., None]  # (S, H, P)
        head_group = jnp.arange(nh) // (nh // g)
        out = []
        for q0 in range(0, s, QUERY_BLOCK):
            q1 = min(q0 + QUERY_BLOCK, s)
            causal = jnp.arange(s)[None, :] <= jnp.arange(q0, q1)[:, None]  # (Q, S)
            seg = cs[q0:q1, None, :] - cs[None, :, :]  # (Q, S, H)
            decay = jnp.exp(jnp.where(causal[..., None], seg, -jnp.inf))
            cb = mm("qgn,sgn->qsg", cm[q0:q1], -1, bm, -1)[..., head_group]  # (Q, S, H)
            out.append(mm("qsh,shp->qhp", decay * cb, 1, u, 0))
        return jnp.concatenate(out, axis=0)

    def layer(x, lw):
        lw = jax.tree.map(lambda w: w.astype(f32), lw)
        m = lw["mamba"]
        s = x.shape[0]
        t = rms(x, lw["norm_mixer"]["scale"], eps)
        proj = mm("sd,de->se", t, -1, m["w_in"], 0)
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * n], proj[:, 2 * di + 2 * g * n:]
        xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        conv = sum(xp[i:i + s] * m["conv_w"][i] for i in range(k)) + m["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :di].reshape(s, nh, p)
        bm = xbc[:, di:di + g * n].reshape(s, g, n)
        cm = xbc[:, di + g * n:].reshape(s, g, n)
        dt = jax.nn.softplus(dt + m["dt_bias"])  # (S, H)
        a = -jnp.exp(m["A_log"]) * dt
        y = ssd(xs, dt, a, bm, cm) + m["D"][None, :, None] * xs
        y = y.reshape(s, di) * jax.nn.silu(z)
        y = rms(y, m["norm_scale"], eps)
        return x + mm("se,ed->sd", y, -1, m["w_out"], 0), None

    @jax.jit
    def fn(weights, tokens, rows):
        emb = weights["embed"]["embedding"]
        x = emb[tokens].astype(f32)
        x, _ = jax.lax.scan(layer, x, weights["stack"]["seg0"])
        x = rms(x[rows], weights["final_norm"]["scale"].astype(f32), eps)
        return mm("md,vd->mv", x, -1, emb.astype(f32), -1)

    return fn
