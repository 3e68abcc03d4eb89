"""Plain reference of granite-4.0-h (granitemoehybrid): Mamba2 and NoPE
attention mixers, a sparse MoE feed-forward in every layer.

Each layer, pre-norm (RMSNorm): the mixer, then the MoE, each output
scaled by ``residual_multiplier`` before its residual add.  The mixer is
the layer's ``layer_types`` entry:

* ``mamba``: one input projection to (z, x, B, C, dt); a causal
  depthwise convolution with bias and SiLU over (x, B, C);
  dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD recurrence
  h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, in
  its quadratic dual form over blocks of query rows; the gated RMSNorm
  RMSNorm(y * silu(z)); the output projection.
* ``attention``: grouped-query attention with no position embedding,
  scores scaled by ``attention_multiplier``, a causal mask.

The MoE: router logits over all experts, the softmax of the top
``num_experts_per_tok`` of them; each held expert (``w_gate`` holds
``num_local_experts`` of them, from ``first_local_expert``) is a SwiGLU
weighted by its gate where the token chose it; absent experts add
nothing.  Plus a shared SwiGLU expert for every token.  The embedding is
scaled by ``embedding_multiplier``; a final RMSNorm, the tied
unembedding, logits divided by ``logits_scaling``.

float32 throughout and every product at HIGHEST precision; it routes on
its own float32 router logits.  It imports nothing of the program: it
reads the benchmark's weight tree by name, segment by segment (a
segment's leaves carry a leading layer dimension when it stacks layers),
and casts each layer to float32 as it goes.  ``logits_fn(cfg)`` gives a
jitted ``(weights, tokens (S,), rows (M,)) -> (M, V)``.  ``fmt="fp8"`` is
the control, as in ``dense_gqa``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import HIGHEST, qdq, rms

INIT = {
    # the embedding enters the residual stream times embedding_multiplier
    # (12): drawn at 0.02 / 12 it enters at the 0.02 of an unscaled one.
    # Drawn at 0.02, the last token's own row outweighs every other in the
    # tied unembedding at full width (about 19 against 5.6), so every
    # position's argmax repeats its input and no error could show
    "embedding": ("embedding", 0.02 / 12),
    "scale": ("ones",),
    # Mamba2 mixer
    "w_in": ("fan_in", 1, 1),
    "conv_w": ("uniform", -0.5, 0.5),
    "conv_b": ("zeros",),
    "A_log": ("log_uniform", 1.0, 16.0),
    "D": ("ones",),
    "dt_bias": ("inv_softplus_log_uniform", 1e-3, 1e-1),
    "norm_scale": ("ones",),
    "w_out": ("fan_in", 1, 1),
    # attention
    "wq": ("fan_in", 1, 2),
    "wk": ("fan_in", 1, 2),
    "wv": ("fan_in", 1, 2),
    "wo": ("fan_in", 2, 1),
    # MoE: router, held experts (experts, in, out), shared expert
    "router": ("fan_in", 1, 1),
    "w_gate": ("fan_in", 1, 1),
    "w_up": ("fan_in", 1, 1),
    "w_down": ("fan_in", 1, 1),
    "shared_w_gate": ("fan_in", 1, 1),
    "shared_w_up": ("fan_in", 1, 1),
    "shared_w_down": ("fan_in", 1, 1),
}

QUERY_BLOCK = 256
MIXERS = {"mamba": "mamba", "attention": "attn"}  # layer type -> weight group


def logits_fn(cfg, fmt=None):
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g, k = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    di = cfg["mamba_expand"] * d
    if di != nh * p:
        raise ValueError(f"mamba inner width {di} != {nh} heads x {p}")
    eps = cfg["rms_norm_eps"]
    att = cfg["attention_multiplier"]
    res = cfg["residual_multiplier"]
    top_k, first = cfg["num_experts_per_tok"], cfg["first_local_expert"]
    kinds = list(cfg["layer_types"][: cfg["num_hidden_layers"]])
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

    def mamba(t, m):
        s = t.shape[0]
        proj = mm("sd,de->se", t, -1, m["w_in"], 0)
        z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * g * n], proj[:, 2 * di + 2 * g * n:]
        xp = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(xp[i:i + s] * m["conv_w"][i] for i in range(k)) + m["conv_b"])
        xs = xbc[:, :di].reshape(s, nh, p)
        bm = xbc[:, di:di + g * n].reshape(s, g, n)
        cm = xbc[:, di + g * n:].reshape(s, g, n)
        dt = jax.nn.softplus(dt + m["dt_bias"])  # (S, H)
        a = -jnp.exp(m["A_log"]) * dt
        y = ssd(xs, dt, a, bm, cm) + m["D"][None, :, None] * xs
        y = rms(y.reshape(s, di) * jax.nn.silu(z), m["norm_scale"], eps)
        return mm("se,ed->sd", y, -1, m["w_out"], 0)

    def attention(t, a):
        q = mm("sd,dhk->shk", t, -1, a["wq"], 0)
        kk = jnp.repeat(mm("sd,dhk->shk", t, -1, a["wk"], 0), h // kv, axis=1)
        v = jnp.repeat(mm("sd,dhk->shk", t, -1, a["wv"], 0), h // kv, axis=1)
        s = mm("qhk,thk->hqt", q, -1, kk, -1) * att
        m = s.shape[-1]
        causal = jnp.arange(m)[None, :] <= jnp.arange(m)[:, None]
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = mm("hqt,thk->qhk", pr, -1, v, 0)
        return mm("qhk,hkd->qd", o, (-2, -1), a["wo"], (0, 1))

    def swiglu(t, gate, up, down, spec_in, spec_out):
        hid = jax.nn.silu(mm(spec_in, t, -1, gate, -2)) * mm(spec_in, t, -1, up, -2)
        return mm(spec_out, hid, -1, down, -2)

    def moe(t, w):
        logits = mm("sd,de->se", t, -1, w["router"], 0)  # (S, all experts)
        top, ids = jax.lax.top_k(logits, top_k)
        gate = jax.nn.softmax(top, axis=-1)  # (S, top_k)
        held = w["w_gate"].shape[0]
        # each held expert's gate where the token chose it; ids of absent
        # experts fall outside [0, held) and one-hot to nothing
        weight = jnp.einsum("sk,ske->se", gate, jax.nn.one_hot(ids - first, held, dtype=f32))
        eo = swiglu(t, w["w_gate"], w["w_up"], w["w_down"], "sd,edf->sef", "sef,efd->sed")
        y = jnp.einsum("sed,se->sd", eo, weight, precision=HIGHEST)
        return y + swiglu(t, w["shared_w_gate"], w["shared_w_up"], w["shared_w_down"],
                          "sd,df->sf", "sf,fd->sd")

    def block(x, lw, kind):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        t = rms(x, lw["norm_mixer"]["scale"], eps)
        y = mamba(t, lw["mamba"]) if kind == "mamba" else attention(t, lw["attn"])
        x = x + res * y
        t = rms(x, lw["norm_ffn"]["scale"], eps)
        return x + res * moe(t, lw["moe"])

    def pattern(seg):
        """The layer types of one application of a segment, in order."""
        subs = [seg[f"sub{i}"] for i in range(len(seg))] if "sub0" in seg else [seg]
        return [next(t for t, key in MIXERS.items() if key in sub) for sub in subs], subs

    def apply_once(x, seg):
        types, subs = pattern(seg)
        for kind, lw in zip(types, subs):
            x = block(x, lw, kind)
        return x

    def repeats(seg):
        """How many times a segment applies its pattern: its leaves carry a
        leading layer dimension when more than once."""
        scale = (seg["sub0"] if "sub0" in seg else seg)["norm_mixer"]["scale"]
        return scale.shape[0] if scale.ndim == 2 else 1

    @jax.jit
    def fn(weights, tokens, rows):
        stack = weights["stack"]
        segs = [stack[key] for key in sorted(stack, key=lambda s: int(s[3:]))]
        got = [t for seg in segs for t in pattern(seg)[0] * repeats(seg)]
        if got != kinds:
            raise ValueError(f"weight tree holds layers {got}, the config {kinds}")
        emb = weights["embed"]["embedding"]
        x = emb[tokens].astype(f32) * cfg["embedding_multiplier"]
        for seg in segs:
            if repeats(seg) > 1:
                x, _ = jax.lax.scan(lambda x, lw: (apply_once(x, lw), None), x, seg)
            else:
                x = apply_once(x, seg)
        x = rms(x[rows], weights["final_norm"]["scale"].astype(f32), eps)
        return mm("md,vd->mv", x, -1, emb.astype(f32), -1) / cfg["logits_scaling"]

    return fn
