"""Operations and bytes of a granite-4.0-h (Mamba2 + attention + MoE)
decoder's serving steps (see ``chipbench/flops.py``).  Weights and the KV
cache are bfloat16, the SSM state float32, as the program keeps them.

Of the experts, the layer holds ``num_local_experts`` and the router
scores ``router_experts``; a token's ``num_experts_per_tok`` choices fall
on held experts ``top_k x held / router`` times on average, the count the
flops take (the convention of both steps).  A decode tick's least bytes
read every held expert's weights once."""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4


def _sizes(cfg: Dict) -> Dict[str, float]:
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    di = cfg["mamba_expand"] * d
    mh, mp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g, k = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    conv_dim = di + 2 * g * n
    in_dim = 2 * di + 2 * g * n + mh
    held, experts = cfg["num_local_experts"], cfg["router_experts"]
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    types = cfg["layer_types"][:layers]
    n_attn = types.count("attention")
    n_mamba = layers - n_attn
    mamba_params = d + d * in_dim + k * conv_dim + conv_dim + 3 * mh + di + di * d
    attn_params = d + 2 * d * h * hd + 2 * d * kv * hd
    moe_params = d + d * experts + held * 3 * d * f + 3 * d * fs
    per_token_held = cfg["num_experts_per_tok"] * held / experts
    return dict(
        d=d, layers=layers, h=h, kv=kv, hd=hd, n_attn=n_attn, n_mamba=n_mamba,
        vocab=cfg["vocab_size"],
        params=(cfg["padded_vocab_size"] * d + n_mamba * mamba_params + n_attn * attn_params
                + layers * moe_params + d),
        moe_params=moe_params,
        # per token and layer: router, held choices, shared expert
        moe_flops=2 * d * experts + per_token_held * 6 * d * f + 6 * d * fs,
        # per token and layer: projections, the depthwise conv, and the
        # recurrence h = a*h + (dt x) B^T (3 per state element), y = C.h (2)
        mamba_flops=2 * d * in_dim + 2 * k * conv_dim + 5 * mh * mp * n + 2 * di * d,
        attn_proj_flops=2 * (2 * d * h * hd + 2 * d * kv * hd),
        state_bytes=mh * mp * n * F32 + (k - 1) * conv_dim * BF16,
    )


def moe_decode(cfg: Dict, slots: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode tick's MoE layers: router, held
    experts' and shared expert's weights once."""
    s = _sizes(cfg)
    return (float(slots * s["layers"] * s["moe_flops"]),
            float(s["layers"] * s["moe_params"] * BF16))


def decode(cfg: Dict, slots: int, kv_len: int) -> Tuple[float, float]:
    s = _sizes(cfg)
    per_token = (s["n_mamba"] * s["mamba_flops"]
                 + s["n_attn"] * (s["attn_proj_flops"] + 4 * s["h"] * s["hd"] * kv_len)
                 + s["layers"] * s["moe_flops"]
                 + 2 * s["d"] * s["vocab"])
    state = slots * s["n_mamba"] * s["state_bytes"] * 2  # read and write
    kv_bytes = slots * s["n_attn"] * 2 * s["kv"] * s["hd"] * BF16 * kv_len
    return float(slots * per_token), float(s["params"] * BF16 + state + kv_bytes)


def prefill(cfg: Dict, plen: int) -> float:
    s = _sizes(cfg)
    pairs = plen * (plen + 1) // 2  # causal (query, key) pairs
    return float((s["n_mamba"] * s["mamba_flops"] + s["n_attn"] * s["attn_proj_flops"]
                  + s["layers"] * s["moe_flops"]) * plen
                 + s["n_attn"] * 4 * s["h"] * s["hd"] * pairs
                 + 2 * s["d"] * s["vocab"])
