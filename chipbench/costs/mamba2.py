"""Operations and bytes of a Mamba2 decoder's serving steps (see
``chipbench/flops.py``).  Weights are bfloat16, the SSM state float32,
as the program keeps them."""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4


def _mamba(cfg: Dict) -> Dict[str, int]:
    d, layers = cfg["d_model"], cfg["n_layer"]
    di = cfg["expand"] * d
    p, n, g, k = cfg["headdim"], cfg["d_state"], cfg["ngroups"], cfg["d_conv"]
    h = di // p
    conv_dim = di + 2 * g * n
    in_dim = 2 * di + 2 * g * n + h
    layer_params = d + d * in_dim + k * conv_dim + conv_dim + 3 * h + di + di * d
    params = cfg["padded_vocab_size"] * d + layers * layer_params + d
    # per token and layer: projections, the depthwise conv, and the
    # recurrence h = a*h + (dt x) B^T (3 per state element), y = C.h (2)
    layer_flops = 2 * d * in_dim + 2 * k * conv_dim + 5 * h * p * n + 2 * di * d
    state_bytes = h * p * n * F32 + (k - 1) * conv_dim * BF16
    return dict(d=d, layers=layers, params=params, layer_flops=layer_flops,
                state_bytes=state_bytes, vocab=cfg["vocab_size"])


def decode(cfg: Dict, slots: int, kv_len: int) -> Tuple[float, float]:
    s = _mamba(cfg)
    per_token = s["layers"] * s["layer_flops"] + 2 * s["d"] * s["vocab"]
    state = slots * s["layers"] * s["state_bytes"] * 2  # read and write
    return float(slots * per_token), float(s["params"] * BF16 + state)


def prefill(cfg: Dict, plen: int) -> float:
    s = _mamba(cfg)
    return float(s["layers"] * s["layer_flops"] * plen + 2 * s["d"] * s["vocab"])
