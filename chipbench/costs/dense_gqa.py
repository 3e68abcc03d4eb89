"""Operations and bytes of a dense GQA decoder's serving steps (see
``chipbench/flops.py``).  Weights and the KV cache are bfloat16."""

from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def _dense(cfg: Dict) -> Dict[str, int]:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff, layers = cfg["intermediate_size"], cfg["num_hidden_layers"]
    layer_mm = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    params = cfg["padded_vocab_size"] * d + layers * (layer_mm + 2 * d) + d
    return dict(d=d, h=h, kv=kv, hd=hd, layers=layers, layer_mm=layer_mm, params=params,
                vocab=cfg["vocab_size"])


def decode(cfg: Dict, slots: int, kv_len: int) -> Tuple[float, float]:
    s = _dense(cfg)
    per_token = (2 * s["layers"] * s["layer_mm"]
                 + 4 * s["layers"] * s["h"] * s["hd"] * kv_len
                 + 2 * s["d"] * s["vocab"])
    kv_bytes = slots * s["layers"] * 2 * s["kv"] * s["hd"] * BF16 * kv_len
    return float(slots * per_token), float(s["params"] * BF16 + kv_bytes)


def prefill(cfg: Dict, plen: int) -> float:
    s = _dense(cfg)
    pairs = plen * (plen + 1) // 2  # causal (query, key) pairs
    return float(2 * s["layers"] * s["layer_mm"] * plen
                 + 4 * s["layers"] * s["h"] * s["hd"] * pairs
                 + 2 * s["d"] * s["vocab"])
