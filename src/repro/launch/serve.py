"""Serving launcher: batched continuous-batching server driver.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --requests 8 --max-tokens 16
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Request, ServeConfig, Server


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    srv = Server(
        model, params,
        ServeConfig(batch_slots=args.slots, max_seq=args.max_seq, seed=args.seed),
        dtype=cfg.dtype,
    )
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(2, 12))
        srv.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
            max_tokens=args.max_tokens,
            temperature=args.temperature,
        ))
    t0 = time.perf_counter()
    srv.run_until_done()
    dt = time.perf_counter() - t0
    tokens = args.requests * args.max_tokens
    ticks = srv.counters["decode_ticks"]
    print(f"[serve] {args.requests} requests, {tokens} tokens in {dt:.2f}s "
          f"({tokens/dt:.1f} tok/s), {ticks} decode ticks")
    return {"tokens": tokens, "seconds": dt, "ticks": ticks}


if __name__ == "__main__":
    main()
