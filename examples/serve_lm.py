"""Serve a small LM with batched, continuously-batched requests.

    PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, build_model
from repro.runtime import Request, ServeConfig, Server


def main() -> None:
    cfg = ModelConfig(name="serve-demo", family="dense", n_layers=4,
                      d_model=128, n_heads=8, n_kv_heads=4, d_ff=512,
                      vocab=2048, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    srv = Server(model, params,
                 ServeConfig(batch_slots=4, max_seq=128, seed=0),
                 dtype=jnp.float32)

    rng = np.random.default_rng(0)
    n_requests = 10
    for rid in range(n_requests):
        plen = int(rng.integers(3, 20))
        srv.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
            max_tokens=12,
            temperature=0.0 if rid % 2 == 0 else 0.8,
        ))

    t0 = time.perf_counter()
    srv.run_until_done()
    dt = time.perf_counter() - t0
    total = n_requests * 12
    ticks = srv.counters["decode_ticks"]
    print(f"{n_requests} requests x 12 tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, {ticks} decode ticks, "
          f"{total / max(ticks, 1):.1f} tokens/tick batching efficiency)")


if __name__ == "__main__":
    main()
