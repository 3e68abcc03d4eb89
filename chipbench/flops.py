"""Operations and bytes of one serving step, from a configuration's sizes.

Each family's functions live in ``chipbench/costs/<family>.py``, found by
the config's ``family``:

* ``decode(cfg, slots, kv_len)`` -> (flops, bytes) of one decode tick that
  advances ``slots`` sequences, each attending over ``kv_len`` positions
  (its prompt, its tokens so far and the new one).  Bytes are what the
  tick has to move at the least: every weight once, and the cache or
  state of each slot up to its filled length, not the whole buffer.
* ``prefill(cfg, plen)`` -> the useful flops of one request's prompt: one
  row, causal attention, the unembedding of its last position only.

A multiply-add counts as 2 flops.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple


@functools.lru_cache(maxsize=None)
def _costs(family: str):
    from chipbench.cells import HERE, load_module

    return load_module(HERE / "costs" / f"{family}.py")


def decode(cfg: Dict, slots: int, kv_len: int) -> Tuple[float, float]:
    return _costs(cfg["family"]).decode(cfg, slots, kv_len)


def prefill(cfg: Dict, plen: int) -> float:
    return _costs(cfg["family"]).prefill(cfg, plen)
