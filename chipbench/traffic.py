"""Closed-loop waves for any traffic file; prompt tokens from the seed.

A wave is ``slots`` requests that share one prompt length and one output
length, admitted together and run to the end: the only traffic that
``Server`` answers correctly today, since all slots share one cache
length.  One cycle holds every (prompt, output) pair of the mix once, in
rows that each hold every prompt bucket once, with the outputs rotated
from row to row (a Latin square).  The order is the same for every seed,
and cycles repeat, so every run serves the same work and a window cut
inside a row still holds whole rows before it; the seed draws the prompt
tokens, uniform over the vocabulary.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

Wave = Tuple[int, int, np.ndarray]  # prompt length, output length, prompts (slots, plen)


def cycle(traffic: Dict) -> List[Tuple[int, int]]:
    prompts, outputs = traffic["prompt_buckets"], traffic["output_buckets"]
    return [
        (prompts[i], outputs[(i + r) % len(outputs)])
        for r in range(len(outputs))
        for i in range(len(prompts))
    ]


def waves(traffic: Dict, seed: int, vocab: int) -> Iterator[Wave]:
    """The window's waves, without end."""
    rng = np.random.default_rng([seed, 0])
    while True:
        for plen, out in cycle(traffic):
            yield plen, out, rng.integers(0, vocab, (traffic["slots"], plen), dtype=np.int32)


def warmup_waves(traffic: Dict, seed: int, vocab: int) -> Iterator[Wave]:
    """One wave per prompt bucket, two tokens each: every program the
    window runs (each prefill length, the decode, splices into every
    slot, the cache reset), from a stream of its own."""
    rng = np.random.default_rng([seed, 1])
    for plen in traffic["prompt_buckets"]:
        yield plen, 2, rng.integers(0, vocab, (traffic["slots"], plen), dtype=np.int32)
