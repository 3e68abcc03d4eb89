"""Serving runtime: batched prefill + decode with KV-cache management.

``Server`` packs concurrent requests into a fixed-batch decode loop:
prefill fills each request's cache slice; ``decode_step`` advances every
active slot one token; finished slots (EOS or max_tokens) are freed and
refilled from the queue — continuous batching at slot granularity.

While ``jax.profiler`` traces, every step writes host spans into the
trace (``serve.step``, ``serve.admit``, ``serve.prefill``,
``serve.splice``, ``serve.sample``, ``serve.decode``, and ``gc`` around
collections; see ``spans.py``), so each device-idle gap can be put down
to what the host was doing.  ``Server.counters`` counts prefill calls
and the prompt tokens they served, decode ticks and the slots they
served, collections and the slowest step, for an operator to read
between steps.

The dry-run lowers the same ``decode_step`` for the production meshes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spans import gc_counters, span, watch_gc

PyTree = Any


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (plen,) int32
    max_tokens: int = 16
    temperature: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_seq: int = 512
    eos_id: int = -1  # -1: never
    seed: int = 0


class Server:
    """Slot-based continuous batching over a single model replica."""

    def __init__(self, model, params: PyTree, cfg: ServeConfig,
                 dtype=jnp.float32):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.dtype = dtype
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * cfg.batch_slots
        self.key = jax.random.key(cfg.seed)
        # per-slot caches: one cache tree of batch = slots
        self.caches = model.init_caches(cfg.batch_slots, cfg.max_seq, dtype=dtype)
        # traces of each jitted entry, i.e. its compiles: prefill retraces
        # for every new prompt length
        self.compiles: Dict[str, int] = {"prefill": 0, "decode": 0}

        def decode(p, t, c):
            self.compiles["decode"] += 1
            return model.decode_step(p, t, c)

        def prefill(p, t, c):
            self.compiles["prefill"] += 1
            return model.prefill(p, t, c)

        self._decode = jax.jit(decode)
        self._prefill_one = jax.jit(prefill)
        self.slot_tokens = np.zeros((cfg.batch_slots, 1), np.int32)
        self.last_logits: Optional[jax.Array] = None  # (slots, 1, V) of the last tick
        # prefill calls (one request each) and the prompt tokens they
        # served, decode ticks and the slots they served, collections by
        # generation, and the slowest step since an operator last set
        # ``step_max_s`` to 0.  Each call and tick computes ``batch_slots``
        # rows, so the padding is slots x calls - calls, and slots x ticks
        # - ``decode_rows_used``.
        watch_gc()
        self.counters: Dict[str, float] = {
            "prefill_calls": 0, "prefill_tokens_used": 0,
            "decode_ticks": 0, "decode_rows_used": 0,
            **gc_counters(), "step_max_s": 0.0, "step_max_tick": -1,
        }

    # -- queue ------------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        """Prefill queued requests into free slots (one at a time)."""
        free = [slot for slot, r in enumerate(self.active) if r is None]
        n = min(len(free), len(self.queue))
        if not n:
            return
        with span("serve.admit", admitted=n):
            for slot in free[:n]:
                req = self.queue.pop(0)
                self._prefill_slot(slot, req)
                self.active[slot] = req

    def _prefill_slot(self, slot: int, req: Request) -> None:
        """Prefill ``req`` into ``slot`` with a masked full-batch pass: the
        prompt in its row, zeros in the others, on a fresh cache tree;
        then copy the slot's cache lines into the live tree, so that the
        other slots keep theirs."""
        plen = len(req.prompt)
        if plen >= self.cfg.max_seq:
            raise ValueError("prompt longer than max_seq")
        b = self.cfg.batch_slots
        self.counters["prefill_calls"] += 1
        self.counters["prefill_tokens_used"] += plen
        with span("serve.prefill", rid=req.rid, slot=slot, plen=plen, rows=b, used=1):
            toks = np.zeros((b, plen), np.int32)
            toks[slot] = req.prompt
            fresh = self.model.init_caches(b, self.cfg.max_seq, dtype=self.dtype)
            logits, filled = self._prefill_one(self.params, jnp.asarray(toks), fresh)
        with span("serve.splice", rid=req.rid, slot=slot):
            self.caches = _splice_slot(self.caches, filled, slot)
        with span("serve.sample", rid=req.rid, slots=1):
            nxt = self._sample(logits[slot, -1], req)
        self.slot_tokens[slot, 0] = nxt
        req.out_tokens.append(int(nxt))

    # -- decode ------------------------------------------------------------

    def _sample(self, logits: jax.Array, req: Request) -> int:
        if req.temperature <= 0.0:
            return int(jnp.argmax(logits))
        self.key, sub = jax.random.split(self.key)
        return int(
            jax.random.categorical(sub, logits / req.temperature)
        )

    def step(self) -> None:
        """One decode tick for all active slots."""
        t0 = time.perf_counter()
        tick = self.counters["decode_ticks"]
        with span("serve.step", tick=tick):
            self._step(tick)
        c = self.counters
        c.update(gc_counters())
        dt = time.perf_counter() - t0
        if dt > c["step_max_s"]:
            c["step_max_s"], c["step_max_tick"] = dt, tick

    def _step(self, tick: int) -> None:
        self._admit()
        used = sum(r is not None for r in self.active)
        if not used:
            return
        b = self.cfg.batch_slots
        self.counters["decode_ticks"] += 1
        self.counters["decode_rows_used"] += used
        with span("serve.decode", tick=tick, rows=b, used=used):
            logits, self.caches = self._decode(
                self.params, jnp.asarray(self.slot_tokens), self.caches
            )
        self.last_logits = logits
        with span("serve.sample", tick=tick, slots=used):
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                nxt = self._sample(logits[slot, 0], req)
                req.out_tokens.append(nxt)
                self.slot_tokens[slot, 0] = nxt
                if nxt == self.cfg.eos_id or len(req.out_tokens) >= req.max_tokens:
                    req.done = True
                    self.active[slot] = None

    def run_until_done(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.active):
                return
            self.step()


# base (unstacked) rank of each cache leaf kind; +1 when layer-stacked
_CACHE_BASE_RANK = {"k": 4, "v": 4, "c_kv": 3, "k_rope": 3, "conv": 3, "ssm": 4,
                    "length": 0}


def _splice_slot(live: PyTree, fresh: PyTree, slot: int) -> PyTree:
    """Copy slot ``slot``'s batch line from ``fresh`` into ``live``.

    Leaf kind is identified by its dict key; the batch dim is axis 0 for
    plain caches and axis 1 when stacked under a layer dim (rank is
    base+1).  The scalar ``length`` adopts the max: slots shorter than
    the max are correct because their cache lines past their own fill
    hold zero K/V that only their own decode steps overwrite, and
    positions mask attention per slot.
    """
    flat_live, treedef = jax.tree_util.tree_flatten_with_path(live)
    flat_fresh = jax.tree_util.tree_flatten_with_path(fresh)[0]
    out = []
    for (path, a), (_, b) in zip(flat_live, flat_fresh):
        name = str(getattr(path[-1], "key", ""))
        base = _CACHE_BASE_RANK.get(name)
        if base is None:
            out.append(a)
            continue
        if name == "length":
            out.append(jnp.maximum(a, b))
            continue
        if a.ndim == base:  # plain: (B, ...)
            out.append(a.at[slot].set(b[slot]))
        else:  # stacked: (L, B, ...)
            out.append(a.at[:, slot].set(b[:, slot]))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(live), [x for x in out]
    )
