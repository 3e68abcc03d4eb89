"""Serving runtime: batched prefill + decode with KV-cache management.

``Server`` packs concurrent requests into a fixed-batch decode loop:
prefill fills each request's cache slice; ``decode_step`` advances every
active slot one token; finished slots (EOS or max_tokens) are freed and
refilled from the queue — continuous batching at slot granularity.

The jitted step that makes the logits also picks every row's next token
on the device (``_pick``: argmax where the row's temperature is 0, a
draw from the tempered softmax elsewhere, with a PRNG key threaded from
step to step), so the host reads one ``(slots,)`` int32 vector per step
and passes each slot's pick through ``Server._sample``.

While ``jax.profiler`` traces, every step writes host spans into the
trace (``serve.step``, ``serve.admit``, ``serve.prefill``,
``serve.splice``, ``serve.sample``, ``serve.decode``, and ``gc`` around
collections; see ``spans.py``), so each device-idle gap can be put down
to what the host was doing.  ``Server.counters`` counts prefill calls
and the prompt tokens they served, decode ticks and the slots they
served, the reads of the step's picks, collections and the slowest
step, for an operator to read between steps.

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
        self.key = jax.random.key(cfg.seed)  # on the device, threaded through the steps
        # per-slot caches: one cache tree of batch = slots
        self.caches = model.init_caches(cfg.batch_slots, cfg.max_seq, dtype=dtype)
        # traces of each jitted entry, i.e. its compiles: prefill retraces
        # for every new prompt length
        self.compiles: Dict[str, int] = {"prefill": 0, "decode": 0}

        def decode(p, t, c, temps, key):
            self.compiles["decode"] += 1
            logits, c = model.decode_step(p, t, c)
            picks, key = _pick(logits[:, -1], temps, key)
            return logits, picks, c, key

        def prefill(p, t, c, temps, key):
            self.compiles["prefill"] += 1
            logits, c = model.prefill(p, t, c)
            picks, key = _pick(logits[:, -1], temps, key)
            return logits, picks, c, key

        def extra():
            return self._temps, self.key

        self._decode = _Step(decode, extra)
        self._prefill_one = _Step(prefill, extra)
        self.slot_tokens = np.zeros((cfg.batch_slots, 1), np.int32)
        # each slot's request temperature; uploaded when a slot is filled
        self.slot_temps = np.zeros((cfg.batch_slots,), np.float32)
        self._temps = jnp.asarray(self.slot_temps)
        self.last_logits: Optional[jax.Array] = None  # (slots, 1, V) of the last tick
        # prefill calls (one request each) and the prompt tokens they
        # served, decode ticks and the slots they served, collections by
        # generation, and the slowest step since an operator last set
        # ``step_max_s`` to 0.  Each call and tick computes ``batch_slots``
        # rows, so the padding is slots x calls - calls, and slots x ticks
        # - ``decode_rows_used``.  ``sample_reads`` counts the reads of a
        # step's picks: one per prefill call and per decode tick.
        watch_gc()
        self.counters: Dict[str, float] = {
            "prefill_calls": 0, "prefill_tokens_used": 0,
            "decode_ticks": 0, "decode_rows_used": 0, "sample_reads": 0,
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
            # a freed slot keeps its temperature until it is filled again:
            # its row is computed and its pick ignored either way
            self.slot_temps[slot] = req.temperature
            self._temps = jnp.asarray(self.slot_temps)
            logits, picks, filled, self.key = self._prefill_one(
                self.params, jnp.asarray(toks), fresh, self._temps, self.key)
        with span("serve.splice", rid=req.rid, slot=slot):
            self.caches = _splice_slot(self.caches, filled, slot)
        with span("serve.sample", rid=req.rid, slots=1):
            nxt = self._sample(self._read(picks)[slot], req)
            self.slot_tokens[slot, 0] = nxt
            req.out_tokens.append(nxt)

    # -- decode ------------------------------------------------------------

    def _read(self, picks: jax.Array) -> np.ndarray:
        """The step's picks on the host: its one device-to-host read."""
        self.counters["sample_reads"] += 1
        return np.asarray(picks)

    def _sample(self, pick: int, req: Request) -> int:
        """The token served to ``req``: its slot's pick of the step.  Every
        served token passes through here, at admission and at every tick."""
        return int(pick)

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
            logits, picks, self.caches, self.key = self._decode(
                self.params, jnp.asarray(self.slot_tokens), self.caches, self._temps, self.key
            )
        self.last_logits = logits
        with span("serve.sample", tick=tick, slots=used):
            picks = self._read(picks)
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                nxt = self._sample(picks[slot], req)
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


class _Step:
    """``jax.jit`` of a step ``fn(params, tokens, caches, temps, key)``.
    ``lower`` may be given the model's arguments alone, as
    ``chipbench/attribute.py`` gives them to read the op scopes: it then
    lowers with ``extra()``, the server's temperatures and key, so that
    the compiled step is the program that runs."""

    def __init__(self, fn: Callable, extra: Callable[[], Tuple[jax.Array, jax.Array]]):
        self.jit = jax.jit(fn)
        self.extra = extra

    def __call__(self, *args):
        return self.jit(*args)

    def lower(self, *args):
        return self.jit.lower(*args, *(self.extra() if len(args) == 3 else ()))


def _pick(logits: jax.Array, temps: jax.Array, key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Each row's next token from ``logits`` (B, V) and the key for the next
    step: the argmax where the row's temperature ``temps`` (B,) is 0 or
    less, else a draw from softmax(logits / temperature).  The draw runs
    only when some row has a temperature."""
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hot = temps > 0

    def draw():
        t = jnp.where(hot, temps, 1.0)[:, None]
        drawn = jax.random.categorical(sub, logits / t, axis=-1).astype(jnp.int32)
        return jnp.where(hot, drawn, greedy)

    return jax.lax.cond(jnp.any(hot), draw, lambda: greedy), key


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
