"""Fault-tolerance primitives for the profiling pipeline.

The collect->cache->session pipeline must survive the failures a
long-running profiling service actually sees: worker processes dying
mid-shard, shards hanging on a wedged host, corrupted cache files, and
SIGTERM landing in the middle of an artifact commit.  This module holds
the two pieces every layer shares:

* :class:`FaultEvent` — one structured record per recovery action.
  Events are provenance, exactly like :class:`~repro.core.trace.ShardInfo`:
  they ride on the heat map (``Heatmap.faults``), are persisted into the
  v6 artifact manifest, and are deliberately excluded from heat-map
  equality — a recovered collection IS the clean collection, produced
  the hard way.  The set-union merge algebra guarantees that (a
  re-executed shard contributes the same key sets, and unions are
  idempotent), which ``tests/test_resilience.py`` pins.
* :class:`ResiliencePolicy` — the knobs of the recovery loop in
  :class:`~repro.core.collector.ShardedCollector`: per-shard retry
  attempts and backoff, the per-round hang watchdog, how many broken
  pools to tolerate before degrading to serial collection, and how
  finely a hung shard is re-split for its in-process re-run.

The injection side (deterministically *causing* these faults) lives in
:mod:`repro.core.faultinject`; the generic retry/preemption primitives
in :mod:`repro.fault`.  See ``docs/robustness.md`` for the full
fault model.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

#: Event kinds the recovery machinery emits.  Closed set so downstream
#: consumers (render sections, the chaos CI assertions) can match on
#: them without scraping detail strings.
FAULT_KINDS = (
    "worker-crash",      # a pool worker died; its round's shards re-ran
    "shard-timeout",     # the watchdog expired a hung shard
    "shard-retry",       # a shard failed cleanly and was resubmitted
    "pool-rebuild",      # the broken process pool was torn down and respun
    "shard-resplit",     # a hung shard re-ran in-process as smaller runs
    "serial-fallback",   # pool gave up; remaining shards ran serially
    "cache-corrupt",     # a defective disk cache entry was quarantined
    "torn-iteration",    # a half-written iteration was found on load
    "candidate-failure", # a tuner candidate's profile failed; run continued
)


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One structured recovery event (artifact provenance, not an error).

    ``kind`` is one of :data:`FAULT_KINDS`; ``where`` names the pipeline
    layer that recovered (``collector``/``cache``/``session``/``tuner``);
    ``shard`` is the affected shard id (``-1`` when the event is not
    shard-scoped); ``attempt`` counts delivery attempts of that shard at
    the time of the event (0-based); ``wall_s`` is time lost to the
    fault where measurable; ``detail`` is a short human-readable note.
    """

    kind: str
    where: str = "collector"
    shard: int = -1
    attempt: int = 0
    wall_s: float = 0.0
    detail: str = ""

    def as_dict(self) -> dict:
        """JSON-ready form (v6 manifests, report bundles)."""
        return {
            "kind": self.kind,
            "where": self.where,
            "shard": self.shard,
            "attempt": self.attempt,
            "wall_s": self.wall_s,
            "detail": self.detail,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FaultEvent":
        """Inverse of :meth:`as_dict` (artifact loaders)."""
        return cls(
            kind=str(d["kind"]),
            where=str(d.get("where", "collector")),
            shard=int(d.get("shard", -1)),
            attempt=int(d.get("attempt", 0)),
            wall_s=float(d.get("wall_s", 0.0)),
            detail=str(d.get("detail", "")),
        )


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Knobs of the sharded collector's recovery loop.

    ``attempts``       per-shard delivery attempts (including the first)
                       before a clean shard failure is re-raised.
    ``base_delay``     exponential-backoff base between retries, seconds
                       (attempt ``n`` sleeps ``base_delay * 2**(n-1)``).
    ``shard_timeout_s``  per-round hang watchdog: shards still running
                       this long after their round started are declared
                       hung, their workers killed, and the shard re-run
                       in process.  ``None`` disables the watchdog.
    ``max_pool_failures``  consecutive broken-pool rounds tolerated
                       before the collector degrades to serial
                       collection of everything still outstanding.
    ``resplit``        how many smaller contiguous pid runs a hung
                       shard's in-process re-run is split into (``1`` =
                       re-run whole).  Sub-runs keep the shard's id and
                       still partition its ``[lo, hi)``, so the merge
                       algebra is unaffected.
    """

    attempts: int = 3
    base_delay: float = 0.05
    shard_timeout_s: float = 300.0
    max_pool_failures: int = 2
    resplit: int = 2

    def backoff_s(self, attempt: int) -> float:
        """Backoff before delivery attempt ``attempt`` (1-based retries)."""
        return float(self.base_delay) * (2 ** max(0, int(attempt) - 1))


#: The default policy.  Conservative enough for CI boxes (a full-grid
#: production GEMM shard collects in well under a minute); fault
#: injection swaps in a tighter one (`FaultPlan.policy`).
DEFAULT_POLICY = ResiliencePolicy()


def summarize_faults(events: Tuple[FaultEvent, ...]) -> str:
    """One-line digest of a fault-event sequence (CLI/report surfaces)."""
    if not events:
        return "no faults"
    counts: dict = {}
    for e in events:
        counts[e.kind] = counts.get(e.kind, 0) + 1
    return ", ".join(f"{k} x{v}" for k, v in sorted(counts.items()))


__all__ = [
    "DEFAULT_POLICY",
    "FAULT_KINDS",
    "FaultEvent",
    "ResiliencePolicy",
    "summarize_faults",
]
