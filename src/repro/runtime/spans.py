"""Host spans and garbage-collection counts in the profiler's trace.

``span(name, **args)`` writes a named host span into the trace that
``jax.profiler`` records, on the clock its device planes use.  Its
integer arguments become the event's stats in the ``.xplane.pb``, so
the spans of one request can share its ``rid``.  A span records only
while the profiler is tracing; when it is not, one costs about a
microsecond.

``watch_gc()`` adds a ``gc`` span (``gen=``) around every collection of
the process and counts collections and their seconds per generation in
``GC``.  It is idempotent.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional, Tuple

import jax

GC: Dict[str, List[float]] = {"collections": [0, 0, 0], "seconds": [0.0, 0.0, 0.0]}
_running: Optional[Tuple[jax.profiler.TraceAnnotation, float]] = None


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` with ``args`` while tracing."""
    return jax.profiler.TraceAnnotation(name, **args)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _running
    if phase == "start":
        s = span("gc", gen=info["generation"])
        s.__enter__()
        _running = (s, time.perf_counter())
    elif _running is not None:  # collections do not nest
        s, t0 = _running
        _running = None
        s.__exit__(None, None, None)
        gen = info["generation"]
        GC["collections"][gen] += 1
        GC["seconds"][gen] += time.perf_counter() - t0


def watch_gc() -> None:
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_counters() -> Dict[str, float]:
    """``gc_collections.<gen>`` and ``gc_s.<gen>`` since ``watch_gc()``."""
    out: Dict[str, float] = {}
    for gen in range(3):
        out[f"gc_collections.{gen}"] = GC["collections"][gen]
        out[f"gc_s.{gen}"] = GC["seconds"][gen]
    return out
