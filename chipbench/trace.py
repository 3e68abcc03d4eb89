"""Device activity and the benchmark's host spans, from a profiler trace.

A traced run records a few waves with ``jax.profiler`` and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``.  Each TPU device
plane has a line of program executions ("XLA Modules") and a line of
operations ("XLA Ops"); the host planes hold the spans the benchmark
writes with ``TraceAnnotation`` around each call into ``Server``.  Both
are on one clock.  Times here are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start, end

SPANS = ("reset", "step.admit", "step.decode")  # written by the harness
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    modules: Dict[int, List[Event]]  # per device, by start
    ops: Dict[int, List[Event]]
    spans: List[Event]  # the harness's host spans, by start

    @property
    def window(self) -> Interval:
        return self.spans[0][1], max(e for _, _, e in self.spans)


def options():
    """Profiler options: device and host activity, no Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _events(line) -> List[Event]:
    return sorted(((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9) for e in line.events),
                  key=lambda ev: ev[1])


def op_name(name: str) -> str:
    """``%fusion.7 = bf16[16,256]{1,0:T(8,128)} fusion(...)`` -> ``%fusion.7 bf16[16,256]``."""
    lhs, _, rhs = name.partition(" = ")
    return f"{lhs} {rhs.split('{', 1)[0]}".strip()


def from_profile(pd) -> Trace:
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = _events(line)
                elif line.name == "XLA Ops":
                    ops[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in line.events if e.name in SPANS]
    return Trace(modules, ops, sorted(spans, key=lambda s: s[1]))


def read(log_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


# -- interval arithmetic ----------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_intervals(trace: Trace, dev: int) -> List[Interval]:
    """When an operation ran on ``dev`` (executions where ops are absent)."""
    events = trace.ops.get(dev) or trace.modules.get(dev) or []
    return merge(clip([(s, e) for _, s, e in events], *trace.window))


def busy_seconds(trace: Trace) -> float:
    """Device-busy seconds in the window, averaged over the devices."""
    devs = sorted(set(trace.ops) | set(trace.modules))
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(trace, d)) for d in devs) / len(devs)


def idle_gaps(trace: Trace, dev: int = 0) -> List[Interval]:
    lo, hi = trace.window
    edges = [lo] + [t for iv in busy_intervals(trace, dev) for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


# -- programs ----------------------------------------------------------------


def program(name: str) -> str:
    """Base name of an execution event: ``jit_decode(12)`` -> ``jit_decode``."""
    return name.split("(", 1)[0].strip()


def inside(s: float, e: float, window: Interval) -> bool:
    """An event belongs to the window when its midpoint does: the device
    clock runs up to a millisecond off the host's spans."""
    return window[0] <= (s + e) / 2 <= window[1]


def runs(trace: Trace, jit_name: str, dev: int = 0) -> List[Interval]:
    """Executions of the program ``jit_name`` (e.g. ``jit_decode``) in the window."""
    return [(s, e) for n, s, e in trace.modules.get(dev, [])
            if program(n) == jit_name and inside(s, e, trace.window)]


def idle_between(trace: Trace, lo: float, hi: float, dev: int = 0) -> float:
    """Seconds of [lo, hi] in which no operation ran on ``dev``."""
    return (hi - lo) - sum(e - s for s, e in clip(busy_intervals(trace, dev), lo, hi))


def consecutive_gaps(trace: Trace, jit_name: str, dev: int = 0) -> List[float]:
    """Device-idle seconds between each two successive executions of
    ``jit_name`` that no other jitted step of the model separates (the
    small programs that the host loop dispatches in between are not
    steps; ``jit_prefill`` and ``jit_decode`` are)."""
    steps = [(s, e, program(n)) for n, s, e in trace.modules.get(dev, [])
             if program(n) in ("jit_prefill", "jit_decode") and inside(s, e, trace.window)]
    return [idle_between(trace, a[1], b[0], dev) for a, b in zip(steps, steps[1:])
            if a[2] == jit_name and b[2] == jit_name and b[0] >= a[1]]


def span_at(trace: Trace, t: float) -> str:
    """The harness span that holds time ``t`` (the innermost, latest)."""
    starts = [s for _, s, _ in trace.spans]
    i = bisect.bisect_right(starts, t)
    for name, s, e in reversed(trace.spans[max(0, i - 8):i]):
        if s <= t <= e:
            return name
    return "harness"


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List[list]]:
    """The operations that took most device time (by program/op), and the
    longest idle gaps named by the host span they fall in."""
    lo, hi = trace.window
    mods = trace.modules.get(0, [])
    starts = [s for _, s, _ in mods]
    ops = trace.ops.get(0, [])
    totals: Dict[str, float] = {}
    for k, (name, s, e) in enumerate(ops):
        if not inside(s, e, (lo, hi)) or (k + 1 < len(ops) and ops[k + 1][1] < e):
            continue  # outside, or a loop that holds the next operations
        i = bisect.bisect_right(starts, s) - 1
        owner = program(mods[i][0]) if i >= 0 and mods[i][2] >= e else "?"
        key = f"{owner}/{op_name(name)}"
        totals[key] = totals.get(key, 0.0) + (e - s)
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[span_at(trace, (s + e) / 2), e - s] for s, e in gaps],
    }


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
