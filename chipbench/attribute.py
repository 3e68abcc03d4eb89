#!/usr/bin/env python3
"""One run of a cell, as ``run.py`` makes it, with the program's own
spans, scopes and counters read beside the benchmark's numbers.

    python3 chipbench/attribute.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--save FILE.json.gz]

``repro.runtime.Server`` writes host spans into the profiler's trace
(``serve.step``, ``serve.admit``, ``serve.prefill``, ``serve.splice``,
``serve.sample``, ``serve.decode``, ``gc``) and keeps ``counters``; the
model's steps carry named scopes (``embed``, ``stack``, ``attn``, ``mla``,
``ssm``, ``ssm/scan``, ``mlp``, ``moe``, ``unembed``).  ``run.py`` reads none of
them.  This tool runs the same ``harness.run`` and adds:

* always, on standard error before the ``check`` line, the window's
  deltas of ``Server.counters`` (collections and their seconds by
  generation, the slowest step and its tick), and TTFT, TPOT and the
  seconds of the window's first waves, those a traced run traces, to
  set a traced run against an untraced one of the same seed;
* with ``--trace 1``, the traced waves' program spans and each device
  operation's scope, read from the same ``.xplane.pb`` before the
  harness deletes it, reduced to the quantities of ``reduce()``;
  ``--save`` writes those events, gzipped, for reading later.

Its last line of standard output is one JSON object: the run's result
(as ``run.py`` prints it) under ``result``, and ``counters``,
``first_waves`` and, traced, ``program``.  The benchmark's runs do not
run this; its reductions are to move into ``chipbench/trace.py`` and
``chipbench/metrics/`` (ROADMAP.md), and this tool with them.
"""

from __future__ import annotations

import time

T0 = time.time()  # set-up is counted from here, as in run.py

import bisect  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional, Sequence, Tuple  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from chipbench import trace as T  # noqa: E402

Span = Tuple[str, float, float, Dict[str, int]]  # name, start, end, args
Op = Tuple[str, float, float, str]  # op_name(...), start, end, scope path

LAYERS = ("embed", "stack", "attn", "mla", "ssm", "mlp", "moe", "unembed")
MIXERS = ("attn", "mla", "ssm", "ssm/scan")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def is_program_span(name: str) -> bool:
    return name.startswith("serve.") or name == "gc"


# -- reading -------------------------------------------------------------------


def program_spans(pd) -> List[Span]:
    """The program's host spans with their integer args, by start (the
    device planes ``trace.from_profile`` reads)."""
    spans = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
              {k: v for k, v in e.stats if isinstance(v, int)})
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events if is_program_span(e.name)]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"(?<![\w=])%([\w.\-]+)")


def hlo_scopes(text: str) -> Dict[str, str]:
    """``%fusion.7 bf16[16,256]`` -> op_name path, from one executable's
    compiled HLO text (the key ``trace.op_name`` makes of an op event).
    An instruction the compiler made without metadata (a multi-output
    fusion, a layout copy, a bitcast) takes the scope of the computation
    it calls, else of its first operand that has one."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    body: Dict[str, List[str]] = {}  # computation -> its instructions
    keys: Dict[str, str] = {}
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            if line and not line[0].isspace() and "{" in line:
                comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            continue
        name, rhs = m.groups()
        meta = OP_NAME.search(rhs)
        if meta:
            own[name] = meta.group(1)
        c = _CALLS.search(rhs)
        if c:
            calls[name] = c.group(1)
        args = rhs.split(", metadata=")[0].split(", backend_config=")[0]
        operands[name] = [r for r in _REF.findall(args) if r != calls.get(name)]
        body.setdefault(comp, []).append(name)
        keys[name] = T.op_name(line.strip().removeprefix("ROOT "))

    memo: Dict[str, str] = {}

    def scope(name: str, depth: int = 0) -> str:
        if name in own or depth > 8:
            return own.get(name, "")
        if name not in memo:
            memo[name] = ""
            found = ""
            if name in calls:
                found = next((sc for sc in (scope(n, depth + 1)
                                            for n in reversed(body.get(calls[name], []))) if sc), "")
            for arg in operands.get(name, []):
                if found:
                    break
                found = scope(arg, depth + 1)
            memo[name] = found
        return memo[name]

    out: Dict[str, str] = {}
    for name, key in keys.items():
        sc = scope(name)
        if sc:
            out.setdefault(key, sc)
    return out


def owners(trace: T.Trace) -> List[Optional[str]]:
    """For each of device 0's operations, the execution event that holds
    it (``jit_prefill(<fingerprint>)``: one name per executable), or None."""
    mods = trace.modules.get(0, [])
    starts = [s for _, s, _ in mods]
    out: List[Optional[str]] = []
    for _, s, e in trace.ops.get(0, []):
        i = bisect.bisect_right(starts, s) - 1
        out.append(mods[i][0] if i >= 0 and mods[i][2] >= e else None)
    return out


def scoped_ops(trace: T.Trace, texts: Sequence[str]) -> List[Op]:
    """Device 0's operations with their scopes.  Each executable's
    operations are looked up in the one compiled HLO (of ``texts``) that
    holds most of their instruction keys, so that an instruction name and
    shape that two executables share (the decode and a prefill, or the
    prefill at two prompt lengths) takes its scope from its own."""
    tables = [hlo_scopes(t) for t in texts]
    ops = [(T.op_name(n), s, e) for n, s, e in trace.ops.get(0, [])]
    held = owners(trace)
    keys: Dict[str, set] = {}
    for (key, _, _), mod in zip(ops, held):
        if mod is not None:
            keys.setdefault(mod, set()).add(key)
    table: Dict[str, Dict[str, str]] = {}
    for mod, ks in keys.items():
        best = max(tables, key=lambda t: len(ks.intersection(t)), default={})
        table[mod] = best if ks.intersection(best) else {}
    return [(key, s, e, table.get(mod, {}).get(key, "") if mod else "")
            for (key, s, e), mod in zip(ops, held)]


# -- reductions ------------------------------------------------------------------


def layer(scope: str) -> str:
    """The innermost layer scope of an op_name path ('' for none):
    ``jit(decode)/while/body/closed_call/ssm/scan/add`` -> ``ssm/scan``."""
    parts = scope.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in LAYERS:
            if parts[i] == "ssm" and i + 1 < len(parts) - 1 and parts[i + 1] == "scan":
                return "ssm/scan"
            return parts[i]
    return ""


def leaf_ops(trace: T.Trace, ops: Sequence[Op], jit_name: str) -> List[Tuple[Op, int]]:
    """Operations inside executions of ``jit_name`` in the window, with
    the index of their execution; containers (a loop that holds later
    operations) are left out, as ``trace.breakdown`` leaves them."""
    mods = [(s, e) for n, s, e in trace.modules.get(0, [])
            if T.program(n) == jit_name and T.inside(s, e, trace.window)]
    starts = [s for s, _ in mods]
    out = []
    for k, op in enumerate(ops):
        _, s, e, _ = op
        if k + 1 < len(ops) and ops[k + 1][1] < e:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][1] >= e:
            out.append((op, i))
    return out


def layer_ms(trace: T.Trace, ops: Sequence[Op], jit_name: str) -> Dict[str, float]:
    """Device ms per execution of ``jit_name`` by layer ('' for no scope)."""
    n = len(T.runs(trace, jit_name))
    out: Dict[str, float] = {}
    if not n:
        return out
    for (_, s, e, scope), _ in leaf_ops(trace, ops, jit_name):
        key = layer(scope)
        out[key] = out.get(key, 0.0) + 1e3 * (e - s) / n
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def mixer_device_ms(trace: T.Trace, ops: Sequence[Op], jit_name: str) -> Optional[float]:
    split = layer_ms(trace, ops, jit_name)
    mixer = [v for k, v in split.items() if k in MIXERS]
    return sum(mixer) if mixer else None


def unscoped_share(trace: T.Trace, ops: Sequence[Op], jit_name: str) -> Optional[float]:
    """% of the step's leaf-op device time that carries no layer scope."""
    split = layer_ms(trace, ops, jit_name)
    total = sum(split.values())
    return 100.0 * split.get("", 0.0) / total if total else None


def in_window(trace: T.Trace, spans: Sequence[Span]) -> List[Span]:
    return [sp for sp in spans if T.inside(sp[1], sp[2], trace.window)]


class Busy:
    """Device 0's busy intervals in the window, merged once
    (``trace.busy_intervals``), for ``trace.idle_between`` over many
    short intervals: each reads only the busy intervals near it."""

    def __init__(self, trace: T.Trace):
        self.busy = T.busy_intervals(trace, 0)
        self.starts = [s for s, _ in self.busy]
        self.ends = [e for _, e in self.busy]

    def within(self, lo: float, hi: float) -> List[T.Interval]:
        i, j = bisect.bisect_right(self.ends, lo), bisect.bisect_left(self.starts, hi)
        return T.clip(self.busy[i:j], lo, hi)

    def idle(self, lo: float, hi: float) -> float:
        """``trace.idle_between(trace, lo, hi)``."""
        return (hi - lo) - sum(e - s for s, e in self.within(lo, hi))

    def gaps(self, lo: float, hi: float) -> List[T.Interval]:
        """The idle sub-intervals of [lo, hi], as ``trace.idle_gaps`` makes them."""
        edges = [lo] + [t for iv in self.within(lo, hi) for t in iv] + [hi]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def sample_idle_ms_decode(trace: T.Trace, spans: Sequence[Span],
                          busy: Optional[Busy] = None) -> Optional[float]:
    """Device-idle ms inside each decode tick's ``serve.sample`` span."""
    busy = busy or Busy(trace)
    ticks = [sp for sp in in_window(trace, spans) if sp[0] == "serve.sample" and "tick" in sp[3]]
    return 1e3 * T.mean([busy.idle(s, e) for _, s, e, _ in ticks]) if ticks else None


def admit_idle_ms(trace: T.Trace, spans: Sequence[Span],
                  busy: Optional[Busy] = None) -> Optional[float]:
    """Device-idle ms inside ``serve.admit`` spans, per admitted request."""
    busy = busy or Busy(trace)
    admits = [sp for sp in in_window(trace, spans) if sp[0] == "serve.admit"]
    n = sum(a.get("admitted", 0) for *_, a in admits)
    return 1e3 * sum(busy.idle(s, e) for _, s, e, _ in admits) / n if n else None


def pad_share(trace: T.Trace, spans: Sequence[Span], name: str) -> Optional[float]:
    """% of the rows that the ``name`` spans (``serve.prefill`` or
    ``serve.decode``) computed for no request: their args ``rows``, ``used``."""
    args = [a for n, *_, a in in_window(trace, spans) if n == name]
    rows = sum(a["rows"] for a in args)
    return 100.0 * sum(a["rows"] - a["used"] for a in args) / rows if rows else None


def span_at(spans: Sequence[Span], t: float) -> str:
    """The innermost program or harness span that holds ``t``."""
    held = [(e - s, n) for n, s, e, _ in spans if s <= t <= e]
    return min(held)[1] if held else "harness"


def named_gaps(trace: T.Trace, spans: Sequence[Span], top: int = 10) -> List[list]:
    """The longest idle gaps, named by the innermost span at their middle."""
    every = list(spans) + [(n, s, e, {}) for n, s, e in trace.spans]
    gaps = sorted(T.idle_gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return [[span_at(every, (s + e) / 2), e - s] for s, e in gaps]


def decode_gap_cover(trace: T.Trace, spans: Sequence[Span],
                     busy: Optional[Busy] = None) -> Optional[float]:
    """% of the device-idle time between back-to-back decode executions
    (what ``host_gap_ms.decode`` averages) that lies inside a program
    span other than ``serve.step``."""
    busy = busy or Busy(trace)
    inner = T.merge((s, e) for n, s, e, _ in spans if n != "serve.step")
    steps = [(s, e, T.program(n)) for n, s, e in trace.modules.get(0, [])
             if T.program(n) in ("jit_prefill", "jit_decode") and T.inside(s, e, trace.window)]
    total = covered = 0.0
    for a, b in zip(steps, steps[1:]):
        if a[2] != "jit_decode" or b[2] != "jit_decode" or b[0] < a[1]:
            continue
        for lo, hi in busy.gaps(a[1], b[0]):
            total += hi - lo
            covered += sum(e - s for s, e in T.clip(inner, lo, hi))
    return 100.0 * covered / total if total else None


def reduce(trace: T.Trace, spans: Sequence[Span], ops: Sequence[Op]) -> Dict[str, Any]:
    """What the program's spans and scopes say about a traced window."""
    busy = Busy(trace)
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for n, s, e, args in in_window(trace, spans):
        if n == "serve.sample":  # an admitted request's, or a tick's
            n += ".tick" if "tick" in args else ".admit"
        by_name.setdefault(n, []).append((e - s, busy.idle(s, e)))
    return {
        "sample_idle_ms.decode": sample_idle_ms_decode(trace, spans, busy),
        "admit_idle_ms": admit_idle_ms(trace, spans, busy),
        "prefill_pad_share": pad_share(trace, spans, "serve.prefill"),
        "decode_pad_share": pad_share(trace, spans, "serve.decode"),
        "mixer_device_ms.decode": mixer_device_ms(trace, ops, "jit_decode"),
        "mixer_device_ms.prefill": mixer_device_ms(trace, ops, "jit_prefill"),
        "unscoped_share.decode": unscoped_share(trace, ops, "jit_decode"),
        "unscoped_share.prefill": unscoped_share(trace, ops, "jit_prefill"),
        "layer_ms.decode": layer_ms(trace, ops, "jit_decode"),
        "layer_ms.prefill": layer_ms(trace, ops, "jit_prefill"),
        "decode_gap_cover": decode_gap_cover(trace, spans, busy),
        # per span kind: how many, and the mean ms each lasts and leaves the device idle
        "span_ms": {n: [len(v), 1e3 * sum(d for d, _ in v) / len(v), 1e3 * sum(i for _, i in v) / len(v)]
                    for n, v in sorted(by_name.items())},
        "idle_gaps": named_gaps(trace, spans),
    }


# -- the run ---------------------------------------------------------------------


def first_waves(requests, n: int) -> Dict[str, Any]:
    """TTFT and TPOT p90 and the seconds to serve the window's first ``n``
    waves (those a traced run traces), to set a traced run against an
    untraced one of the same seed."""
    from chipbench.harness import p90

    starts = sorted({r.t_wave for r in requests})[:n]
    reqs = [r for r in requests if r.t_wave in starts]
    if not reqs or any(r.t_done is None for r in reqs):
        return {}
    ends = {t: max(r.t_done for r in reqs if r.t_wave == t) for t in starts}
    return {"waves": len(starts), "seconds": sum(ends[t] - t for t in starts),
            "ttft_p90_ms": p90([1e3 * (r.t_first - r.t_wave) for r in reqs]),
            "tpot_p90_ms": p90([1e3 * (r.t_done - r.t_first) / (r.out_len - 1)
                                for r in reqs if r.out_len > 1])}


class Recorder:
    """Sees inside ``harness.run`` through the two calls it makes by
    module: ``harness.serve_window`` (the window's counter deltas, the
    first waves and, traced, the compiled HLO while the server lives) and
    ``trace.read`` (the program's spans of the same ``.xplane.pb``)."""

    def __init__(self, harness):
        self.counters: Dict[str, float] = {}
        self.first_waves: Dict[str, Any] = {}
        self.spans: List[Span] = []
        self.ops: List[Op] = []
        self.trace: Optional[T.Trace] = None
        self.log = harness.log
        self._serve_window = harness.serve_window
        harness.serve_window = self.serve_window
        T.read = self.read

    def read(self, log_dir):
        from jax.profiler import ProfileData

        files = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
        pd = ProfileData.from_file(str(files[-1]))
        self.spans = program_spans(pd)
        self.trace = T.from_profile(pd)
        return self.trace

    def serve_window(self, s, tr, *args, **kw):
        c = s.srv.counters
        c["step_max_s"], c["step_max_tick"] = 0.0, -1
        before = dict(c)
        win = self._serve_window(s, tr, *args, **kw)
        self.counters = {k: v - before[k] for k, v in c.items() if not k.startswith("step_max")}
        self.counters.update(step_max_s=c["step_max_s"], step_max_tick=c["step_max_tick"])
        self.first_waves = first_waves(win.requests, len(tr["prompt_buckets"]))
        self.log("[counters] " + json.dumps(self.counters))
        self.log("[first waves] " + json.dumps(self.first_waves))
        if self.trace is not None:
            self.ops = scoped_ops(self.trace, compiled_texts(s, tr))
        return win


def compiled_texts(s, tr) -> List[str]:
    """Compiled HLO of the window's decode and of its prefill at every
    prompt bucket (from the compilation cache), for the op scopes."""
    import jax
    import jax.numpy as jnp

    srv = s.srv
    b = tr["slots"]
    caches = s.model.init_caches(b, tr["max_seq"], dtype=s.pcfg.dtype, abstract=True)
    texts = [srv._decode.lower(srv.params, jax.ShapeDtypeStruct((b, 1), jnp.int32), caches)
             .compile().as_text()]
    for plen in tr["prompt_buckets"]:
        toks = jax.ShapeDtypeStruct((b, plen), jnp.int32)
        texts.append(srv._prefill_one.lower(srv.params, toks, caches).compile().as_text())
    return texts


def save(path: str, rec: Recorder) -> None:
    """The traced window's events, in ns, for reading later."""
    tr = rec.trace

    def ns(rows):
        return [[r[0], round(r[1] * 1e9), round(r[2] * 1e9), *r[3:]] for r in rows]

    scopes = sorted({o[3] for o in rec.ops})
    index = {sc: i for i, sc in enumerate(scopes)}
    data = {"modules": ns(tr.modules.get(0, [])), "scopes": scopes,
            "ops": ns([(n, s, e, index[sc]) for n, s, e, sc in rec.ops]),
            "spans": ns(tr.spans), "program": ns(rec.spans)}
    with gzip.open(path, "wt") as f:
        json.dump(data, f)


def load(path) -> Tuple[T.Trace, List[Span], List[Op]]:
    """What ``save`` wrote: the harness's trace, program spans, scoped ops."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)

    def sec(rows):
        return [(r[0], r[1] * 1e-9, r[2] * 1e-9, *r[3:]) for r in rows]

    ops = [(n, s, e, d["scopes"][i]) for n, s, e, i in sec(d["ops"])]
    trace = T.Trace({0: sec(d["modules"])}, {0: [o[:3] for o in ops]}, sec(d["spans"]))
    return trace, sec(d["program"]), ops


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CHECKOUT / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from chipbench import cells, harness

    rec = Recorder(harness)
    cell = cells.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    out: Dict[str, Any] = {"result": result, "counters": rec.counters,
                           "first_waves": rec.first_waves}
    if rec.trace is not None:
        out["program"] = reduce(rec.trace, rec.spans, rec.ops)
        if args.save:
            save(args.save, rec)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
