"""The trace reducers, on a small recorded trace and on made-up events."""

import gzip
import json
import pathlib

import pytest

from chipbench import cells, flops, peaks
from chipbench import trace as T

DATA = pathlib.Path(__file__).parent / "data"


def recorded() -> T.Trace:
    """Four decode ticks of mamba2-2.7b (8 slots) on one TPU v5 lite."""
    with gzip.open(DATA / "decode_ticks.json.gz", "rt") as f:
        d = json.load(f)

    def ev(rows):
        return [(n, s * 1e-9, e * 1e-9) for n, s, e in rows]

    return T.Trace({0: ev(d["modules"])}, {0: ev(d["ops"])}, ev(d["spans"]))


def ctx(trace, **kw):
    base = {"trace": trace, "decode_kv": [], "prefill_plens": [], "slots": 8,
            "config": cells.load_cell("mamba2-2.7b.prefill_heavy").config,
            "peaks": peaks.peaks_for("TPU v5 lite")}
    base.update(kw)
    return base


def read(metric, c):
    return cells.metric_reader(metric).read(c)


def brute_idle(trace: T.Trace, step: float = 2e-6) -> float:
    """Idle share of the window by sampling it on a fine grid."""
    lo, hi = trace.window
    ops = [(s, e) for _, s, e in trace.ops[0]]
    n = int((hi - lo) / step)
    idle = 0
    j = 0
    ops.sort()
    active = []
    for i in range(n):
        t = lo + (i + 0.5) * step
        while j < len(ops) and ops[j][0] <= t:
            active.append(ops[j])
            j += 1
        active = [a for a in active if a[1] > t]
        idle += not active
    return 100.0 * idle / n


def test_recorded_decode_ticks():
    tr = recorded()
    runs = T.runs(tr, "jit_decode")
    assert len(runs) == 4  # one per tick, though the device clock leads the spans
    ms = read("decode_device_ms", ctx(tr))
    assert ms == pytest.approx(1e3 * sum(e - s for s, e in runs) / 4)
    assert 13.5 < ms < 14.5
    gap = read("host_gap_ms.decode", ctx(tr))
    assert 14.0 < gap < 17.0  # 8 slots sampled on the host between ticks
    idle = read("idle_share", ctx(tr))
    assert idle == pytest.approx(brute_idle(tr), abs=0.2)


def test_recorded_breakdown():
    b = T.breakdown(recorded())
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(k.startswith("jit_decode/") for k, _ in b["device_ops"])
    assert {name for name, _ in b["idle_gaps"]} <= set(T.SPANS) | {"harness"}


def test_decode_shares_match_the_formulas():
    tr = recorded()
    kv = [600, 601, 602, 603]
    c = ctx(tr, decode_kv=kv)
    dev = sum(e - s for s, e in T.runs(tr, "jit_decode"))
    pk = c["peaks"]
    least = sum(max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
                for f, b in (flops.decode(c["config"], 8, n) for n in kv))
    assert read("decode_roofline", c) == pytest.approx(100 * least / dev)
    assert 0 < read("decode_roofline", c) <= 100
    work = sum(flops.decode(c["config"], 8, n)[0] for n in kv)
    assert read("mfu.decode", c) == pytest.approx(100 * work / dev / pk["flops_bf16"])
    # a count of ticks that does not match the trace reads nothing
    assert read("decode_roofline", ctx(tr, decode_kv=kv[:3])) is None


def made_up() -> T.Trace:
    """Two prefills and three decodes; the host idles 2 ms between steps."""
    mods, ops, t = [], [], 0.0
    for name, dur in [("jit_prefill(1)", 0.100), ("jit_prefill(1)", 0.050),
                      ("jit_decode(2)", 0.010), ("jit_decode(2)", 0.010), ("jit_decode(2)", 0.010)]:
        mods.append((name, t, t + dur))
        ops.append(("%fusion.1", t, t + dur))
        t += dur + 0.002
    spans = [("step.admit", 0.0, 0.166), ("step.decode", 0.166, t)]
    return T.Trace({0: mods}, {0: ops}, spans)


def test_made_up_prefill_and_idle():
    tr = made_up()
    c = ctx(tr, prefill_plens=[512, 256])
    assert read("prefill_device_ms", c) == pytest.approx(75.0)
    want = (flops.prefill(c["config"], 512) + flops.prefill(c["config"], 256)) / 0.150
    assert read("mfu.prefill", c) == pytest.approx(100 * want / c["peaks"]["flops_bf16"])
    assert read("host_gap_ms.decode", c) == pytest.approx(2.0)
    lo, hi = tr.window
    assert read("idle_share", c) == pytest.approx(100 * (1 - 0.180 / (hi - lo)))
    assert read("mfu.prefill", ctx(tr, prefill_plens=[512])) is None


def test_empty_trace_reads_nothing():
    tr = T.Trace({}, {}, [("step.decode", 0.0, 1.0)])
    for m in ("host_gap_ms.decode", "decode_device_ms", "decode_roofline", "mfu.decode",
              "prefill_device_ms", "mfu.prefill", "idle_share"):
        assert read(m, ctx(tr)) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
