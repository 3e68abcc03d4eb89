"""``attribute.py``: the program's spans, scopes and counters read beside a
run, on made-up events and in a small run on the CPU."""

import pathlib
import time

import pytest

from chipbench import attribute as A
from chipbench import cells, harness, peaks
from chipbench import trace as T

from conftest import small_cell

DATA = pathlib.Path(__file__).parent / "data"


def made_up():
    """One admission (a 100 ms prefill of 4 rows for one request) and three
    decode ticks of 10 ms, each followed by 5 ms of host sampling."""
    mods, ops, prog = [], [], []
    prog.append(("serve.admit", 0.000, 0.120, {"admitted": 1}))
    prog.append(("serve.prefill", 0.001, 0.004, {"rid": 0, "slot": 0, "plen": 8, "rows": 4, "used": 1}))
    mods.append(("jit_prefill(1)", 0.004, 0.104))
    ops += [("%fusion.1 f32[4,8]", 0.004, 0.064, "jit(prefill)/while/body/attn/dot"),
            ("%fusion.2 bf16[4,8]", 0.064, 0.094, "jit(prefill)/while/body/mlp/dot"),
            ("%fusion.3 bf16[4,8]", 0.094, 0.104, "")]
    prog.append(("serve.splice", 0.104, 0.110, {"rid": 0, "slot": 0}))
    prog.append(("serve.sample", 0.110, 0.120, {"rid": 0, "slots": 1}))
    t = 0.120
    for tick in range(3):
        prog.append(("serve.decode", t, t + 0.001, {"tick": tick, "rows": 4, "used": 1}))
        mods.append(("jit_decode(2)", t + 0.001, t + 0.011))
        ops += [("%while s32[]", t + 0.001, t + 0.009, ""),  # a loop that holds the next two
                ("%fusion.4 f32[4,1]", t + 0.001, t + 0.005, "jit(decode)/while/body/ssm/scan/add"),
                ("%fusion.5 f32[4,1]", t + 0.005, t + 0.009, "jit(decode)/while/body/ssm/mul"),
                ("%fusion.6 f32[4,1]", t + 0.009, t + 0.011, "jit(decode)/unembed/dot")]
        prog.append(("serve.sample", t + 0.001, t + 0.016, {"tick": tick, "slots": 1}))
        t += 0.016
    prog.append(("gc", 0.125, 0.126, {"gen": 0}))
    harness_spans = [("step.admit", 0.0, 0.136), ("step.decode", 0.136, t)]
    trace = T.Trace({0: mods}, {0: [o[:3] for o in ops]}, harness_spans)
    return trace, sorted(prog, key=lambda s: (s[1], -s[2])), ops


def test_layer_of_a_scope_path():
    assert A.layer("jit(decode)/while/body/closed_call/attn/dot_general") == "attn"
    assert A.layer("jit(decode)/while/body/closed_call/ssm/scan/add") == "ssm/scan"
    assert A.layer("jit(decode)/while/body/closed_call/ssm/mul") == "ssm"
    assert A.layer("jit(prefill)/unembed/dot_general") == "unembed"
    assert A.layer("jit(decode)/while/body/dynamic_slice") == ""


def test_made_up_reductions():
    trace, prog, ops = made_up()
    r = A.reduce(trace, prog, ops)
    # each tick's sample span holds its 10 ms of decode and 5 ms of idle
    assert r["sample_idle_ms.decode"] == pytest.approx(5.0)
    # idle in the admission: 4 ms before the prefill, 16 ms after it
    assert r["admit_idle_ms"] == pytest.approx(20.0)
    assert r["prefill_pad_share"] == pytest.approx(75.0)
    assert r["decode_pad_share"] == pytest.approx(75.0)
    # the loop is a container: its 8 ms are not counted again
    assert r["mixer_device_ms.decode"] == pytest.approx(8.0)
    assert r["layer_ms.decode"] == pytest.approx({"ssm/scan": 4.0, "ssm": 4.0, "unembed": 2.0})
    assert r["mixer_device_ms.prefill"] == pytest.approx(60.0)
    assert r["unscoped_share.prefill"] == pytest.approx(10.0)
    assert r["unscoped_share.decode"] == pytest.approx(0.0)
    # the idle between ticks lies inside sample spans, save 1 ms of each
    # gap that is the next tick's decode dispatch (also a program span)
    assert r["decode_gap_cover"] == pytest.approx(100.0)
    # the admission's idle, span by span: 3 ms building the prefill, 6 in
    # the splice, 10 sampling its first token
    assert r["span_ms"]["serve.prefill"] == pytest.approx([1, 3.0, 3.0])
    assert r["span_ms"]["serve.splice"] == pytest.approx([1, 6.0, 6.0])
    assert r["span_ms"]["serve.sample.admit"] == pytest.approx([1, 10.0, 10.0])
    assert r["span_ms"]["serve.sample.tick"] == pytest.approx([3, 15.0, 5.0])
    # gaps are named by the innermost span at their middle, never the harness's
    names = [n for n, _ in r["idle_gaps"]]
    assert set(names) == {"serve.prefill", "serve.sample"}


def test_nothing_to_read_reads_none():
    trace = T.Trace({}, {}, [("step.decode", 0.0, 1.0)])
    r = A.reduce(trace, [], [])
    for key in ("sample_idle_ms.decode", "admit_idle_ms", "prefill_pad_share",
                "mixer_device_ms.decode", "mixer_device_ms.prefill", "decode_gap_cover"):
        assert r[key] is None


def test_busy_reads_as_the_trace_module():
    """``Busy`` reads what ``trace.idle_between`` and ``idle_gaps`` read, on
    recorded ticks."""
    from test_trace import recorded

    tr = recorded()
    busy = A.Busy(tr)
    lo, hi = tr.window
    for k in range(40):
        a = lo + (hi - lo) * k / 40
        b = a + (hi - lo) * (0.003 + 0.5 * (k % 7) / 7)
        assert busy.idle(a, b) == pytest.approx(T.idle_between(tr, a, b), abs=1e-12)
    assert busy.gaps(lo, hi) == pytest.approx(T.idle_gaps(tr))


def test_hlo_scopes_follow_calls_and_operands():
    text = """HloModule jit_decode
%fused_computation.16 (p: f32[2]) -> (f32[2], f32[2]) {
  %mul.1 = f32[2]{0} multiply(%p, %p), metadata={op_name="jit(decode)/while/body/attn/mul"}
  ROOT %tuple.2 = (f32[2]{0}, f32[2]{0}) tuple(%mul.1, %mul.1)
}
ENTRY %main (a: f32[2]) -> f32[2] {
  %a = f32[2]{0} parameter(0)
  %fusion.144 = (f32[2]{0}, f32[2]{0}) fusion(%a), kind=kLoop, calls=%fused_computation.16
  %gte.1 = f32[2]{0} get-tuple-element(%fusion.144), index=0
  %bitcast.3 = f32[2,1]{1,0} bitcast(%gte.1)
  ROOT %copy.5 = f32[2,1]{0,1} copy(%bitcast.3)
}
"""
    got = A.hlo_scopes(text)
    assert got["%fusion.144 (f32[2]"] == "jit(decode)/while/body/attn/mul"
    assert got["%copy.5 f32[2,1]"] == "jit(decode)/while/body/attn/mul"
    assert "%a f32[2]" not in got  # a parameter has no scope


def test_hlo_scopes_key_like_op_events():
    text = ('  %fusion.7 = bf16[16,256]{1,0:T(8,128)} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(decode)/attn/dot_general" source_file="x.py"}\n'
            '  ROOT %tuple.1 = (f32[2]{0}) tuple(%a)\n')
    assert A.hlo_scopes(text) == {"%fusion.7 bf16[16,256]": "jit(decode)/attn/dot_general"}
    event = "%fusion.7 = bf16[16,256]{1,0:T(8,128)} fusion(%p), kind=kLoop"
    assert T.op_name(event) in A.hlo_scopes(text)


def test_scoped_ops_take_each_executables_own_scope():
    """``%fusion.1 f32[4]`` is attention in the decode and the MLP in the
    prefill at one prompt length; each execution reads its own HLO."""
    def hlo(prog, rows):
        return "\n".join(f'  %{name} = {shape}{{0}} fusion(%p), kind=kLoop, '
                         f'metadata={{op_name="jit({prog})/{scope}/dot"}}'
                         for name, shape, scope in rows)

    decode = hlo("decode", [("fusion.1", "f32[4]", "attn"), ("fusion.2", "f32[4,1]", "mlp")])
    prefill = hlo("prefill", [("fusion.1", "f32[4]", "mlp"), ("fusion.3", "f32[4,8]", "attn")])
    mods = [("jit_prefill(7)", 0.0, 1.0), ("jit_decode(9)", 2.0, 3.0), ("jit_other(3)", 4.0, 5.0)]
    ops = [("%fusion.3 = f32[4,8]{1,0} fusion(%p)", 0.0, 0.5), ("%fusion.1 = f32[4]{0} fusion(%p)", 0.5, 1.0),
           ("%fusion.1 = f32[4]{0} fusion(%p)", 2.0, 2.5), ("%fusion.2 = f32[4,1]{1,0} fusion(%p)", 2.5, 3.0),
           ("%fusion.9 = f32[2]{0} fusion(%p)", 4.0, 5.0), ("%copy.1 = f32[2]{0} copy(%p)", 6.0, 7.0)]
    trace = T.Trace({0: mods}, {0: ops}, [("step.decode", 0.0, 7.0)])
    got = [(n, sc) for n, _, _, sc in A.scoped_ops(trace, [decode, prefill])]
    assert got == [("%fusion.3 f32[4,8]", "jit(prefill)/attn/dot"),
                   ("%fusion.1 f32[4]", "jit(prefill)/mlp/dot"),
                   ("%fusion.1 f32[4]", "jit(decode)/attn/dot"),
                   ("%fusion.2 f32[4,1]", "jit(decode)/mlp/dot"),
                   ("%fusion.9 f32[2]", ""),  # an executable of no text
                   ("%copy.1 f32[2]", "")]  # outside every execution


def test_first_waves_of_a_window():
    S = harness.Served
    reqs = [S(8, 3, None, [1, 2, 3], 0.0, 0.1, 0.3), S(8, 3, None, [1, 2, 3], 0.0, 0.2, 0.5),
            S(8, 5, None, [1] * 5, 1.0, 1.4, 1.8), S(8, 5, None, [1], 2.0, 2.1)]
    got = A.first_waves(reqs, 2)
    assert got["waves"] == 2 and got["seconds"] == pytest.approx(0.5 + 0.8)
    assert got["ttft_p90_ms"] == pytest.approx(harness.p90([100.0, 200.0, 400.0]))
    assert got["tpot_p90_ms"] == pytest.approx(harness.p90([100.0, 150.0, 100.0]))
    assert A.first_waves(reqs, 3) == {}  # the third wave was cut by the window


def test_small_run_records_counters_and_program_spans(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(harness, "serve_window", harness.serve_window)
    monkeypatch.setattr(T, "read", T.read)
    rec = A.Recorder(harness)
    cell = small_cell("granite-3-2b.decode_heavy")
    res = harness.run(cell, 2**33 + 5, 2.0, True, time.time(), require_tpu=False)
    assert res["correct"]
    c, slots = rec.counters, cell.traffic["slots"]
    assert c["prefill_calls"] > 0 and c["decode_ticks"] > 0
    assert c["decode_rows_used"] <= slots * c["decode_ticks"]
    assert c["step_max_s"] > 0 and c["step_max_tick"] >= 0
    assert rec.first_waves["waves"] == len(cell.traffic["prompt_buckets"])
    names = {n for n, *_ in rec.spans}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.splice", "serve.sample",
            "serve.decode"} <= names
    r = A.reduce(rec.trace, rec.spans, rec.ops)  # no device planes on the CPU
    assert r["prefill_pad_share"] == pytest.approx(100.0 * (slots - 1) / slots)
    assert r["mixer_device_ms.decode"] is None


def test_save_and_load(tmp_path):
    trace, prog, ops = made_up()
    rec = A.Recorder.__new__(A.Recorder)
    rec.trace, rec.spans, rec.ops = trace, prog, ops
    A.save(str(tmp_path / "ev.json.gz"), rec)
    got, want = A.reduce(*A.load(tmp_path / "ev.json.gz")), A.reduce(trace, prog, ops)
    for key in ("sample_idle_ms.decode", "admit_idle_ms", "prefill_pad_share",
                "mixer_device_ms.decode", "mixer_device_ms.prefill", "decode_gap_cover"):
        assert got[key] == pytest.approx(want[key], abs=1e-6)
    assert got["layer_ms.decode"] == pytest.approx(want["layer_ms.decode"])


def test_recorded_admission_and_ticks():
    """One admission of 16 requests and four decode ticks of
    granite-3-2b.decode_heavy on one TPU v5 lite, with the program's
    spans and each op's scope."""
    trace, prog, ops = A.load(DATA / "serve_admission.json.gz")
    prefills = [a for n, *_, a in prog if n == "serve.prefill"]
    assert len(prefills) == 16 and len({a["rid"] for a in prefills}) == 16
    ticks = [a["tick"] for n, *_, a in prog if n == "serve.sample" and "tick" in a]
    assert ticks == list(range(ticks[0], ticks[0] + 4))
    r = A.reduce(trace, prog, ops)
    assert r["prefill_pad_share"] == 93.75  # one row of 16 serves a request
    assert 25.0 < r["sample_idle_ms.decode"] < 35.0  # 16 slots sampled one by one
    assert 25.0 < r["admit_idle_ms"] < 40.0
    decode = cells.metric_reader("decode_device_ms").read({"trace": trace})
    prefill = cells.metric_reader("prefill_device_ms").read({"trace": trace})
    assert 19.0 < decode < 20.5
    # the float32 attention over the whole buffer (a multi-output fusion
    # with no metadata of its own) is 4.6 ms of the 10.8 under attn
    assert 10.0 < r["mixer_device_ms.decode"] < decode
    assert 5.0 < r["mixer_device_ms.prefill"] < prefill
    assert sum(r["layer_ms.decode"].values()) == pytest.approx(decode, rel=0.05)
    assert r["unscoped_share.decode"] < 1.0 and r["unscoped_share.prefill"] < 1.0
    assert r["decode_gap_cover"] >= 90.0
    assert {n for n, _ in r["idle_gaps"]} <= {n for n, *_ in prog}
