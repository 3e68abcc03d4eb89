"""A whole run on the CPU at a small size, past the harness's look for a
chip: it comes out correct as it is, and not correct with the served
tokens altered where the program produces them, or with a decode step
that returns its cache or state unchanged.  ``run.py`` itself
refuses a machine with no TPU, and a checkout with no program."""

import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from chipbench import harness, peaks, readings

from conftest import CHECKOUT, small_cell

WORKLOADS = ["granite-3-2b.decode_heavy", "granite-3-2b.prefill_heavy", "mamba2-2.7b.prefill_heavy"]


def run(cell, trace=False):
    return harness.run(cell, 2**33 + 5, 2.0, trace, time.time(), require_tpu=False)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    res = run(small_cell(workload))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks" and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_altered_token_is_not_correct(workload, monkeypatch):
    from repro.runtime import serve

    sample = serve.Server._sample

    def altered(self, logits, req):
        tok = sample(self, logits, req)
        # the third token of every request, moved to another id
        return (tok + 101) % 500 if len(req.out_tokens) == 2 else tok

    monkeypatch.setattr(serve.Server, "_sample", altered)
    res = run(small_cell(workload))
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > res["checks"]["logit_gap_max"]["limit"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_decode_that_keeps_its_state_is_not_correct(workload, monkeypatch):
    from repro.models import model as M

    step = M.LM.decode_step

    def stale(self, params, tokens, caches):
        logits, _ = step(self, params, tokens, caches)
        return logits, caches  # the cache or state it was given, unchanged

    monkeypatch.setattr(M.LM, "decode_step", stale)
    res = run(small_cell(workload))
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > res["checks"]["logit_gap_max"]["limit"]


def test_traced_run_reads_no_device_metric_off_the_chip(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    res = run(small_cell("granite-3-2b.decode_heavy"), trace=True)
    assert res["correct"] and res["metrics"] == {}  # no TPU planes in a CPU trace
    assert "busy_s" in res["device"] and "breakdown" in res


@pytest.mark.parametrize("workload", ["granite-3-2b.decode_heavy", "mamba2-2.7b.prefill_heavy"])
def test_control_reads_far_above_the_program(workload):
    cell = small_cell(workload, slots=2, max_seq=80, prompt_buckets=[32, 64],
                      output_buckets=[8, 16], check_requests=4)
    row = readings.read_seed(cell, 11, "fp8", require_tpu=False)
    assert row["control"] > 3 * row["program"] and row["control"] > 0.01
    assert row["program_correct"] and row["limit"] == cell.limits["logit_gap_max"]


def test_run_refuses_a_machine_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite-3-2b.decode_heavy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0 and "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite-3-2b.decode_heavy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_window_credits_the_step_cut_by_its_end_pro_rata(monkeypatch):
    clock = [0.0]
    monkeypatch.setattr(harness.time, "perf_counter", lambda: clock[0])

    class Srv:  # every step takes one second and serves one token a request
        compiles = {}

        def __init__(self):
            self.queue = []

        def submit(self, r):
            self.queue.append(r)

        def step(self):
            clock[0] += 1.0
            for r in self.queue:
                r.out_tokens.append(0)
                r.done = len(r.out_tokens) >= r.max_tokens

    class Model:
        def init_caches(self, *a, **k):
            return None

    s = harness.Setup([], None, Model(), types.SimpleNamespace(dtype=None), None, Srv())
    tr = {"slots": 2, "max_seq": 16, "prompt_buckets": [4], "output_buckets": [3]}
    win = harness.serve_window(s, tr, 1, 50, 2.5, 0, types.SimpleNamespace(count=0))
    # 2 x 4 prompt tokens and 2 served by the first step, 2 by the second,
    # and half of the third's 2, which ends half a second past the window
    assert win.tokens == 8 + 2 + 2 + 1
    assert [r.t_first for r in win.requests] == [1.0, 1.0]
    assert [r.t_done for r in win.requests] == [None, None]


def test_waves_cover_the_mix_in_balanced_rows():
    from chipbench import traffic

    tr = small_cell("granite-3-2b.decode_heavy", prompt_buckets=[8, 16, 24],
                    output_buckets=[4, 8, 12]).traffic
    order = traffic.cycle(tr)
    assert sorted(order) == sorted((p, o) for p in [8, 16, 24] for o in [4, 8, 12])
    for r in range(3):
        row = order[3 * r:3 * r + 3]
        assert sorted(p for p, _ in row) == [8, 16, 24] and sorted(o for _, o in row) == [4, 8, 12]
    a = [w for w, _ in zip(traffic.waves(tr, 2**40, 500), range(12))]
    b = [w for w, _ in zip(traffic.waves(tr, 2**40, 500), range(12))]
    c = [w for w, _ in zip(traffic.waves(tr, 7, 500), range(12))]
    assert [w[:2] for w in a] == order + order[:3] == [w[:2] for w in c]
    assert all(np.array_equal(x[2], y[2]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][2], c[0][2])
