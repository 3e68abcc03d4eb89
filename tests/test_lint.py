"""Static linter: affine probing, rule predictions, prescreen, CLI gate.

The heart of this suite is the *static/dynamic agreement contract*:
every pattern class the linter predicts for a registry variant must
either be observed by the traced detectors on the same spec, or be a
documented static-only check (coverage gaps and spec bugs a trace
cannot show).  Under-prediction is always allowed — dynamic operands
are invisible to the static view by design.
"""

import json

import numpy as np
import pytest

from repro import kernels as kreg
from repro.cli import main as cli_main
from repro.core.advisor import advise_static
from repro.core.check import CheckError, check_static
from repro.core.collector import analyze, probe_affine_map
from repro.core.lint import (
    COVERAGE_GAP,
    DEAD_OPERAND,
    OUT_OF_BOUNDS,
    STATIC_ONLY_PATTERNS,
    lint_document,
    lint_ref,
    lint_spec,
    predicted_vs_observed,
    static_transactions,
)
from repro.core.patterns import (
    FALSE_SHARING,
    HOT,
    MISALIGNMENT,
    SCRATCH_ABUSE,
    STRIDED,
    detect_all,
)
from repro.core.session import ProfileSession
from repro.core.spec import KernelSpec, OperandSpec
from repro.core.trace import GridSampler
from repro.core.tuner import trajectories_from_session, tune

FULL = GridSampler(None)

#: Fully-static refs: the linter's transfer total must equal the traced
#: heat map's total bit-exactly (same walk arithmetic, no TraceBuffer).
FULLY_STATIC_REFS = (
    "gemm:v00",
    "gemm:v01",
    "gemm:v02",
    "histogram:partials",
    "histogram:scratch",
    "ttm:scratch",
    "ttm:fused",
    "cuszp:like",
    "flash:default",
    "gmm:default",
    "ssd:chunk",
)

#: Refs with dynamically-walked HBM operands: no static total exists.
DYNAMIC_REFS = (
    "spmv:csr",
    "spmv:zigzag",
    "histogram:naive",
    "gramschm:naive",
    "gramschm:opt",
)

#: Predicted classes the dynamic detectors never report for that ref,
#: with the reason they are static-only there.  Entries are either a
#: bare pattern (exempt on every region) or a (region, pattern) pair.
DOCUMENTED_STATIC_ONLY = {
    # gmm's expert-indexed W fetch only reaches the experts the ids hit;
    # the untouched remainder of the weight table is exactly what the
    # coverage-gap rule exists to show and what a trace cannot.
    "gmm:default": {COVERAGE_GAP},
    # the serving families' scalar-prefetch bounds (a handful of int32
    # words re-read by every grid program) are statically a textbook
    # redundant fetch, but the region is a single sector — below the
    # dynamic hot detector's multi-sector evidence threshold.
    "ragged_flash:decode": {("starts", HOT), ("ends", HOT)},
    "ragged_flash:decode-ragged": {("starts", HOT), ("ends", HOT)},
    "ragged_flash:prefill": {("starts", HOT), ("ends", HOT)},
    "ragged_flash:prefill-ragged": {("starts", HOT), ("ends", HOT)},
    "paged_attn:decode": {("context_lens", HOT)},
    "paged_attn:decode-paged": {("context_lens", HOT)},
    "paged_attn:prefill": {("context_lens", HOT)},
    "paged_attn:prefill-paged": {("context_lens", HOT)},
}


def _all_refs():
    return [
        f"{name}:{v.name}"
        for name in kreg.names()
        for v in kreg.get(name).variants
    ]


def _observe(ref):
    """Traced heat map + detected patterns for a registry ref."""
    entry, _variant = kreg.resolve(ref)
    spec, ctx = kreg.build(ref)
    hm = analyze(spec, entry.sampler(), ctx)
    return hm, detect_all(hm)


# -- the static transfer model is the collector's, exactly -------------------


@pytest.mark.parametrize("ref", FULLY_STATIC_REFS)
def test_static_transactions_equal_traced_total(ref):
    entry, _variant = kreg.resolve(ref)
    spec, ctx = kreg.build(ref)
    tx = static_transactions(spec, entry.sampler())
    assert tx is not None
    hm = analyze(spec, entry.sampler(), ctx)
    assert tx == hm.sector_transactions()


@pytest.mark.parametrize("ref", DYNAMIC_REFS)
def test_static_transactions_refuse_dynamic_specs(ref):
    entry, _variant = kreg.resolve(ref)
    spec, _ctx = kreg.build(ref)
    assert static_transactions(spec, entry.sampler()) is None
    # the lint report agrees and still gives per-operand verdicts
    rep = lint_ref(ref)
    assert rep.static_transactions is None
    assert any(ov.status == "dynamic" for ov in rep.operands)


def test_static_transactions_empty_grid_is_zero():
    spec = KernelSpec(
        name="k", grid=(0,),
        operands=(
            OperandSpec("x", (4096,), np.int32, (1024,), lambda i: (i,)),
        ),
    )
    assert static_transactions(spec, FULL) == 0


# -- static/dynamic agreement over the whole registry ------------------------


@pytest.mark.parametrize("ref", _all_refs())
def test_agreement_predictions_subset_of_observations(ref):
    rep = lint_ref(ref)
    _hm, observed = _observe(ref)
    obs_keys = {(r.region, r.pattern) for r in observed}
    allowed = DOCUMENTED_STATIC_ONLY.get(ref, set())
    for f in rep.findings:
        if f.pattern in STATIC_ONLY_PATTERNS or f.pattern in allowed \
                or (f.region, f.pattern) in allowed:
            continue
        assert (f.region, f.pattern) in obs_keys, (
            f"{ref}: lint predicted {f.pattern} on {f.region} "
            f"(rule {f.rule}) but the trace observed only {obs_keys}"
        )


# -- the known-bad variants are flagged with zero traces ---------------------


def test_known_bad_gemm_v00():
    rep = lint_ref("gemm:v00")
    keys = {(f.pattern, f.region) for f in rep.findings}
    assert (FALSE_SHARING, "A") in keys
    assert (FALSE_SHARING, "C") in keys
    assert (HOT, "B") in keys
    assert rep.verdict() == "dirty" and not rep.errors


def test_known_bad_spmv_misalignment():
    rep = lint_ref("spmv:csr")
    keys = {(f.pattern, f.region) for f in rep.findings}
    assert (MISALIGNMENT, "rowOffsets_shift1") in keys
    # the fixed variant drops the finding
    assert MISALIGNMENT not in lint_ref("spmv:zigzag").patterns()


def test_known_bad_scratch_abuse():
    assert (SCRATCH_ABUSE, "Y_shr") in {
        (f.pattern, f.region) for f in lint_ref("ttm:scratch").findings
    }
    assert SCRATCH_ABUSE in lint_ref("cuszp:like").patterns()
    # the fused fix and the genuinely-shared histogram scratch stay clean
    assert SCRATCH_ABUSE not in lint_ref("ttm:fused").patterns()
    assert SCRATCH_ABUSE not in lint_ref("histogram:scratch").patterns()


def test_strided_predicted_on_naive_column_walk():
    from repro.kernels.gramschm import k3_naive_block_spec

    rep = lint_spec(k3_naive_block_spec(512, 512, 512), sampler=FULL)
    assert STRIDED in rep.patterns()
    strided = [f for f in rep.findings if f.pattern == STRIDED]
    assert strided[0].region == "q"
    assert strided[0].rule == "lane-minor-stride"


def test_ladder_tops_stay_statically_dirty():
    """Regression: even the best ladder rungs keep their residual hot
    findings — the linter must not report them clean."""
    v02 = lint_ref("gemm:v02")
    assert v02.verdict() == "dirty"
    assert {f.region for f in v02.findings if f.pattern == HOT} == {
        "A", "B", "C",
    }
    flash = lint_ref("flash:default")
    assert flash.verdict() == "dirty"
    assert HOT in flash.patterns()


def test_lint_collects_zero_traces(monkeypatch):
    import repro.core.trace as trace_mod

    def boom(self, *a, **k):
        raise AssertionError("lint must never allocate a TraceBuffer")

    monkeypatch.setattr(trace_mod.TraceBuffer, "__init__", boom)
    rep = lint_ref("gemm:v00")
    assert rep.verdict() == "dirty"
    assert rep.static_transactions == 1064960


# -- affine probing ----------------------------------------------------------


def test_probe_affine_recovers_exact_model():
    model = probe_affine_map(lambda i, j: (2 * i + 3 * j + 1, j), (4, 5))
    assert model is not None
    assert model.base == (1, 0)
    for i in range(4):
        for j in range(5):
            assert model.predict((i, j)) == (2 * i + 3 * j + 1, j)


def test_probe_rejects_piecewise_map():
    # agrees with an affine model on a corner but not mid-grid
    assert probe_affine_map(lambda i: (0 if i < 5 else i,), (8,)) is None


def test_probe_rejects_multiplicative_map():
    assert probe_affine_map(lambda i, j: (i * j,), (4, 4)) is None


def test_nonaffine_operand_still_priced_exactly():
    rep = lint_ref("gmm:default")
    status = {ov.region: ov.status for ov in rep.operands}
    assert status["W"] == "nonaffine"
    modeled = {ov.region: ov.modeled_transactions for ov in rep.operands}
    # nonaffine != unpriced: the per-key replay still gives the total
    assert modeled["W"] is not None and modeled["W"] > 0
    assert rep.static_transactions == sum(
        ov.modeled_transactions
        for ov in rep.operands
        if ov.space == "hbm"
    )


# -- purely-static error rules ----------------------------------------------


def test_oob_origin_is_an_error():
    spec = KernelSpec(
        name="k", grid=(4,),
        operands=(
            OperandSpec("x", (4096,), np.int32, (1024,), lambda i: (i,),
                        origin=(0, 1024)),
        ),
    )
    rep = lint_spec(spec, sampler=FULL)
    assert rep.verdict() == "error"
    (err,) = rep.errors
    assert err.pattern == OUT_OF_BOUNDS and err.rule == "oob-origin"
    # errors gate the document even without --strict
    doc = lint_document([rep])
    assert doc["passed"] is False and doc["failures"]


def test_dead_operand_is_an_error():
    spec = KernelSpec(
        name="k", grid=(4,),
        operands=(
            OperandSpec("x", (4096,), np.int32, (1024,), lambda i: (i,),
                        origin=(0, 8192)),
        ),
    )
    rep = lint_spec(spec, sampler=FULL)
    assert DEAD_OPERAND in rep.patterns()
    assert rep.verdict() == "error"


def test_coverage_gap_on_gmm():
    rep = lint_ref("gmm:default")
    gaps = [f for f in rep.findings if f.pattern == COVERAGE_GAP]
    assert gaps and gaps[0].region == "W"
    assert gaps[0].level == "warning"  # reachable-but-wasteful, not a bug


# -- lint -> advisor (the shared Action surface) ------------------------------


def test_advise_static_prices_gemm_v00():
    acts = advise_static(lint_ref("gemm:v00"))
    assert acts[0].kind == "vmem_pin" and acts[0].region == "B"
    assert acts[0].est_transaction_saving > 0.9  # B is ~98% of traffic
    kinds = {(a.kind, a.region) for a in acts}
    assert ("retile", "A") in kinds and ("retile", "C") in kinds


def test_advise_static_drop_scratch():
    acts = advise_static(lint_ref("ttm:scratch"))
    assert acts[0].kind == "drop_scratch" and acts[0].region == "Y_shr"


# -- predicted vs observed cross-tab -----------------------------------------


def test_predicted_vs_observed_statuses():
    hm, observed = _observe("spmv:csr")
    rows = predicted_vs_observed(lint_ref("spmv:csr"), observed)
    by = {(r["region"], r["pattern"]): r["status"] for r in rows}
    assert by[("rowOffsets_shift1", MISALIGNMENT)] == "agree"
    # the dynamic x gather is invisible to the static view
    assert "dynamic-only" in set(by.values())
    agree = [r for r in rows if r["status"] == "agree"]
    assert all(
        r["predicted_severity"] is not None
        and r["observed_severity"] is not None
        for r in agree
    )


def test_predicted_vs_observed_static_only_gap():
    _hm, observed = _observe("gmm:default")
    rows = predicted_vs_observed(lint_ref("gmm:default"), observed)
    assert ("W", COVERAGE_GAP) in {
        (r["region"], r["pattern"])
        for r in rows
        if r["status"] == "static-only"
    }


# -- tuner pre-screen --------------------------------------------------------


def _step_sig(res):
    return [
        (s.candidate.label, s.accepted, s.transactions) for s in res.steps
    ]


def test_prescreen_preserves_gemm_trajectory():
    on = tune("gemm", budget=8, seed=0)
    off = tune("gemm", budget=8, seed=0, static_prescreen=False)
    # identical accepted trajectory, bit for bit
    assert _step_sig(on) == _step_sig(off)
    assert on.best_label == off.best_label
    # ...but the transpose counter-candidates were priced and skipped
    # statically, never traced
    labels = {d["label"] for d in on.static_skipped}
    assert labels == {"transpose(A)", "transpose(C)"}
    for d in on.static_skipped:
        assert d["static_transactions"] > d["parent_transactions"]
        assert d["candidate"]["source"] == "generated"
    assert not off.static_skipped
    assert "prescreen: 2 candidate(s) statically worse" in on.summary()
    doc = on.as_dict()
    json.dumps(doc)
    assert len(doc["static_skipped"]) == 2


def test_prescreen_skips_regressing_pin_on_gramschm():
    res = tune("gramschm", budget=2, seed=0)
    assert [s.candidate.label for s in res.steps] == ["ladder:opt"]
    assert [d["label"] for d in res.static_skipped] == ["pin(qT)"]
    assert res.improved and res.converged


def test_prescreen_session_provenance(tmp_path):
    sess = ProfileSession(tmp_path / "sess")
    res = sess.tune("histogram", budget=6, seed=0)
    # the partials ladder rung is statically worse than the naive
    # baseline: skipped at generation time, recorded in the iteration
    labels = [d["label"] for d in res.static_skipped]
    assert "ladder:partials" in labels
    (traj,) = trajectories_from_session(
        ProfileSession(tmp_path / "sess", create=False)
    )
    assert [d["label"] for d in traj["static_skipped"]] == labels
    # skips ride the iteration that triggered the regeneration
    per_step = [d["label"] for s in traj["steps"] for d in s["static_skipped"]]
    stored = json.loads(
        (sess.iteration(0).path / "manifest.json").read_text()
    )
    baseline_skips = [
        d["label"] for d in stored["tuning"].get("static_skipped", [])
    ]
    assert sorted(per_step + baseline_skips) == sorted(labels)


def test_prescreen_can_be_disabled_through_session(tmp_path):
    sess = ProfileSession(tmp_path / "sess")
    res = sess.tune("gramschm", budget=2, seed=0, static_prescreen=False)
    assert not res.static_skipped
    assert [s.candidate.label for s in res.steps] == ["ladder:opt", "pin(qT)"]


# -- static regression gate (check --static) ---------------------------------


def test_check_static_passes_down_ladder():
    rep = check_static("gemm:v01", "gemm:v00")
    assert rep.mode == "static" and rep.passed
    assert rep.kernels[0].transactions_after < rep.kernels[0].transactions_before


def test_check_static_fails_up_ladder():
    rep = check_static("gemm:v00", "gemm:v02")
    assert not rep.passed
    assert any("modeled transfers" in f for f in rep.failures)
    assert ("A", FALSE_SHARING) in rep.kernels[0].new_patterns


def test_check_static_applies_family_region_map():
    # gramschm's q -> qT rename must align, in either direction
    assert check_static("gramschm:opt", "gramschm:naive").passed
    doc = check_static("gramschm:opt", "gramschm:naive").as_dict()
    assert doc["format"] == "cuthermo-check" and doc["mode"] == "static"


def test_check_static_unknown_ref_raises():
    with pytest.raises(CheckError):
        check_static("nope:x", "gemm:v00")


# -- CLI contract ------------------------------------------------------------


def test_cli_lint_exit_codes(tmp_path, capsys):
    assert cli_main(["lint", "histogram:scratch"]) == 0  # clean
    assert cli_main(["lint", "gemm:v00"]) == 0  # warnings pass by default
    assert cli_main(["lint", "gemm:v00", "--strict"]) == 1
    assert cli_main(["lint", "definitely-not-a-kernel"]) == 2
    assert cli_main(["lint"]) == 2
    capsys.readouterr()


def test_cli_lint_json_document(tmp_path, capsys):
    path = tmp_path / "lint.json"
    rc = cli_main(
        ["lint", "gemm:v00", "--strict", "--json", str(path), "--quiet"]
    )
    assert rc == 1
    doc = json.loads(path.read_text())
    assert doc["format"] == "cuthermo-lint"
    assert doc["schema_version"] == 1
    assert doc["strict"] is True and doc["passed"] is False
    patterns = {
        f["pattern"] for rep in doc["reports"] for f in rep["findings"]
    }
    assert FALSE_SHARING in patterns
    capsys.readouterr()


def test_cli_lint_all_registry_passes(capsys):
    # the whole registry is warning-or-clean: default lint must exit 0
    assert cli_main(["lint", "--all", "--quiet"]) == 0
    capsys.readouterr()


def test_cli_kernels_lint_column(capsys):
    assert cli_main(["kernels", "--lint"]) == 0
    out = capsys.readouterr().out
    # every variant shows a verdict; known-dirty rungs read dirty
    assert "v00        dirty" in out
    assert "scratch    clean" in out  # histogram:scratch
    assert "hot(B)" in out and "scratch-abuse(Y_shr)" in out
    assert "no kernels were run or traced" in out


def test_cli_check_static_exit_codes(capsys):
    assert cli_main(
        ["check", "gemm:v01", "--static", "--baseline", "gemm:v00", "-q"]
    ) == 0
    assert cli_main(
        ["check", "gemm:v00", "--static", "--baseline", "gemm:v02", "-q"]
    ) == 1
    assert cli_main(
        ["check", "gemm:v00", "--static", "--baseline", "nope", "-q"]
    ) == 2
    # --static is ref-based: session-mode flags are usage errors
    assert cli_main(
        ["check", "gemm:v00", "--static", "--anomaly",
         "--baseline", "gemm:v01", "-q"]
    ) == 2
    assert cli_main(["check", "gemm:v00", "--static", "-q"]) == 2
    capsys.readouterr()


def test_cli_tune_no_prescreen_flag(tmp_path, capsys):
    rc = cli_main(
        ["tune", "gramschm", "--budget", "2",
         "--out", str(tmp_path / "s1")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "prescreen: pin(qT) statically worse" in out
    rc = cli_main(
        ["tune", "gramschm", "--budget", "2", "--no-prescreen",
         "--out", str(tmp_path / "s2")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "prescreen:" not in out
    assert "pin(qT)" in out  # actually profiled this time


# -- report bundle cross-tab -------------------------------------------------


def test_report_bundle_lint_section(tmp_path):
    from repro.core.render import ReportEntry, write_report_bundle

    hm, observed = _observe("gemm:v00")
    rep = lint_ref("gemm:v00")
    rows = predicted_vs_observed(rep, observed)
    assert any(r["status"] == "agree" for r in rows)
    payload = [
        {
            "kernel": "gemm",
            "ref": "gemm:v00",
            "verdict": rep.verdict(),
            "static_transactions": rep.static_transactions,
            "rows": rows,
        }
    ]
    written = write_report_bundle(
        [ReportEntry(heatmap=hm)], str(tmp_path / "rep"), lint=payload
    )
    html = open(written["index.html"]).read()
    assert "static lint: predicted vs observed" in html
    assert "agree" in html
    md = open(written["report.md"]).read()
    assert "## static lint: predicted vs observed" in md


def test_cli_report_includes_lint_crosstab(tmp_path, capsys):
    rc = cli_main(
        ["profile", "-k", "gemm:v00", "--out", str(tmp_path / "s"), "-q"]
    )
    assert rc == 0
    rc = cli_main(["report", str(tmp_path / "s")])
    assert rc == 0
    capsys.readouterr()
    md = (tmp_path / "s" / "iter0" / "report" / "report.md").read_text()
    assert "static lint: predicted vs observed" in md
    assert "false-sharing" in md


# -- the document ------------------------------------------------------------


def test_lint_document_versioned_and_strict():
    reps = [lint_ref("gemm:v00"), lint_ref("histogram:scratch")]
    doc = lint_document(reps)
    assert doc["format"] == "cuthermo-lint"
    assert doc["schema_version"] == 1
    assert doc["passed"] is True  # warnings only, not strict
    json.dumps(doc)
    strict = lint_document(reps, strict=True)
    assert strict["passed"] is False
    assert any("gemm:v00" in f for f in strict["failures"])
    assert not any("histogram:scratch" in f for f in strict["failures"])
