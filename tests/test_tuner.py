"""Autotuner: action->candidate mapping, generated-spec surgery, the loop."""

import json

import numpy as np
import pytest

from repro.core.advisor import Action
from repro.core.collector import analyze
from repro.core.patterns import MISALIGNMENT, detect_all
from repro.core.session import ARTIFACT_VERSION, ProfileSession
from repro.core.spec import KernelSpec, OperandSpec
from repro.core.trace import GridSampler
from repro.core.tuner import (
    VMEM_PIN_BUDGET_BYTES,
    TuneError,
    align_spec,
    candidates_for_action,
    drop_scratch_spec,
    ladder_candidates,
    pin_spec,
    retile_spec,
    transpose_spec,
    tune,
    tune_all,
    trajectories_from_session,
)

FULL = GridSampler(None)


def _action(kind, region, pattern="hot", saving=0.5, params=()):
    return Action(
        kind=kind,
        region=region,
        pattern=pattern,
        description="synthetic",
        est_transaction_saving=saving,
        params=params,
    )


# -- every Action.kind produces at least one candidate -----------------------


@pytest.mark.parametrize(
    "kind,pattern,region,spec_fn",
    [
        ("retile", "false-sharing", "C",
         lambda: __import__("repro.kernels.gemm", fromlist=["x"])
         .gemm_v00_spec(256, 256, 256)),
        ("vmem_pin", "hot", "B",
         lambda: __import__("repro.kernels.gemm", fromlist=["x"])
         .gemm_v00_spec(256, 256, 256)),
        ("reorder_grid", "hot-random", "x",
         lambda: __import__("repro.kernels.spmv", fromlist=["x"])
         .spmv_csr_spec(8192, 4096)),
        ("pad_align", "misalignment", "rowOffsets_shift1",
         lambda: __import__("repro.kernels.spmv", fromlist=["x"])
         .spmv_csr_spec(8192, 4096)),
        ("drop_scratch", "scratch-abuse", "Y_shr",
         lambda: __import__("repro.kernels.ttm", fromlist=["x"])
         .ttm_scratch_spec(512, 8, 32)),
        ("transpose", "strided", "q",
         lambda: __import__("repro.kernels.gramschm", fromlist=["x"])
         .k3_naive_block_spec(512, 512, 512, k=3)),
        # 1-D data-dependent strided region: falls back to the pin/stage fix
        ("transpose", "strided", "q",
         lambda: __import__("repro.kernels.gramschm", fromlist=["x"])
         .k3_naive_spec(512, 512, 512, k=3)),
    ],
)
def test_every_action_kind_yields_a_candidate(kind, pattern, region, spec_fn):
    spec = spec_fn()
    cands = candidates_for_action(_action(kind, region, pattern), spec)
    assert cands, f"{kind} produced no candidate for {region}"
    for c in cands:
        built, _ctx = c.build()
        assert isinstance(built, KernelSpec)
        assert built.source is None  # generated specs are not registry refs
        # every generated candidate must actually be collectable
        hm = analyze(built, sampler=FULL)
        assert hm.sector_transactions() >= 0


def test_candidates_carry_action_provenance():
    from repro.kernels.gemm import gemm_v00_spec

    act = _action("retile", "C", "false-sharing", saving=0.9)
    (cand, *_rest) = candidates_for_action(act, gemm_v00_spec(256, 256, 256))
    prov = cand.provenance()
    json.dumps(prov)  # JSON-ready end to end
    assert prov["action"]["kind"] == "retile"
    assert prov["action"]["region"] == "C"
    assert prov["source"] == "generated"
    assert cand.predicted_saving == act.est_transaction_saving


# -- generated-spec surgery is exact ----------------------------------------


def test_retile_matches_handwritten_v01():
    """The generated retile of gemm v00 is the hand-written v01 fix."""
    from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec

    retiled = retile_spec(gemm_v00_spec(512, 512, 512), "C", 8)
    assert retiled is not None
    assert retiled.grid == (64,)
    hm_gen = analyze(retiled, sampler=FULL)
    hm_ref = analyze(gemm_v01_spec(512, 512, 512, bm=8), sampler=FULL)
    assert hm_gen.sector_transactions() == hm_ref.sector_transactions()
    for ra, rb in zip(hm_gen.regions, hm_ref.regions):
        assert np.array_equal(ra.tags_array, rb.tags_array)
        assert np.array_equal(ra.sector_temps_array, rb.sector_temps_array)


def test_retile_refuses_unknown_region_and_exotic_maps():
    from repro.kernels.gemm import gemm_v00_spec

    spec = gemm_v00_spec(256, 256, 256)
    assert retile_spec(spec, "nope", 8) is None
    assert retile_spec(spec, "C", 3) is None  # 256 % 3 != 0
    # a strided (non-identity) map cannot be certified -> refused
    import dataclasses

    strided = dataclasses.replace(
        spec,
        operands=tuple(
            dataclasses.replace(op, index_map=lambda i: (2 * i, 0))
            if op.name == "C"
            else op
            for op in spec.operands
        ),
    )
    assert retile_spec(strided, "C", 8) is None


def test_align_spec_fixes_misalignment():
    from repro.kernels.spmv import spmv_csr_spec

    spec = spmv_csr_spec(8192, 4096)
    before = analyze(spec, sampler=FULL)
    assert any(
        r.pattern == MISALIGNMENT and r.region == "rowOffsets_shift1"
        for r in detect_all(before)
    )
    aligned = align_spec(spec, "rowOffsets_shift1")
    assert aligned is not None
    after = analyze(aligned, sampler=FULL, dynamic_context=None)
    assert not any(
        r.pattern == MISALIGNMENT and r.region == "rowOffsets_shift1"
        for r in detect_all(after)
    )
    assert after.sector_transactions() < before.sector_transactions()
    # aligning an already-aligned region is not a candidate
    assert align_spec(spec, "rowOffsets") is None


def test_drop_scratch_removes_the_region():
    from repro.kernels.ttm import ttm_scratch_spec

    spec = ttm_scratch_spec(512, 8, 32)
    dropped = drop_scratch_spec(spec, "Y_shr")
    assert dropped is not None and dropped.scratch == ()
    hm = analyze(dropped, sampler=FULL)
    assert "Y_shr" not in hm.region_names()
    assert drop_scratch_spec(spec, "vals") is None  # not a scratch buffer


def test_pin_only_loads_within_vmem_budget():
    from repro.kernels.gemm import gemm_v00_spec

    spec = gemm_v00_spec(256, 256, 256)
    pinned = pin_spec(spec, "B")
    assert pinned is not None
    b = next(o for o in pinned.operands if o.name == "B")
    assert b.once
    hm = analyze(pinned, sampler=FULL)
    assert hm.sector_transactions() < analyze(
        spec, sampler=FULL
    ).sector_transactions()
    # stores are not pinnable: they must cross back to HBM
    assert pin_spec(spec, "C") is None
    # an operand bigger than VMEM is not pinnable
    n = int(np.sqrt(VMEM_PIN_BUDGET_BYTES / 4)) + 256
    big = KernelSpec(
        name="big",
        grid=(4,),
        operands=(
            OperandSpec("W", (n, n), np.float32, (n, n), lambda i: (0, 0)),
        ),
    )
    assert pin_spec(big, "W") is None


def test_transpose_turns_column_block_into_row_block():
    from repro.kernels.gramschm import k3_naive_block_spec

    spec = k3_naive_block_spec(512, 512, 512, k=3)
    t = transpose_spec(spec, "q")
    assert t is not None
    q = next(o for o in t.operands if o.name == "q")
    assert q.shape == (512, 512) and q.block_shape == (1, 512)
    before = analyze(spec, sampler=FULL)
    after = analyze(t, sampler=FULL)
    assert after.sector_transactions("q") < before.sector_transactions("q")


# -- ladder candidates round-trip through the registry -----------------------


def test_ladder_candidates_round_trip_kernels_build():
    from repro import kernels as kreg

    for name in kreg.names():
        entry = kreg.get(name)
        cands = ladder_candidates(entry, frozenset(), [], min_position=0)
        assert len(cands) == sum(
            1 for v in entry.variants if v.role == "optimized"
        )
        for c in cands:
            assert c.ref and c.source == "ladder"
            spec, _ctx = c.build()  # rebuilds through kernels.build
            spec2, _ = kreg.build(c.ref)
            from repro.core.collector import _spec_fingerprint

            assert _spec_fingerprint(spec) == _spec_fingerprint(spec2)
            assert spec.source == c.ref  # shard workers can rebuild it


def test_ladder_is_walked_forward():
    from repro import kernels as kreg

    entry = kreg.get("gemm")
    cands = ladder_candidates(entry, frozenset(), [], min_position=2)
    assert [c.variant for c in cands] == ["v02"]  # v01 is behind the floor


# -- the loop ----------------------------------------------------------------


def test_tune_closes_the_loop_on_gemm():
    res = tune("gemm", budget=4, seed=0)
    assert res.improved
    assert res.final.tx_after < res.final.tx_before
    assert res.fixed_patterns  # a fixed-pattern final verdict
    assert res.best.transactions == res.final.tx_after
    assert 1 <= len(res.steps) <= 4
    assert res.steps[0].candidate.label == "ladder:v01"  # ladder order
    json.dumps(res.as_dict())  # BENCH_tune.json row is JSON-ready
    assert "tune: gemm" in res.summary()


def test_tune_is_deterministic_under_a_fixed_seed():
    a = tune("gemm", budget=3, seed=123)
    b = tune("gemm", budget=3, seed=123)
    assert [s.candidate.label for s in a.steps] == [
        s.candidate.label for s in b.steps
    ]
    assert [s.accepted for s in a.steps] == [s.accepted for s in b.steps]
    assert [s.transactions for s in a.steps] == [
        s.transactions for s in b.steps
    ]
    assert a.ranked()[0].candidate.label == b.ranked()[0].candidate.label


def test_tune_budget_zero_returns_baseline():
    res = tune("gemm", budget=0)
    assert res.steps == ()
    assert res.best_label == "baseline"
    assert not res.improved and not res.converged


def test_tune_target_pattern_filters_actions():
    res = tune("gemm", budget=2, target_patterns=["false-sharing"])
    # the ladder fixes false sharing in one step; the hot-B pattern is
    # out of scope, so the run converges without chasing it
    assert res.improved and res.converged
    assert all(p == "false-sharing" for _r, p in res.fixed_patterns)


def test_tune_scratch_abuse_accepted_at_equal_traffic():
    # ttm's fix keeps HBM traffic identical; the tuner must still accept
    # it (pattern gone, scratch traffic gone) and report it as fixed
    res = tune("ttm", budget=2)
    assert not res.improved  # equal HBM transfers by design
    assert ("Y_shr", "scratch-abuse") in res.fixed_patterns
    assert res.best_label != "baseline"


def test_tune_unknown_kernel_raises():
    from repro.core.tuner import TuneError

    with pytest.raises(TuneError):
        tune("definitely-not-a-kernel")


# -- session persistence ------------------------------------------------------


def test_tune_persists_trajectory_with_provenance(tmp_path):
    sess = ProfileSession(tmp_path / "sess")
    res = sess.tune("gramschm", budget=2)
    names = sess.iteration_names()
    assert len(names) == 1 + len(res.steps)
    # baseline iteration carries step-0 provenance
    it0 = sess.iteration(0)
    assert it0.tuning["role"] == "baseline"
    assert it0.tuning["family"] == "gramschm"
    # candidate iterations record which Action spawned which candidate
    it1 = sess.iteration(1)
    assert it1.tuning["role"] == "candidate"
    cand = it1.tuning["candidate"]
    assert cand["label"] == res.steps[0].candidate.label
    assert cand["action"] is not None and "kind" in cand["action"]
    assert it1.tuning["verdict"] == res.steps[0].diff.verdict
    # the manifest stamps the current version and is JSON all the way down
    manifest = json.loads((it1.path / "manifest.json").read_text())
    assert manifest["version"] == ARTIFACT_VERSION == 6
    assert manifest["tuning"]["candidate"]["label"] == cand["label"]
    # a later process recovers the whole trajectory from disk alone
    (traj,) = trajectories_from_session(
        ProfileSession(tmp_path / "sess", create=False)
    )
    assert traj["kernel"] == "gramschm"
    assert traj["improved"] == res.improved
    assert traj["baseline"]["transactions"] == res.baseline.transactions
    assert traj["best"]["transactions"] == res.best.transactions
    assert len(traj["steps"]) == len(res.steps)


def test_retuning_same_family_yields_separate_trajectories(tmp_path):
    """Two tune runs into one session must not merge into one garbled
    trajectory: each run is keyed by its baseline iteration."""
    sess = ProfileSession(tmp_path / "sess")
    r1 = sess.tune("ttm", budget=1)
    r2 = sess.tune("ttm", budget=1)
    trajs = trajectories_from_session(
        ProfileSession(tmp_path / "sess", create=False)
    )
    assert len(trajs) == 2
    assert [t["kernel"] for t in trajs] == ["ttm", "ttm"]
    assert trajs[0]["run"] != trajs[1]["run"]
    for traj, res in zip(trajs, (r1, r2)):
        assert traj["candidates_tried"] == len(res.steps)
        assert traj["baseline"]["transactions"] == res.baseline.transactions
        assert traj["best"]["transactions"] == res.best.transactions
    # the best iteration link points at an accepted step (or baseline)
    assert trajs[0]["best"]["iteration"] in {
        s["iteration"] for s in trajs[0]["steps"] if s["accepted"]
    } | {trajs[0]["baseline"]["iteration"]}


def test_classify_rejects_prefix_identity_maps():
    """A map that is identity only on a prefix must not certify."""
    import dataclasses

    from repro.kernels.gemm import gemm_v00_spec

    spec = gemm_v00_spec(256, 256, 256)
    piecewise = dataclasses.replace(
        spec,
        operands=tuple(
            dataclasses.replace(op, index_map=lambda i: (min(int(i), 7), 0))
            if op.name == "C"
            else op
            for op in spec.operands
        ),
    )
    assert retile_spec(piecewise, "C", 8) is None


def test_non_tuned_iterations_have_no_tuning(tmp_path):
    from repro.kernels.gemm import gemm_v00_spec

    sess = ProfileSession(tmp_path / "sess")
    it = sess.profile([gemm_v00_spec(128, 128, 128)])
    assert it.tuning is None
    assert trajectories_from_session(sess) == []


# -- the concurrent tune scheduler -------------------------------------------


def test_tune_all_single_family_matches_serial():
    """With one family the scheduler degenerates to the serial loop."""
    serial = tune("gramschm", budget=3, seed=7)
    sched = tune_all(["gramschm"], budget=3, seed=7)
    (res,) = sched.results
    assert [s.candidate.label for s in res.steps] == [
        s.candidate.label for s in serial.steps
    ]
    assert [s.accepted for s in res.steps] == [
        s.accepted for s in serial.steps
    ]
    assert res.best_label == serial.best_label
    assert res.best.transactions == serial.best.transactions


def test_tune_all_matches_serial_when_budget_ample():
    """Ordered result commitment: each family's trajectory is the one
    serial ``tune`` produces, as long as the global budget never cuts a
    family short (both converge well under 10 candidates)."""
    sched = tune_all(["gramschm", "ttm"], budget=10, seed=0)
    for res in sched.results:
        assert res.converged
        serial = tune(res.kernel, budget=10, seed=0)
        assert [s.candidate.label for s in res.steps] == [
            s.candidate.label for s in serial.steps
        ]
        assert res.best.transactions == serial.best.transactions


def test_tune_all_is_deterministic_per_seed():
    a = tune_all(["gramschm", "ttm"], budget=4, seed=42)
    b = tune_all(["gramschm", "ttm"], budget=4, seed=42)
    sig = lambda r: [  # noqa: E731
        (s.candidate.label, s.accepted, s.transactions) for s in r.steps
    ]
    assert [sig(r) for r in a.results] == [sig(r) for r in b.results]
    assert a.spent == b.spent and a.rounds == b.rounds


def test_tune_all_enforces_one_global_budget():
    """budget=2 across two families: one candidate each (round-robin in
    family order), baselines excluded from the count."""
    res = tune_all(["gramschm", "ttm"], budget=2, seed=0)
    assert res.spent == 2
    assert [len(r.steps) for r in res.results] == [1, 1]
    assert res.rounds == 1


def test_tune_all_budget_zero_profiles_baselines_only():
    res = tune_all(["gramschm", "ttm"], budget=0, seed=0)
    assert res.spent == 0
    assert all(not r.steps for r in res.results)
    assert all(r.best_label == "baseline" for r in res.results)


def test_tune_all_empty_family_list_raises():
    with pytest.raises(TuneError):
        tune_all([], budget=2)


def test_tune_all_persists_linked_provenance(tmp_path):
    """Session iterations commit in family order with baseline links,
    and every step records the iteration that stored it."""
    sess = ProfileSession(tmp_path / "sess")
    res = tune_all(["gramschm", "ttm"], budget=2, seed=0, session=sess)
    # 2 baselines + 2 candidates, committed deterministically
    assert sess.iteration_names() == ["iter0", "iter1", "iter2", "iter3"]
    assert sess.iteration(0).tuning["family"] == "gramschm"
    assert sess.iteration(1).tuning["family"] == "ttm"
    for r in res.results:
        assert r.baseline_iteration
        for s in r.steps:
            assert s.iteration  # the satellite fix: never ""
            it = sess.iteration(sess.iteration_names().index(s.iteration))
            assert it.tuning["baseline"] == r.baseline_iteration
            assert it.tuning["candidate"]["label"] == s.candidate.label
    trajs = trajectories_from_session(
        ProfileSession(tmp_path / "sess", create=False)
    )
    assert sorted(t["kernel"] for t in trajs) == ["gramschm", "ttm"]
    for traj in trajs:
        assert all(s["iteration"] for s in traj["steps"])


def test_tune_all_shared_cache_bounds_fresh_traces(tmp_path):
    """A repeated tune --all run re-traces nothing: every profile
    (baselines included) is served from the shared cache."""
    from repro.core.cache import CollectionCache

    cache = CollectionCache(tmp_path / "cache")
    tune_all(["gramschm", "ttm"], budget=2, seed=0, cache=cache)
    before_miss = cache.stats.misses
    res = tune_all(["gramschm", "ttm"], budget=2, seed=0, cache=cache)
    fresh = cache.stats.misses - before_miss
    profiles = res.spent + len(res.results)  # candidates + baselines
    assert fresh == 0
    assert cache.stats.hits >= profiles
