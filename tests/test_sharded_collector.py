"""ShardedCollector mechanics: partition math, shard provenance, token
unification, chunk consolidation, drop accounting, and the spawn pool.

Bit-identity of sharded vs serial heat maps is pinned (for every
collector path) in ``tests/test_golden_equivalence.py``; this module
covers the machinery around it.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.collector import (
    ShardedCollector,
    _unify_shard_groups,
    analyze,
    collect,
    collect_shard,
    shard_bounds,
    sourced_spec,
)
from repro.core.heatmap import Analyzer, HeatKeys
from repro.core.session import heatmaps_equal
from repro.core.trace import GridSampler, ShardInfo


# -- partition math ----------------------------------------------------------


def test_shard_bounds_partition_exactly():
    for total in (0, 1, 2, 7, 128, 1000):
        for shards in (1, 2, 3, 8, 64):
            bounds = shard_bounds(total, shards)
            # contiguous, ordered, covering [0, total) exactly once
            assert bounds[0][0] == 0
            assert bounds[-1][1] == total
            for (lo, hi), (lo2, _) in zip(bounds, bounds[1:]):
                assert hi == lo2
            # never more shards than programs (no empty shards), except
            # the degenerate empty grid which keeps one empty shard
            if total > 0:
                assert len(bounds) == min(shards, total)
                assert all(hi > lo for lo, hi in bounds)
            else:
                assert bounds == [(0, 0)]


def test_shard_bounds_near_equal():
    bounds = shard_bounds(10, 3)
    sizes = [hi - lo for lo, hi in bounds]
    assert sum(sizes) == 10 and max(sizes) - min(sizes) <= 1


# -- shard collection & provenance ------------------------------------------


def _spec():
    from repro.kernels.gemm import gemm_v00_spec

    return gemm_v00_spec(128, 128, 128)


def test_collect_shard_provenance_and_stamps():
    spec = _spec()
    buf, info = collect_shard(spec, GridSampler(None), None, 32, 96, 5)
    assert info == ShardInfo(
        shard=5, lo=32, hi=96, programs=64, records=len(buf),
        dropped=0, wall_s=info.wall_s,
    )
    assert info.wall_s > 0
    assert all(c.shard == 5 for c in buf.chunks)
    # the shard walked exactly its slice of the sampled grid
    pids = np.concatenate([c.pids for c in buf.chunks])
    assert pids.min() >= 32 and pids.max() < 96


def test_shard_info_dict_roundtrip():
    info = ShardInfo(shard=1, lo=0, hi=8, programs=8, records=24,
                     dropped=2, wall_s=0.5)
    assert ShardInfo.from_dict(info.as_dict()) == info


def test_once_operand_owned_by_first_shard_only():
    """once= operands are emitted by the lo==0 shard alone."""
    from repro.kernels.histogram import hist_opt2_spec

    spec = hist_opt2_spec(16384, 512)
    once_names = {op.name for op in spec.operands if op.once}
    assert once_names  # the case study actually has one
    b0, _ = collect_shard(spec, GridSampler(None), None, 0, 8, 0)
    b1, _ = collect_shard(spec, GridSampler(None), None, 8, 16, 1)
    sites0 = {c.site.array for c in b0.chunks}
    sites1 = {c.site.array for c in b1.chunks}
    assert once_names <= sites0
    assert not (once_names & sites1)


def test_unify_shard_groups_one_token_per_site():
    spec = _spec()
    b0, _ = collect_shard(spec, GridSampler(None), None, 0, 64, 0)
    b1, _ = collect_shard(spec, GridSampler(None), None, 64, 128, 1)
    _unify_shard_groups([b0, b1])
    by_site = {}
    for buf in (b0, b1):
        for c in buf.chunks:
            by_site.setdefault(c.site, set()).add(c.group)
    for site, groups in by_site.items():
        assert len(groups) == 1, site
    # distinct sites got distinct tokens
    tokens = [next(iter(g)) for g in by_site.values()]
    assert len(set(tokens)) == len(tokens)


# -- chunk consolidation -----------------------------------------------------


def test_consolidate_is_exact_and_compacts():
    spec = _spec()  # one broadcast chunk per grid row: 128+1+128 chunks
    buf, _ = collect(spec, GridSampler(None))
    n_before = len(buf.chunks)
    records_before = len(buf)
    hm_before = _flush(spec, buf)
    buf.consolidate()
    assert len(buf.chunks) < n_before
    assert len(buf) == records_before
    assert heatmaps_equal(_flush(spec, buf), hm_before)


def test_consolidate_skips_record_heavy_broadcast():
    """Broadcast chunks with many records per touch set (e.g. B read by
    every program) must NOT be expanded into CSR."""
    spec = _spec()
    buf, _ = collect(spec, GridSampler(None))
    b_chunks = [c for c in buf.chunks if c.site.array == "B"]
    assert len(b_chunks) == 1 and b_chunks[0].n_records == 128
    buf.consolidate()
    b_after = [c for c in buf.chunks if c.site.array == "B"]
    assert len(b_after) == 1 and b_after[0].ptr is None  # still broadcast


def _flush(spec, buf):
    an = Analyzer(spec.name, spec.grid, "full-grid")
    an.ingest(buf)
    return an.flush()


# -- drop accounting across shards ------------------------------------------


def test_drop_accounting_sums_exactly_across_shards():
    spec = _spec()
    with ShardedCollector(4, max_records=40) as sc:
        spec_local = dataclasses.replace(spec, source=None)
        bufs, infos = sc.collect(spec_local, GridSampler(None))
    assert sum(i.dropped for i in infos) == sum(b.dropped for b in bufs)
    assert any(i.dropped for i in infos)
    # the GLOBAL cap holds: shards share the serial budget, not N of it
    assert sum(i.records for i in infos) <= 40
    # serial admits the same total and drops the same total (the
    # *specific* surviving records may differ under truncation)
    serial_buf, _ = collect(spec_local, GridSampler(None), max_records=40)
    assert sum(i.records for i in infos) == len(serial_buf)
    assert sum(i.dropped for i in infos) == serial_buf.dropped
    an = Analyzer(spec.name, spec.grid, "full-grid")
    for b in bufs:
        an.ingest(b)
        an.ingest(b)  # re-ingest must not double-count shard drops
    hm = an.flush()
    assert hm.dropped == sum(i.dropped for i in infos)


def test_truncated_sharded_analyze_warns():
    spec = dataclasses.replace(_spec(), source=None)
    with ShardedCollector(2, max_records=40) as sc:
        with pytest.warns(RuntimeWarning, match="not bit-identical"):
            hm = sc.analyze(spec, GridSampler(None))
    assert hm.dropped > 0 and hm.n_records <= 40


# -- merge algebra guard rails ----------------------------------------------


def test_heatmap_merge_rejects_mismatched_launches():
    from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec

    a = analyze(gemm_v00_spec(128, 128, 128), GridSampler(None))
    b = analyze(gemm_v01_spec(128, 128, 128), GridSampler(None))
    with pytest.raises(ValueError, match="different launches"):
        a.merge(b)


def test_region_merge_requires_key_state():
    spec = _spec()
    hm = analyze(spec, GridSampler(None))  # flushed without keys
    with pytest.raises(ValueError, match="key-set state"):
        hm.merge(hm)


def test_heat_keys_union_is_idempotent_and_commutative():
    spec = _spec()
    buf, _ = collect_shard(spec, GridSampler(None), None, 0, 64, 0)
    an = Analyzer(spec.name, spec.grid, "s")
    an.ingest(buf)
    ks = an.flush(keep_keys=True).region("A").key_state
    assert ks is not None and ks.union(ks).equals(ks)
    assert ks.union(HeatKeys.empty()).equals(ks)
    buf2, _ = collect_shard(spec, GridSampler(None), None, 64, 128, 1)
    an2 = Analyzer(spec.name, spec.grid, "s")
    an2.ingest(buf2)
    ks2 = an2.flush(keep_keys=True).region("A").key_state
    assert ks.union(ks2).equals(ks2.union(ks))


# -- spec sources ------------------------------------------------------------


def test_sourced_spec_builds_and_stamps():
    spec = sourced_spec("repro.kernels.gemm:gemm_v01_spec", 256, 256, 256)
    assert spec.grid and spec.source == (
        "repro.kernels.gemm:gemm_v01_spec", (256, 256, 256), {},
    )
    from repro.kernels.gemm import gemm_v01_spec

    direct = gemm_v01_spec(256, 256, 256)
    assert heatmaps_equal(
        analyze(spec, GridSampler(None)), analyze(direct, GridSampler(None))
    )


def test_registry_build_stamps_source():
    from repro import kernels as kreg

    spec, ctx = kreg.build("gemm")
    assert spec.source == "gemm:v00"
    spec2, _ = kreg.build("gemm:v01")
    assert spec2.source == "gemm:v01"


def test_rebuild_rejects_stale_source():
    """A spec structurally modified after source stamping must not be
    silently replaced by the pristine registry rebuild in the worker."""
    from repro import kernels as kreg
    from repro.core.collector import _collect_shard_task, _spec_fingerprint
    from repro.kernels.gemm import gemm_v00_spec

    spec, _ = kreg.build("gemm:v00")  # registry builds at 1024^3
    stale = dataclasses.replace(
        gemm_v00_spec(64, 64, 64), source=spec.source
    )
    task = {
        "source": stale.source,
        "fingerprint": _spec_fingerprint(stale),
        "sampler": GridSampler(None),
        "dynamic_context": None,
        "lo": 0, "hi": 1, "shard": 0, "max_records": 100,
    }
    with pytest.raises(ValueError, match="structurally"):
        _collect_shard_task(task)


# -- merge-algebra property: duplication/permutation invariance --------------
#
# The recovery loop leans on this: a re-executed shard (retry, pool
# rebuild, watchdog resplit) contributes its key sets AGAIN, and the
# union must not care.  Property: folding any shard sequence that
# covers every shard at least once — duplicates and order arbitrary —
# yields temperature state bit-identical to the serial full-grid build.
# Runs under hypothesis when available, else a seeded deterministic
# sweep (this container ships no hypothesis; no new deps).

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

_N_SHARDS = 4


@pytest.fixture(scope="module")
def shard_maps():
    from repro.core.collector import shard_bounds as _bounds

    spec = _spec()
    maps = []
    for i, (lo, hi) in enumerate(_bounds(spec.grid[0], _N_SHARDS)):
        buf, _ = collect_shard(spec, GridSampler(None), None, lo, hi, i)
        an = Analyzer(spec.name, spec.grid, "full-grid")
        an.ingest(buf)
        maps.append(an.flush(keep_keys=True))
    serial_buf, _ = collect(spec, GridSampler(None))
    an = Analyzer(spec.name, spec.grid, "full-grid")
    an.ingest(serial_buf)
    return maps, an.flush(keep_keys=True)


def _temps_equal(a, b):
    """Bit-identity of temperature state only (n_records/shards differ
    by construction when a shard is merged twice)."""
    if a.region_names() != b.region_names():
        return False
    for ra, rb in zip(a.regions, b.regions):
        if ra.n_programs != rb.n_programs:
            return False
        if not (
            np.array_equal(ra.tags_array, rb.tags_array)
            and np.array_equal(ra.word_temps_matrix, rb.word_temps_matrix)
            and np.array_equal(ra.sector_temps_array, rb.sector_temps_array)
        ):
            return False
    return True


def _assert_fold_matches_serial(seq, shard_maps):
    maps, serial = shard_maps
    merged = maps[seq[0]]
    for i in seq[1:]:
        merged = merged.merge(maps[i])
    assert _temps_equal(merged, serial), seq


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seq=hyp_st.lists(
            hyp_st.integers(0, _N_SHARDS - 1), min_size=_N_SHARDS,
            max_size=3 * _N_SHARDS,
        ).filter(lambda s: set(s) == set(range(_N_SHARDS)))
    )
    def test_merge_duplication_invariance_property(seq, shard_maps):
        _assert_fold_matches_serial(seq, shard_maps)

else:

    @pytest.mark.parametrize("case", range(24))
    def test_merge_duplication_invariance_property(case, shard_maps):
        import random

        rng = random.Random(case)
        base = list(range(_N_SHARDS))
        rng.shuffle(base)
        extra = [
            rng.randrange(_N_SHARDS)
            for _ in range(rng.randrange(2 * _N_SHARDS + 1))
        ]
        seq = base + extra
        rng.shuffle(seq)
        _assert_fold_matches_serial(seq, shard_maps)


def test_remerging_same_subset_twice_is_bit_identical(shard_maps):
    """The exact resilient-collector shape: a subset lands, then lands
    AGAIN (duplicated delivery after a presumed-lost shard)."""
    maps, serial = shard_maps
    once = maps[0]
    for m in maps[1:]:
        once = once.merge(m)
    twice = once
    for m in maps[:2]:  # re-deliver a subset on top of the full merge
        twice = twice.merge(m)
    assert _temps_equal(once, serial)
    assert _temps_equal(twice, once)


# -- the process pool (spawn) ------------------------------------------------


def test_pool_sharded_analyze_matches_serial():
    """End to end across real spawned workers: registry spec rebuilt in
    the worker, chunks shipped back, merged bit-identically."""
    from repro import kernels as kreg

    spec, ctx = kreg.build("gemm:v01")
    serial = analyze(spec, GridSampler(None), ctx)
    with ShardedCollector(2) as sc:
        sharded = sc.analyze(spec, GridSampler(None), ctx)
        # pool reuse: a second collect through the same pool
        sharded2 = sc.analyze(spec, GridSampler(None), ctx)
    assert heatmaps_equal(serial, sharded)
    assert heatmaps_equal(serial, sharded2)
    assert [(s.lo, s.hi) for s in sharded.shards] == [
        (s.lo, s.hi) for s in sharded2.shards
    ]
    assert len(sharded.shards) == 2


def test_pool_workers_hold_jax_to_the_cpu(monkeypatch):
    """Workers only walk grids: their JAX is pinned to the CPU, so a
    worker never reaches for the accelerator the parent holds."""
    import os

    # the workers inherit no platform choice from this process
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with ShardedCollector(1) as sc:
        pool = sc._ensure_pool()
        assert pool.submit(os.getenv, "JAX_PLATFORMS").result() == "cpu"
    assert "JAX_PLATFORMS" not in os.environ  # the parent is untouched
