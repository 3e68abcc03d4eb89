"""Golden equivalence: the columnar engine vs the seed per-record engine.

Every case asserts *bit-identical* heat maps — region set, sector tags,
word temps, sector temps, contributor counts, record counts, plus the
derived transaction model (``sector_transactions``, ``waste_ratio``).
"""

import numpy as np
import pytest

from repro.core._reference import (
    ReferenceAnalyzer,
    analyze_reference,
    collect_reference,
    drain_dynamic_reference,
)
from repro.core.collector import analyze, collect, drain_dynamic
from repro.core.heatmap import Analyzer
from repro.core.spec import OperandSpec
from repro.core.trace import GridSampler


def assert_heatmaps_identical(got, want):
    assert got.kernel == want.kernel
    assert got.grid == want.grid
    assert got.n_records == want.n_records
    assert got.dropped == want.dropped
    assert got.region_names() == want.region_names()
    for g, w in zip(got.regions, want.regions):
        name = w.region.name
        assert g.region.name == name
        assert g.region.space == w.region.space
        assert g.n_programs == w.n_programs, name
        np.testing.assert_array_equal(
            g.tags_array, w.tags_array, err_msg=f"tags of {name}"
        )
        np.testing.assert_array_equal(
            g.word_temps_matrix, w.word_temps_matrix,
            err_msg=f"word temps of {name}",
        )
        np.testing.assert_array_equal(
            g.sector_temps_array, w.sector_temps_array,
            err_msg=f"sector temps of {name}",
        )
        # row views agree too (lazy materialization path)
        assert g.rows == w.rows, name
    assert got.sector_transactions() == want.sector_transactions()
    assert got.useful_word_transactions() == want.useful_word_transactions()
    assert got.waste_ratio() == want.waste_ratio()
    for name in got.region_names():
        assert got.waste_ratio(name) == want.waste_ratio(name), name
        assert (
            got.sector_transactions(name) == want.sector_transactions(name)
        ), name


SAMPLERS = [GridSampler((0,), window=8), GridSampler(None)]


@pytest.mark.parametrize("sampler", SAMPLERS, ids=["window8", "full"])
def test_gemm_equivalence(sampler):
    from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec, gemm_v02_spec

    for spec in (
        gemm_v00_spec(128, 128, 128),
        gemm_v01_spec(256, 256, 256),
        gemm_v02_spec(256, 256, 256, bm=64, bn=64, bk=64),
    ):
        assert_heatmaps_identical(
            analyze(spec, sampler), analyze_reference(spec, sampler)
        )


@pytest.mark.parametrize("sampler", SAMPLERS, ids=["window8", "full"])
def test_spmv_misaligned_origin_equivalence(sampler):
    from repro.kernels.spmv import spmv_csr_spec

    rng = np.random.default_rng(7)
    colidx = rng.integers(0, 2048, size=4096).astype(np.int32)
    spec = spmv_csr_spec(4096, 2048, block_rows=512)
    ctx = {"col_indices": colidx}
    assert_heatmaps_identical(
        analyze(spec, sampler, dynamic_context=ctx),
        analyze_reference(spec, sampler, dynamic_context=ctx),
    )


@pytest.mark.parametrize("sampler", SAMPLERS, ids=["window8", "full"])
def test_dynamic_gather_equivalence(sampler):
    from repro.kernels.histogram import hist_naive_spec

    rng = np.random.default_rng(3)
    cells = rng.integers(0, 512, size=8192).astype(np.int64)
    spec = hist_naive_spec(8192, 512, block=1024)
    ctx = {"cells": cells}
    assert_heatmaps_identical(
        analyze(spec, sampler, dynamic_context=ctx),
        analyze_reference(spec, sampler, dynamic_context=ctx),
    )


@pytest.mark.parametrize("sampler", SAMPLERS, ids=["window8", "full"])
def test_scratch_accumulator_equivalence(sampler):
    from repro.kernels.histogram import hist_opt2_spec
    from repro.kernels.ttm import cuszp_like_spec, ttm_scratch_spec

    for spec in (
        ttm_scratch_spec(256, 8, 32),
        hist_opt2_spec(16384, 512),
        cuszp_like_spec(32),
    ):
        assert_heatmaps_identical(
            analyze(spec, sampler), analyze_reference(spec, sampler)
        )


def test_misc_kernels_full_equivalence():
    """Sweep the remaining case-study specs at full trace."""
    from repro.kernels.gramschm import k3_naive_block_spec, k3_opt_spec
    from repro.kernels.spmv import spmv_zigzag_spec
    from repro.kernels.ttm import ttm_fused_spec

    rng = np.random.default_rng(11)
    colidx = rng.integers(0, 1024, size=2048).astype(np.int32)
    cases = [
        (k3_naive_block_spec(256, 256, 256, k=3), None),
        (k3_opt_spec(256, 256, 256, k=3), None),
        (ttm_fused_spec(128, 8, 32), None),
        (spmv_zigzag_spec(2048, 1024, block_rows=512),
         {"col_indices": colidx}),
    ]
    for spec, ctx in cases:
        assert_heatmaps_identical(
            analyze(spec, GridSampler(None), dynamic_context=ctx),
            analyze_reference(spec, GridSampler(None), dynamic_context=ctx),
        )


def test_drain_dynamic_equivalence():
    op = OperandSpec("x", (4096,), np.float32, (4096,), lambda i: (0,))
    rng = np.random.default_rng(5)
    trace = rng.integers(-64, 4096, size=(8, 96))
    for sampler in SAMPLERS:
        buf = drain_dynamic("k", (8,), op, trace, sampler)
        ref = drain_dynamic_reference("k", (8,), op, trace, sampler)
        an, ran = Analyzer("k", (8,), "s"), ReferenceAnalyzer("k", (8,), "s")
        an.ingest(buf)
        ran.ingest(ref)
        assert_heatmaps_identical(an.flush(), ran.flush())
        # record views agree up to object identity
        got = sorted(
            (r.program_id, r.touches) for r in buf.records
        )
        want = sorted((r.program_id, r.touches) for r in ref.records)
        assert got == want


def test_drain_dynamic_valid_mask_equivalence():
    op = OperandSpec("x", (1024, 256), np.float32, (8, 256), lambda i: (i, 0))
    rng = np.random.default_rng(9)
    trace = rng.integers(0, 1024 * 256, size=(4, 32))
    mask = rng.random((4, 32)) < 0.5
    buf = drain_dynamic("k", (4,), op, trace, GridSampler(None), mask)
    ref = drain_dynamic_reference("k", (4,), op, trace, GridSampler(None), mask)
    an, ran = Analyzer("k", (4,), "s"), ReferenceAnalyzer("k", (4,), "s")
    an.ingest(buf)
    ran.ingest(ref)
    assert_heatmaps_identical(an.flush(), ran.flush())


def test_compat_append_path_equivalence():
    """Record-at-a-time appends (the exact path) match the seed bitmasks,
    including duplicate touches and repeated program ids."""
    from repro.core._reference import ReferenceTraceBuffer
    from repro.core.tiles import TileGeometry
    from repro.core.trace import AccessRecord, RegionInfo, TraceBuffer

    geom = TileGeometry(shape=(64, 256), itemsize=4, name="A")
    recs = [
        ((0,), [(0, 0), (0, 0), (1, 3)]),  # duplicate touch
        ((1,), [(0, 0)]),
        ((0,), [(1, 3), (2, 7)]),  # repeated pid, overlapping touch
        ((2,), []),  # zero-touch record still counts as a contributor
    ]
    buf, ref = TraceBuffer(), ReferenceTraceBuffer()
    for b in (buf, ref):
        b.register_region(RegionInfo("A", geom))
        for pid, touches in recs:
            b.append(
                AccessRecord(array="A", site="k/A", space="hbm", kind="load",
                             program_id=pid, touches=tuple(touches))
            )
    an, ran = Analyzer("k", (4,), "s"), ReferenceAnalyzer("k", (4,), "s")
    an.ingest(buf)
    ran.ingest(ref)
    assert_heatmaps_identical(an.flush(), ran.flush())


def test_compress_region_matches_compress_rows():
    from repro.core.heatmap import compress_region, compress_rows
    from repro.kernels.gemm import gemm_v00_spec
    from repro.kernels.spmv import spmv_csr_spec

    rng = np.random.default_rng(2)
    colidx = rng.integers(0, 1024, size=2048).astype(np.int32)
    heatmaps = [
        analyze(gemm_v00_spec(512, 512, 512), GridSampler((0,), window=32)),
        analyze(spmv_csr_spec(2048, 1024, block_rows=512), GridSampler(None),
                dynamic_context={"col_indices": colidx}),
    ]
    for hm in heatmaps:
        for rh in hm.regions:
            assert compress_region(rh) == compress_rows(rh.rows)


def test_mixed_buffer_ingest_equivalence():
    """Two collect() buffers (overlapping pid windows) ingested into one
    Analyzer must still dedupe contributors exactly (cross-group path)."""
    from repro.kernels.gemm import gemm_v01_spec

    spec = gemm_v01_spec(256, 256, 256)
    buf1, _ = collect(spec, GridSampler((0,), window=8))
    buf2, _ = collect(spec, GridSampler((0,), window=16))  # superset window
    an = Analyzer(spec.name, spec.grid, "mixed")
    an.ingest(buf1)
    an.ingest(buf2)

    ref1, _ = collect_reference(spec, GridSampler((0,), window=8))
    ref2, _ = collect_reference(spec, GridSampler((0,), window=16))
    ran = ReferenceAnalyzer(spec.name, spec.grid, "mixed")
    ran.ingest(ref1)
    ran.ingest(ref2)
    assert_heatmaps_identical(an.flush(), ran.flush())


# ---------------------------------------------------------------------------
# merge algebra: any partition of a trace into shards merges bit-identically
# ---------------------------------------------------------------------------


def _shard_cases():
    """Kernel cases exercising every collector path under sharding:
    static broadcast operands, once= single-program stores, scratch
    accumulators, and dynamic (Level-2) CSR operands."""
    from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec
    from repro.kernels.histogram import hist_naive_spec, hist_opt2_spec
    from repro.kernels.spmv import spmv_csr_spec
    from repro.kernels.ttm import ttm_scratch_spec

    rng = np.random.default_rng(17)
    return [
        (gemm_v00_spec(256, 256, 256), None),
        (gemm_v01_spec(256, 256, 256), None),
        (ttm_scratch_spec(256, 8, 32), None),
        (hist_opt2_spec(16384, 512), None),  # once= final store
        (hist_naive_spec(8192, 512, block=1024),
         {"cells": rng.integers(0, 512, size=8192).astype(np.int64)}),
        (spmv_csr_spec(4096, 2048, block_rows=512),
         {"col_indices": rng.integers(0, 2048, size=4096).astype(np.int32)}),
    ]


def _partition_merge(spec, ctx, bounds, sampler=None):
    """Collect each [lo, hi) shard, unify tokens, flush ONE analyzer."""
    from repro.core.collector import _unify_shard_groups, collect_shard

    sampler = sampler or GridSampler(None)
    results = [
        collect_shard(spec, sampler, ctx, lo, hi, i)
        for i, (lo, hi) in enumerate(bounds)
    ]
    bufs = [b for b, _ in results]
    _unify_shard_groups(bufs)
    an = Analyzer(spec.name, spec.grid, sampler.describe())
    for buf in bufs:
        an.ingest(buf)
    return an.flush()


def _heatmap_merge(spec, ctx, bounds, sampler=None):
    """Flush each shard with key state, fold through Heatmap.merge."""
    from repro.core.collector import collect_shard

    sampler = sampler or GridSampler(None)
    merged = None
    for i, (lo, hi) in enumerate(bounds):
        buf, _ = collect_shard(spec, sampler, ctx, lo, hi, i)
        an = Analyzer(spec.name, spec.grid, sampler.describe())
        an.ingest(buf)
        hm = an.flush(keep_keys=True)
        merged = hm if merged is None else merged.merge(hm)
    return merged


def _strip_keys(hm):
    """Key state is an internal carrier; compare the flushed arrays."""
    for rh in hm.regions:
        rh.key_state = None
    return hm


@pytest.mark.parametrize("n_shards", [2, 3, 5])
def test_partitioned_chunk_merge_bit_identical(n_shards):
    """Sharded chunk-level merge == serial single pass, every case."""
    from repro.core.collector import shard_bounds

    for spec, ctx in _shard_cases():
        serial = analyze(spec, GridSampler(None), dynamic_context=ctx)
        total = int(np.prod(spec.grid))
        sharded = _partition_merge(
            spec, ctx, shard_bounds(total, n_shards)
        )
        assert_heatmaps_identical(sharded, serial)


def test_partitioned_heatmap_merge_bit_identical():
    """Heatmap.merge over key-state shards == serial single pass."""
    from repro.core.collector import shard_bounds

    for spec, ctx in _shard_cases():
        serial = analyze(spec, GridSampler(None), dynamic_context=ctx)
        total = int(np.prod(spec.grid))
        merged = _heatmap_merge(spec, ctx, shard_bounds(total, 3))
        assert_heatmaps_identical(_strip_keys(merged), serial)


def test_uneven_partition_merge_bit_identical():
    """Degenerate partitions (empty and single-program shards) merge
    exactly too — the monoid has an identity."""
    from repro.kernels.gemm import gemm_v00_spec

    spec = gemm_v00_spec(128, 128, 128)
    serial = analyze(spec, GridSampler(None))
    bounds = [(0, 0), (0, 1), (1, 1), (1, 128)]
    assert_heatmaps_identical(_partition_merge(spec, None, bounds), serial)
    assert_heatmaps_identical(
        _strip_keys(_heatmap_merge(spec, None, bounds)), serial
    )


def test_overlapping_heatmap_merge_is_union_not_sum():
    """Merging OVERLAPPING shards must union contributors, not add
    temperatures — the defining property of the merge algebra."""
    from repro.kernels.gemm import gemm_v01_spec

    spec = gemm_v01_spec(256, 256, 256)
    # the same full grid twice: union == one pass, sum would double
    full = [(0, int(np.prod(spec.grid)))] * 2
    serial = analyze(spec, GridSampler(None))
    merged = _heatmap_merge(spec, None, full)
    assert merged.n_records == 2 * serial.n_records  # records DO add
    for name in serial.region_names():  # temperatures do NOT
        np.testing.assert_array_equal(
            merged.region(name).word_temps_matrix,
            serial.region(name).word_temps_matrix,
        )
        np.testing.assert_array_equal(
            merged.region(name).sector_temps_array,
            serial.region(name).sector_temps_array,
        )


def test_sharded_collector_inprocess_bit_identical():
    """The ShardedCollector fallback (no registry source) end to end."""
    from repro.core.collector import ShardedCollector

    for spec, ctx in _shard_cases():
        serial = analyze(spec, GridSampler(None), dynamic_context=ctx)
        with ShardedCollector(3) as sc:
            sharded = sc.analyze(spec, GridSampler(None), ctx)
        assert len(sharded.shards) == 3
        assert sum(s.programs for s in sharded.shards) == int(
            np.prod(spec.grid)
        )
        assert_heatmaps_identical(sharded, serial)


def test_collection_cache_hits_bit_identical(tmp_path):
    """GOLDEN: a cache hit — memory tier or a fresh process's disk tier —
    reproduces the freshly collected heat map exactly, for every shard
    case (operand walks, dynamic gathers, scratch accumulators)."""
    from repro.core.cache import CollectionCache, spec_content_hash

    cache = CollectionCache(tmp_path / "cache")
    for spec, ctx in _shard_cases():
        serial = analyze(spec, GridSampler(None), dynamic_context=ctx)
        key = spec_content_hash(spec, GridSampler(None), ctx)
        cache.put(key, serial)
        assert_heatmaps_identical(cache.get(key), serial)  # memory tier
        rebooted = CollectionCache(tmp_path / "cache")  # fresh process
        assert_heatmaps_identical(rebooted.get(key), serial)


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests degrade to the deterministic ones
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def _partitions(draw, total):
        """A random contiguous partition of range(total) into shards."""
        n_cuts = draw(st.integers(min_value=0, max_value=min(6, total)))
        cuts = sorted(
            draw(
                st.lists(
                    st.integers(min_value=0, max_value=total),
                    min_size=n_cuts,
                    max_size=n_cuts,
                )
            )
        )
        edges = [0] + cuts + [total]
        return list(zip(edges[:-1], edges[1:]))

    @given(data=st.data(), case=st.integers(min_value=0, max_value=5))
    @settings(max_examples=20, deadline=None)
    def test_any_partition_merges_bit_identically(data, case):
        """PROPERTY: for ANY contiguous partition of the sampled grid,
        both merge paths reproduce the single-pass heat map exactly."""
        spec, ctx = _shard_cases()[case]
        total = int(np.prod(spec.grid))
        bounds = data.draw(_partitions(total))
        serial = analyze(spec, GridSampler(None), dynamic_context=ctx)
        assert_heatmaps_identical(
            _partition_merge(spec, ctx, bounds), serial
        )
        assert_heatmaps_identical(
            _strip_keys(_heatmap_merge(spec, ctx, bounds)), serial
        )
