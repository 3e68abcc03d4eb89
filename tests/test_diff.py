"""Heat-map diffing (the paper's iterate loop)."""

import numpy as np

from repro.core.collector import analyze
from repro.core.diff import diff
from repro.core.trace import GridSampler
from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec
from repro.kernels.gramschm import k3_naive_spec, k3_opt_spec


def test_diff_gemm_shows_fix_and_speedup():
    S = GridSampler((0,), window=32)
    before = analyze(gemm_v00_spec(1024, 1024, 1024), S)
    after = analyze(gemm_v01_spec(1024, 1024, 1024), S)
    d = diff(before, after)
    assert ("C", "false-sharing") in d.fixed
    assert not any(p == "false-sharing" for _, p in d.introduced)
    assert d.tx_before > 0 and d.tx_after > 0
    assert "thermo diff" in d.summary()


def test_diff_with_region_rename():
    before = analyze(k3_naive_spec(512, 512, 512, k=3), GridSampler(None))
    after = analyze(k3_opt_spec(512, 512, 512, k=3), GridSampler(None))
    d = diff(before, after, region_map={"q": "qT"})
    assert ("q", "strided") in d.fixed
    assert d.speedup_estimate > 1.5


def test_diff_identical_is_clean():
    S = GridSampler((0,), window=32)
    hm = analyze(gemm_v00_spec(256, 256, 256), S)
    hm2 = analyze(gemm_v00_spec(256, 256, 256), S)
    d = diff(hm, hm2)
    assert d.fixed == () and d.introduced == ()
    assert abs(d.speedup_estimate - 1.0) < 1e-9


def test_verdict_property():
    from repro.core.diff import HeatmapDiff

    def hd(tx_before, tx_after, fixed=(), introduced=()):
        return HeatmapDiff(
            kernel_before="a", kernel_after="b", regions=(),
            fixed=tuple(fixed), introduced=tuple(introduced),
            persisting=(), tx_before=tx_before, tx_after=tx_after,
        )

    assert hd(100, 50).verdict == "improved"
    assert hd(100, 200).verdict == "regressed"
    assert hd(100, 100).verdict == "unchanged"
    # a new pattern without reduced traffic is a regression, even when
    # another pattern was fixed in trade
    assert hd(100, 100, introduced=[("r", "p2")]).verdict == "regressed"
    assert hd(
        100, 100, fixed=[("r", "p1")], introduced=[("r", "p2")]
    ).verdict == "regressed"
    # reduced traffic wins even with a new (milder) pattern
    assert hd(100, 50, introduced=[("r", "p2")]).verdict == "improved"


# -- property-based: the verdict algebra over arbitrary heat maps -----------
#
# Hand-built heat maps (tiny synthetic regions, arbitrary temperatures)
# drive `diff` through its full alignment/rename/verdict path.  When
# hypothesis is unavailable the deterministic tests above still pin the
# core cases.

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests degrade to the deterministic ones
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    from repro.core.heatmap import Heatmap, RegionHeatmap
    from repro.core.tiles import TileGeometry
    from repro.core.trace import RegionInfo

    _REGION_NAMES = ("A", "B", "C")

    @st.composite
    def _region(draw, name):
        """One synthetic region heat map with arbitrary temperatures."""
        geometry = TileGeometry((16, 128), itemsize=4, name=name)
        wps = 8  # float32: 8 sublane rows per native tile
        n_sectors = draw(st.integers(min_value=1, max_value=4))
        tags = np.arange(n_sectors, dtype=np.int64) * wps
        word_temps = np.asarray(
            draw(
                st.lists(
                    st.lists(
                        st.integers(min_value=0, max_value=4),
                        min_size=wps, max_size=wps,
                    ),
                    min_size=n_sectors, max_size=n_sectors,
                )
            ),
            dtype=np.int64,
        )
        # a sector is at least as hot as its hottest word
        extra = draw(st.integers(min_value=0, max_value=3))
        sector_temps = np.maximum(word_temps.max(axis=1), 1) + extra
        return RegionHeatmap(
            RegionInfo(name=name, geometry=geometry, space="hbm"),
            n_programs=draw(st.integers(min_value=1, max_value=64)),
            tags=tags,
            word_temps=word_temps,
            sector_temps=sector_temps.astype(np.int64),
        )

    @st.composite
    def _heatmap(draw, kernel="k"):
        n_regions = draw(st.integers(min_value=1, max_value=3))
        regions = tuple(
            draw(_region(_REGION_NAMES[i])) for i in range(n_regions)
        )
        return Heatmap(
            kernel=kernel,
            grid=(4,),
            sampler="full",
            regions=regions,
            n_records=64,
            dropped=0,
        )

    @given(hm=_heatmap())
    @settings(max_examples=30, deadline=None)
    def test_property_self_diff_never_regresses(hm):
        """PROPERTY: diff(a, a) is 'unchanged' — never a regression."""
        d = diff(hm, hm)
        assert d.verdict == "unchanged"
        assert d.fixed == () and d.introduced == ()
        assert d.tx_before == d.tx_after

    @given(a=_heatmap("a"), b=_heatmap("b"))
    @settings(max_examples=30, deadline=None)
    def test_property_swap_exchanges_improved_and_regressed(a, b):
        """PROPERTY: swapping before/after exchanges the verdicts.

        'improved' always flips to 'regressed'.  The reverse is
        one-directional: a regression caused purely by an introduced
        pattern at equal traffic swaps to 'unchanged' (losing a pattern
        is not an improvement), so only traffic-driven regressions flip
        all the way back to 'improved'.
        """
        fwd, rev = diff(a, b), diff(b, a)
        # the pattern bookkeeping is exactly mirrored
        assert set(fwd.fixed) == set(rev.introduced)
        assert set(fwd.introduced) == set(rev.fixed)
        assert set(fwd.persisting) == set(rev.persisting)
        if fwd.verdict == "improved":
            assert rev.verdict == "regressed"
        if fwd.verdict == "regressed" and fwd.tx_after > fwd.tx_before:
            assert rev.verdict == "improved"
        if fwd.verdict == "unchanged":
            assert rev.verdict in ("unchanged", "regressed")

    @given(a=_heatmap("a"), b=_heatmap("b"), data=st.data())
    @settings(max_examples=30, deadline=None)
    def test_property_verdict_invariant_under_region_rename(a, b, data):
        """PROPERTY: renaming an after-region (with the matching
        --region-map entry) never changes the verdict or the traffic."""
        baseline = diff(a, b)
        # rename one of b's regions to something fresh
        victim = data.draw(
            st.sampled_from([rh.region.name for rh in b.regions])
        )
        new_name = victim + "_renamed"
        renamed_regions = tuple(
            RegionHeatmap(
                RegionInfo(
                    name=new_name if rh.region.name == victim
                    else rh.region.name,
                    geometry=rh.region.geometry,
                    space=rh.region.space,
                ),
                n_programs=rh.n_programs,
                tags=rh.tags_array,
                word_temps=rh.word_temps_matrix,
                sector_temps=rh.sector_temps_array,
            )
            for rh in b.regions
        )
        b2 = Heatmap(
            kernel=b.kernel, grid=b.grid, sampler=b.sampler,
            regions=renamed_regions, n_records=b.n_records,
            dropped=b.dropped,
        )
        d2 = diff(a, b2, region_map={victim: new_name})
        assert d2.verdict == baseline.verdict
        assert d2.tx_before == baseline.tx_before
        assert d2.tx_after == baseline.tx_after
        assert set(d2.fixed) == set(baseline.fixed)
        assert set(d2.introduced) == set(baseline.introduced)
