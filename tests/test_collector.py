"""Level-1/2 collection: block sampling, caching, origins, once-stores."""

import numpy as np

from repro.core.collector import analyze, collect, drain_dynamic
from repro.core.spec import KernelSpec, OperandSpec, ScratchSpec
from repro.core.trace import GridSampler, KernelWhitelist, sampled_grid


def _toy_spec(m=64, n=64, k=64):
    return KernelSpec(
        name="toy",
        grid=(m // 8, n // 64),
        operands=(
            OperandSpec("A", (m, k), np.float32, (8, k), lambda i, j: (i, 0)),
            OperandSpec("B", (k, n), np.float32, (k, 64), lambda i, j: (0, j)),
            OperandSpec("C", (m, n), np.float32, (8, 64), lambda i, j: (i, j),
                        kind="store"),
        ),
    )


def test_block_sampling_reduces_records():
    spec = _toy_spec()
    full, stats_full = collect(spec, GridSampler(None))
    sampled, stats_s = collect(spec, GridSampler((0,)))
    assert stats_s.programs < stats_full.programs
    assert len(sampled) < len(full)
    # sampled admits exactly the grid row 0
    assert stats_s.programs == spec.grid[1]


def test_sampler_window():
    s = GridSampler((0,))
    assert s.admits((0, 5)) and not s.admits((1, 0))
    assert list(sampled_grid((2, 3), s)) == [(0, 0), (0, 1), (0, 2)]
    full = GridSampler(None)
    assert len(list(sampled_grid((2, 3), full))) == 6


def test_kernel_whitelist():
    wl = KernelWhitelist(["a", "b"])
    assert wl.admits("a") and not wl.admits("c")
    assert KernelWhitelist(None).admits("anything")


def test_origin_models_misalignment():
    aligned = KernelSpec(
        name="k", grid=(4,),
        operands=(OperandSpec("off", (4097,), np.int32, (1024,), lambda i: (i,)),),
    )
    shifted = KernelSpec(
        name="k", grid=(4,),
        operands=(
            OperandSpec("off", (4097,), np.int32, (1024,), lambda i: (i,),
                        origin=(0, 1)),
        ),
    )
    hm_a = analyze(aligned, GridSampler(None))
    hm_s = analyze(shifted, GridSampler(None))
    # the shifted view costs extra transfers (paper's 5-vs-4 economics)
    assert hm_s.sector_transactions() > hm_a.sector_transactions()


def test_once_store_counted_once():
    spec = KernelSpec(
        name="k", grid=(8,),
        operands=(
            OperandSpec("x", (8192,), np.int32, (1024,), lambda i: (i,)),
            OperandSpec("out", (1024,), np.float32, (1024,), lambda i: (0,),
                        kind="store", once=True),
        ),
    )
    hm = analyze(spec, GridSampler(None))
    out = hm.region("out")
    assert out.max_sector_temp == 1  # one program only


def test_drain_dynamic_level2():
    op = OperandSpec("x", (4096,), np.float32, (4096,), lambda i: (0,))
    # 4 programs, each touching flat indices around its own area
    trace = np.stack([np.arange(i * 128, i * 128 + 64) for i in range(4)])
    buf = drain_dynamic("k", (4,), op, trace, GridSampler(None))
    assert len(buf) == 4
    touched = {t for r in buf.records for t in r.touches}
    assert touched  # nonempty and valid tags
    for tag, w in touched:
        assert 0 <= w < 8


def test_scratch_regions_not_in_hbm_transactions():
    spec = KernelSpec(
        name="k", grid=(4,),
        operands=(OperandSpec("x", (4096,), np.float32, (1024,), lambda i: (i,)),),
        scratch=(ScratchSpec("s", (8, 128), np.float32),),
    )
    hm = analyze(spec, GridSampler(None))
    tx_all = hm.sector_transactions()
    tx_x = hm.sector_transactions("x")
    assert tx_all == tx_x  # scratch excluded from HBM transactions


# -- batch index-map evaluation: vectorized calls are validated, not trusted --


def test_batch_eval_catches_endpoint_agreeing_piecewise_map():
    """Adversarial regression: a vectorized map that matches the scalar
    evaluation at the batch's first and last program but lies in the
    middle.  Endpoint-only validation (the old check) accepted the
    vectorized result and miscollected every interior program; the
    middle sample must force the scalar fallback."""
    from repro.core.collector import _eval_index_map_batch

    n = 8

    def sneaky(i):
        if isinstance(i, np.ndarray):
            return (np.where((i == 0) | (i == n - 1), i, 0),)
        return (int(i),)

    pids = np.arange(n, dtype=np.int64).reshape(n, 1)
    got = _eval_index_map_batch(sneaky, pids)
    want = np.arange(n, dtype=np.int64).reshape(n, 1)
    assert np.array_equal(got, want)


def test_batch_eval_catches_arity_change():
    """A vectorized call returning a different arity than the scalar
    path must not be trusted either."""
    from repro.core.collector import _eval_index_map_batch

    def shapeshifter(i):
        if isinstance(i, np.ndarray):
            return (i, np.zeros_like(i))  # extra bogus component
        return (int(i),)

    pids = np.arange(6, dtype=np.int64).reshape(6, 1)
    got = _eval_index_map_batch(shapeshifter, pids)
    assert got.shape == (6, 1)
    assert np.array_equal(got[:, 0], np.arange(6))


def test_batch_eval_property_matches_scalar_rows():
    """Property: for any index map — affine, piecewise, broadcasting or
    not — the batch evaluation equals per-program scalar evaluation."""
    hypothesis = __import__("pytest").importorskip("hypothesis")
    st = __import__("pytest").importorskip("hypothesis.strategies")
    given, settings = hypothesis.given, hypothesis.settings

    @st.composite
    def _maps(draw):
        a = draw(st.integers(min_value=-3, max_value=3))
        b = draw(st.integers(min_value=0, max_value=7))
        pivot = draw(st.integers(min_value=0, max_value=12))
        kind = draw(st.sampled_from(["affine", "piecewise", "modular"]))
        if kind == "affine":
            return lambda i: (a * i + b,)
        if kind == "modular":
            return lambda i: (i % (pivot + 1), b)
        # piecewise: numpy-vectorizable via np.where, consistent with
        # the scalar branch for every i
        def pw(i):
            if isinstance(i, np.ndarray):
                return (np.where(i < pivot, i, a * i + b),)
            return (i if i < pivot else a * i + b,)
        return pw

    @settings(max_examples=60, deadline=None)
    @given(index_map=_maps(), p=st.integers(min_value=1, max_value=17))
    def _property(index_map, p):
        from repro.core.collector import _eval_index_map_batch

        pids = np.arange(p, dtype=np.int64).reshape(p, 1)
        got = _eval_index_map_batch(index_map, pids)
        want = np.asarray(
            [[int(x) for x in np.atleast_1d(index_map(int(i)))]
             for i in range(p)],
            dtype=np.int64,
        ).reshape(p, -1)
        assert np.array_equal(got, want)

    _property()
