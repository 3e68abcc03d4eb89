"""Serving-shaped kernel families: ragged flash + paged KV-cache attention.

Numerics vs the pure-jnp oracles (interpret mode), registry/trace/lint
plumbing for all eight variants, the dense-vs-dynamic transfer ladders
(the optimized rung must be strictly cheaper — that delta is what lets
``cuthermo tune`` accept it), and one closed tuner loop on the
``ragged_flash`` family with v3 provenance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro import kernels as kreg
from repro.core.lint import lint_ref
from repro.core.session import profile_kernel

RF = K.ragged_flash
PA = K.paged_attn


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


# ---------------------------------------------------------------------------
# numerics vs references
# ---------------------------------------------------------------------------


def test_ragged_decode_matches_reference():
    b, h, s, d = 4, 4, 128, 32
    q = _rand(0, (b, h, d))
    k = _rand(1, (b, s, d))
    v = _rand(2, (b, s, d))
    ctx = RF.ragged_context(b, s)
    starts = jnp.asarray(ctx["starts"])
    ends = jnp.asarray(ctx["ends"])
    got = RF.ragged_decode_attention(q, k, v, starts, ends, bkv=32, interpret=True)
    want = RF.ragged_decode_reference(q, k, v, starts, ends)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_ragged_decode_block_size_invariance():
    # the online-softmax accumulation must not depend on the KV tiling
    b, h, s, d = 2, 4, 128, 32
    q, k, v = _rand(0, (b, h, d)), _rand(1, (b, s, d)), _rand(2, (b, s, d))
    starts = jnp.asarray([0, 16], jnp.int32)
    ends = jnp.asarray([100, 128], jnp.int32)
    a = RF.ragged_decode_attention(q, k, v, starts, ends, bkv=32, interpret=True)
    bb = RF.ragged_decode_attention(q, k, v, starts, ends, bkv=64, interpret=True)
    np.testing.assert_allclose(a, bb, atol=2e-5, rtol=2e-4)


def test_paged_decode_matches_reference():
    b, h, d = 4, 4, 32
    pages, slots, page = 16, 4, 32
    q = _rand(0, (b, h, d))
    k_pages = _rand(1, (1, pages, page, d))
    v_pages = _rand(2, (1, pages, page, d))
    ctx = PA.paged_context(b, pages, slots, page)
    tables = jnp.asarray(ctx["block_tables"])
    lens = jnp.asarray(ctx["context_lens"])
    got = PA.paged_decode_attention(q, k_pages, v_pages, tables, lens, interpret=True)
    want = PA.paged_decode_reference(q, k_pages, v_pages, tables, lens)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)


def test_paged_decode_table_permutation_invariance():
    # physically relocating pages (and renaming them in the table) must
    # not change the attention output — the defining paged-cache property
    b, h, d = 2, 4, 32
    pages, slots, page = 8, 2, 32
    q = _rand(0, (b, h, d))
    k_pages = _rand(1, (1, pages, page, d))
    v_pages = _rand(2, (1, pages, page, d))
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lens = jnp.asarray([48, 64], jnp.int32)
    base = PA.paged_decode_attention(q, k_pages, v_pages, tables, lens, interpret=True)
    perm = np.asarray([5, 3, 7, 0, 2, 6, 1, 4])
    k2 = k_pages[:, perm]
    v2 = v_pages[:, perm]
    inv = np.argsort(perm)
    tables2 = jnp.asarray(inv[np.asarray(tables)], jnp.int32)
    moved = PA.paged_decode_attention(q, k2, v2, tables2, lens, interpret=True)
    np.testing.assert_allclose(base, moved, atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# registry + trace + lint plumbing
# ---------------------------------------------------------------------------

SERVING_REFS = (
    "ragged_flash:decode", "ragged_flash:decode-ragged",
    "ragged_flash:prefill", "ragged_flash:prefill-ragged",
    "paged_attn:decode", "paged_attn:decode-paged",
    "paged_attn:prefill", "paged_attn:prefill-paged",
)


def test_serving_families_are_registered():
    names = kreg.names()
    assert "ragged_flash" in names and "paged_attn" in names
    for family in ("ragged_flash", "paged_attn"):
        entry = kreg.get(family)
        assert [v.role for v in entry.variants] == [
            "baseline", "optimized", "baseline", "optimized"
        ]
        # the ladder proposes only the optimized rungs
        ladder = [v.name for _pos, v in entry.ladder(0)]
        assert all("-" in n for n in ladder), ladder


@pytest.mark.parametrize("ref", SERVING_REFS)
def test_serving_specs_build_and_trace(ref):
    spec, ctx = kreg.build(ref)
    assert spec.source == ref
    assert ctx is not None  # every serving variant carries its context
    pk = profile_kernel(spec, None, ctx, name=ref)
    assert pk.transactions > 0
    # the scalar-prefetch operands are present in the traced map
    regions = {r.region.name for r in pk.heatmap.regions}
    assert {"starts", "ends"} <= regions or {
        "block_tables", "context_lens"
    } <= regions


@pytest.mark.parametrize("ref", SERVING_REFS)
def test_serving_specs_lint_without_nonaffine(ref):
    # static variants must be fully affine; dynamic rungs must be
    # 'dynamic' (modeled), never 'nonaffine' (model failure) — the
    # lint pre-screen in `cuthermo tune` depends on this
    rep = lint_ref(ref)
    statuses = {ov.status for ov in rep.operands}
    assert "nonaffine" not in statuses, (ref, statuses)
    if ref.endswith(("-ragged", "-paged")):
        assert "dynamic" in statuses, (ref, statuses)
    else:
        assert rep.static_transactions is not None


def test_dynamic_rungs_are_strictly_cheaper():
    # the serving trick's whole point: the data-dependent rung moves
    # strictly fewer tiles than its dense baseline on the seeded context
    expected = {
        ("ragged_flash:decode", "ragged_flash:decode-ragged"): (576, 154),
        ("ragged_flash:prefill", "ragged_flash:prefill-ragged"):
            (4224, 2522),
        ("paged_attn:decode", "paged_attn:decode-paged"): (640, 288),
        ("paged_attn:prefill", "paged_attn:prefill-paged"): (6400, 4944),
    }
    for (dense_ref, dyn_ref), (dense_tx, dyn_tx) in expected.items():
        spec, ctx = kreg.build(dense_ref)
        dense = profile_kernel(spec, None, ctx)
        spec, ctx = kreg.build(dyn_ref)
        dyn = profile_kernel(spec, None, ctx)
        # pinned absolute counts: a context/shape drift that silently
        # changes the modeled traffic fails here, not in the tuner
        assert dense.transactions == dense_tx, dense_ref
        assert dyn.transactions == dyn_tx, dyn_ref
        assert dyn.transactions < dense.transactions


def test_serving_traces_are_deterministic():
    # the seeded context must make repeated collections bit-identical
    # (the property the collection cache and check gates rely on)
    from repro.core.session import heatmaps_equal

    spec, ctx = kreg.build("ragged_flash:decode-ragged")
    a = profile_kernel(spec, None, ctx)
    b = profile_kernel(spec, None, ctx)
    assert heatmaps_equal(a.heatmap, b.heatmap)


def test_tune_accepts_the_ragged_rung(tmp_path):
    # close the loop on the serving family: the tuner must accept an
    # improvement and persist v3 provenance for it
    from repro.core.session import ProfileSession
    from repro.core.tuner import trajectories_from_session

    with ProfileSession(tmp_path / "sess") as sess:
        res = sess.tune("ragged_flash:decode", budget=2, use_generated=False)
    assert res.improved
    assert res.best.transactions < res.baseline.transactions
    (traj,) = trajectories_from_session(
        ProfileSession(tmp_path / "sess", create=False)
    )
    assert traj["kernel"] == "ragged_flash"
    accepted = [s for s in traj["steps"] if s["accepted"]]
    assert accepted and accepted[0]["candidate"]["label"].startswith("ladder:")
