"""Content-addressed collection cache: keys, tiers, and bit-identity."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.cache import (
    CACHE_VERSION,
    CacheKeyError,
    CollectionCache,
    callable_fingerprint,
    spec_content_hash,
)
from repro.core.session import heatmaps_equal, profile_kernel
from repro.core.spec import KernelSpec, OperandSpec
from repro.core.trace import GridSampler


def _spec(index_map=None, origin=(0, 0)):
    imap = index_map or (lambda i, j: (i, 0))
    return KernelSpec(
        name="toy",
        grid=(8, 8),
        operands=(
            OperandSpec("A", (64, 64), np.float32, (8, 64), imap),
            OperandSpec(
                "B", (64, 64), np.float32, (8, 64),
                lambda i, j: (0, j), origin=origin,
            ),
        ),
    )


# ---------------------------------------------------------------------------
# key derivation
# ---------------------------------------------------------------------------


def test_hash_is_deterministic_in_process():
    assert spec_content_hash(_spec()) == spec_content_hash(_spec())


def test_hash_changes_with_index_map():
    a = spec_content_hash(_spec(lambda i, j: (i, 0)))
    b = spec_content_hash(_spec(lambda i, j: (0, i)))
    assert a != b


def test_hash_changes_with_captured_closure_value():
    def make(k):
        return lambda i, j: (i * k, 0)

    assert spec_content_hash(_spec(make(1))) != spec_content_hash(
        _spec(make(2))
    )


def test_hash_same_for_identical_closures():
    def make(k):
        return lambda i, j: (i * k, 0)

    assert spec_content_hash(_spec(make(2))) == spec_content_hash(
        _spec(make(2))
    )


def test_hash_changes_with_origin():
    assert spec_content_hash(_spec()) != spec_content_hash(
        _spec(origin=(0, 7))
    )


def test_hash_changes_with_sampler():
    spec = _spec()
    full = spec_content_hash(spec, GridSampler(None))
    windowed = spec_content_hash(spec, GridSampler((0,), window=4))
    wider = spec_content_hash(spec, GridSampler((0,), window=8))
    assert len({full, windowed, wider}) == 3


def test_hash_changes_with_dynamic_context():
    from repro.kernels import build

    spec, ctx = build("spmv:csr")
    base = spec_content_hash(spec, dynamic_context=ctx)
    changed = {k: v.copy() for k, v in ctx.items()}
    name = sorted(changed)[0]
    changed[name] = changed[name] + 1
    assert spec_content_hash(spec, dynamic_context=changed) != base


def test_registry_specs_hash_stably_across_processes():
    """Rebuilding the same registry spec in a fresh interpreter yields
    the same content key — the property the on-disk tier rests on."""
    from repro.kernels import build

    spec, ctx = build("gemm:v00")
    here = spec_content_hash(spec, dynamic_context=ctx)
    script = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, sys.argv[1])
        from repro.core.cache import spec_content_hash
        from repro.kernels import build
        spec, ctx = build("gemm:v00")
        print(spec_content_hash(spec, dynamic_context=ctx))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script, str(Path(__file__).parent.parent / "src")],
        capture_output=True,
        text=True,
        check=True,
    )
    assert out.stdout.strip() == here


def test_uncacheable_callable_raises():
    class Opaque:
        def __call__(self, i, j):
            return (i, 0)

    with pytest.raises(CacheKeyError):
        spec_content_hash(_spec(Opaque()))


def test_callable_fingerprint_distinguishes_bytecode():
    assert callable_fingerprint(lambda i: (i, 0)) != callable_fingerprint(
        lambda i: (0, i)
    )


# ---------------------------------------------------------------------------
# cache behavior through profile_kernel
# ---------------------------------------------------------------------------


def test_hit_is_bit_identical_to_fresh_collection():
    cache = CollectionCache()
    fresh = profile_kernel(_spec(), cache=cache)
    assert not fresh.cached and fresh.cache_key
    again = profile_kernel(_spec(), cache=cache)
    assert again.cached and again.cache_key == fresh.cache_key
    assert heatmaps_equal(fresh.heatmap, again.heatmap)
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_changed_spec_misses():
    cache = CollectionCache()
    profile_kernel(_spec(), cache=cache)
    pk = profile_kernel(_spec(lambda i, j: (0, i)), cache=cache)
    assert not pk.cached
    assert cache.stats.misses == 2 and cache.stats.hits == 0


def test_uncacheable_spec_profiles_uncached():
    class Opaque:
        def __call__(self, i, j):
            return (i, 0)

    cache = CollectionCache()
    pk = profile_kernel(_spec(Opaque()), cache=cache)
    assert not pk.cached and pk.cache_key == ""
    assert pk.transactions > 0
    assert cache.stats.uncacheable == 1
    assert cache.stats.hits == cache.stats.misses == 0


def test_hit_strips_shard_provenance():
    cache = CollectionCache()
    hm = profile_kernel(_spec(), cache=cache).heatmap
    stored = cache.get(spec_content_hash(_spec(), GridSampler(None)))
    assert stored is not None
    assert stored.shards == ()
    assert heatmaps_equal(stored, hm)


# ---------------------------------------------------------------------------
# the on-disk tier
# ---------------------------------------------------------------------------


def test_disk_round_trip_survives_restart(tmp_path):
    first = CollectionCache(tmp_path / "cache")
    fresh = profile_kernel(_spec(), cache=first)
    # a new cache object over the same directory models a new process
    second = CollectionCache(tmp_path / "cache")
    pk = profile_kernel(_spec(), cache=second)
    assert pk.cached
    assert heatmaps_equal(pk.heatmap, fresh.heatmap)
    assert second.stats.disk_hits == 1
    # the disk hit was promoted: the next lookup is a memory hit
    profile_kernel(_spec(), cache=second)
    assert second.stats.memory_hits == 1


def test_cache_version_mismatch_is_a_miss(tmp_path):
    cache = CollectionCache(tmp_path / "cache")
    pk = profile_kernel(_spec(), cache=cache)
    npz_path, meta_path = cache._entry_paths(pk.cache_key)
    meta = json.loads(meta_path.read_text())
    meta["cache_version"] = CACHE_VERSION + 1
    meta_path.write_text(json.dumps(meta))
    stale = CollectionCache(tmp_path / "cache")
    assert stale.get(pk.cache_key) is None
    assert stale.stats.misses == 1


def test_corrupt_npz_is_a_miss(tmp_path):
    cache = CollectionCache(tmp_path / "cache")
    pk = profile_kernel(_spec(), cache=cache)
    npz_path, _meta = cache._entry_paths(pk.cache_key)
    npz_path.write_bytes(b"not an npz")
    broken = CollectionCache(tmp_path / "cache")
    assert broken.get(pk.cache_key) is None


def test_disk_layout_is_sharded_by_key_prefix(tmp_path):
    cache = CollectionCache(tmp_path / "cache")
    pk = profile_kernel(_spec(), cache=cache)
    key = pk.cache_key
    assert (tmp_path / "cache" / key[:2] / f"{key}.npz").is_file()
    meta = json.loads(
        (tmp_path / "cache" / key[:2] / f"{key}.json").read_text()
    )
    assert meta["format"] == "cuthermo-collection-cache"
    assert meta["key"] == key
    assert meta["provenance"]["python"]


# ---------------------------------------------------------------------------
# session + tuner integration
# ---------------------------------------------------------------------------


def test_session_threads_cache_through_profile(tmp_path):
    from repro.core.session import ProfileSession
    from repro.kernels.gemm import gemm_v00_spec

    with ProfileSession(
        tmp_path / "sess", cache=tmp_path / "cache"
    ) as sess:
        sess.profile([gemm_v00_spec(128, 128, 128)])
        sess.profile([gemm_v00_spec(128, 128, 128)])
        assert sess.cache.stats.hits >= 1
        assert sess.cache.stats.misses == 1


def test_tune_reuses_cached_traces(tmp_path):
    """A repeated tune run performs strictly fewer fresh traces than
    candidates tried — the cache-bounded loop the issue asks for."""
    from repro.core.tuner import tune

    cache = CollectionCache()
    tune("gramschm", budget=2, seed=0, cache=cache)
    before = cache.stats.misses
    res = tune("gramschm", budget=2, seed=0, cache=cache)
    fresh = cache.stats.misses - before
    assert fresh < len(res.steps) + 1  # +1: the baseline profile
    assert cache.stats.hits >= 1
