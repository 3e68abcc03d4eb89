"""Whole-model profiling: discovery, per-layer rollup.

Covers the ``repro.core.model_profile`` walker (the ``cuthermo model``
engine): per-layer discovery with source-stamped specs, the backward kind-swap model, the v5 layer-table
partition invariant (property-tested: for ANY partition of the profiled
kernels into layers, per-layer totals sum to the iteration total — and
``_validate_layers`` rejects everything that is not a partition), the
``model.<model>.<kind>`` registry bridge, and one end-to-end
``profile_model`` run persisting a v5 artifact.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import model_profile as mp
from repro.core.heatmap import Heatmap, RegionHeatmap
from repro.core.model_kernels import MODELS, get_model, kernel_kinds, kind_spec
from repro.core.session import (
    SessionError,
    _validate_layers,
    heatmaps_equal,
    load_iteration,
)
from repro.core.tiles import TileGeometry
from repro.core.trace import RegionInfo


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------


def test_discover_transformer_tiny_layers_and_stamps():
    entry = get_model("transformer-tiny")
    found = mp.discover(
        "transformer-tiny", entry.config, entry.batch, entry.seq
    )
    assert [(d.name, d.layer, d.kind) for d in found] == [
        ("layer0.attn", "layer0", "attn"),
        ("layer0.mlp", "layer0", "mlp"),
        ("layer1.attn", "layer1", "attn"),
        ("layer1.mlp", "layer1", "mlp"),
        ("head.unembed", "head", "unembed"),
    ]
    for d in found:
        assert d.family == f"model.transformer-tiny.{d.kind}"
        # default shapes: specs carry the registry string ref a shard
        # worker can rebuild from
        assert isinstance(d.spec.source, str)
        assert d.spec.source.startswith(d.family + ":")
        # the spec is exactly what the derivation builds
        want = kind_spec(entry.config, d.kind, entry.batch, entry.seq)
        assert d.spec.name == want.name
        assert d.spec.grid == want.grid


def test_discover_with_non_default_shapes_uses_builder_triples():
    entry = get_model("transformer-tiny")
    cfg = dataclasses.replace(entry.config, d_ff=512)
    found = mp.discover(
        "transformer-tiny", cfg, entry.batch, entry.seq,
        default_shapes=False,
    )
    for d in found:
        fn_ref, args, kwargs = d.spec.source
        assert fn_ref == "repro.core.model_kernels:kind_spec"
        assert args == (cfg, d.kind, entry.batch, entry.seq)
        assert kwargs == {"rung": 0}


def test_discover_backward_appends_kind_swapped_mirrors():
    entry = get_model("mamba-tiny")
    found = mp.discover(
        "mamba-tiny", entry.config, entry.batch, entry.seq, backward=True
    )
    fwd = [d for d in found if not d.backward]
    bwd = [d for d in found if d.backward]
    assert len(fwd) == len(bwd) == 3
    assert [d.name for d in bwd] == [f"{d.name}.bwd" for d in fwd]
    for f, b in zip(fwd, bwd):
        assert b.spec.name == f.spec.name + "_bwd"
        flipped = {"load": "store", "store": "load"}
        for fop, bop in zip(f.spec.operands, b.spec.operands):
            assert bop.kind == flipped.get(fop.kind, fop.kind), fop.name
        # backward specs rebuild through the module-level bwd_spec triple
        assert b.spec.source[0] == "repro.core.model_profile:bwd_spec"


def test_bwd_spec_preserves_scratch():
    entry = get_model("transformer-tiny")
    fwd = kind_spec(entry.config, "attn", entry.batch, entry.seq)
    bwd = mp.bwd_spec(entry.config, "attn", entry.batch, entry.seq)
    assert bwd.scratch == fwd.scratch  # accumulators are direction-free
    assert bwd.grid == fwd.grid


# ---------------------------------------------------------------------------
# the rollup partition invariant
# ---------------------------------------------------------------------------


def _fake_profiled(name, sector_temps):
    """A minimal ProfiledKernel whose transactions == sum(sector_temps)."""
    from repro.core.session import ProfiledKernel

    temps = np.asarray(sector_temps, dtype=np.int64)
    region = RegionHeatmap(
        RegionInfo(
            name="x",
            geometry=TileGeometry((16, 128), itemsize=4, name="x"),
            space="hbm",
        ),
        n_programs=1,
        tags=np.arange(temps.size, dtype=np.int64) * 8,
        word_temps=np.zeros((temps.size, 8), dtype=np.int64),
        sector_temps=temps,
    )
    hm = Heatmap(
        kernel=name, grid=(1,), sampler="full", regions=(region,),
        n_records=1, dropped=0,
    )
    return ProfiledKernel(
        name=name, variant="v00", heatmap=hm, reports=(), actions=()
    )


def _rows_from_partition(kernels, assignment):
    """Build a layer table from a kernel->layer assignment mapping."""
    rows = {}
    for pk in kernels:
        layer = assignment[pk.name]
        row = rows.setdefault(
            layer,
            {"path": layer, "kinds": [], "kernels": [], "transactions": 0,
             "patterns": []},
        )
        row["kernels"].append(pk.name)
        row["transactions"] += pk.transactions
    return list(rows.values())


def test_rollup_sums_to_iteration_total_for_any_partition():
    """Property: any partition of kernels into layers validates, and its
    per-layer totals sum exactly to the iteration total."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    given, settings = hypothesis.given, hypothesis.settings

    @settings(max_examples=50, deadline=None)
    @given(
        temps=st.lists(
            st.lists(st.integers(min_value=0, max_value=9), min_size=1,
                     max_size=4),
            min_size=1,
            max_size=6,
        ),
        layer_of=st.lists(st.integers(min_value=0, max_value=3), min_size=6,
                          max_size=6),
    )
    def _property(temps, layer_of):
        kernels = [
            _fake_profiled(f"k{i}", t) for i, t in enumerate(temps)
        ]
        assignment = {
            pk.name: f"layer{layer_of[i]}" for i, pk in enumerate(kernels)
        }
        table = _rows_from_partition(kernels, assignment)
        layers = {"model": "prop", "table": table}
        _validate_layers(layers, kernels)  # any true partition passes
        rollup = sum(row["transactions"] for row in table)
        assert rollup == sum(pk.transactions for pk in kernels)

    _property()


def test_rollup_partition_deterministic_fallback():
    # the hypothesis property, pinned on three fixed partitions so the
    # invariant stays covered when hypothesis is not installed
    kernels = [
        _fake_profiled("k0", [2, 3]),
        _fake_profiled("k1", [5]),
        _fake_profiled("k2", [1, 1, 1]),
    ]
    total = sum(pk.transactions for pk in kernels)
    assert total == 13
    partitions = [
        {"k0": "a", "k1": "a", "k2": "a"},  # everything in one layer
        {"k0": "a", "k1": "b", "k2": "c"},  # one kernel per layer
        {"k0": "a", "k1": "b", "k2": "a"},  # mixed
    ]
    for assignment in partitions:
        table = _rows_from_partition(kernels, assignment)
        _validate_layers({"table": table}, kernels)
        assert sum(row["transactions"] for row in table) == total


def test_validate_layers_rejects_non_partitions():
    kernels = [_fake_profiled("k0", [2]), _fake_profiled("k1", [3])]
    ok = _rows_from_partition(kernels, {"k0": "a", "k1": "a"})

    with pytest.raises(SessionError, match="'table'"):
        _validate_layers({}, kernels)
    with pytest.raises(SessionError, match="malformed layer row"):
        _validate_layers({"table": [{"path": "a"}]}, kernels)
    with pytest.raises(SessionError, match="not.*profiled"):
        bad = [dict(ok[0], kernels=["k0", "k1", "ghost"])]
        _validate_layers({"table": bad}, kernels)
    with pytest.raises(SessionError, match="both layer"):
        dup = [dict(ok[0]), dict(ok[0], path="b")]
        _validate_layers({"table": dup}, kernels)
    with pytest.raises(SessionError, match="sum to"):
        wrong = [dict(ok[0], transactions=99)]
        _validate_layers({"table": wrong}, kernels)
    with pytest.raises(SessionError, match="missing from the layer"):
        short = _rows_from_partition(kernels[:1], {"k0": "a"})
        _validate_layers({"table": short}, kernels)


def test_layers_table_matches_discovery_order():
    entry = get_model("transformer-tiny")
    found = mp.discover(
        "transformer-tiny", entry.config, entry.batch, entry.seq
    )
    profiled = [
        _fake_profiled(d.name, [i + 1]) for i, d in enumerate(found)
    ]
    table = mp.layers_table(found, profiled)
    assert [row["path"] for row in table] == ["layer0", "layer1", "head"]
    assert table[0]["kernels"] == ["layer0.attn", "layer0.mlp"]
    assert table[0]["kinds"] == ["attn", "mlp"]
    assert table[0]["transactions"] == 1 + 2
    _validate_layers({"table": table}, profiled)


# ---------------------------------------------------------------------------
# the model.<model>.<kind> registry bridge
# ---------------------------------------------------------------------------


def test_model_refs_resolve_through_kernel_registry():
    from repro import kernels as kreg

    entry = kreg.get("model.transformer-tiny.mlp")
    assert entry.name == "model.transformer-tiny.mlp"
    assert [v.role for v in entry.variants] == ["baseline", "optimized"]
    spec, ctx = kreg.build("model.transformer-tiny.mlp")
    assert ctx is None
    assert spec.source == "model.transformer-tiny.mlp:v01"
    # the optimized rung builds too, with its own stamp
    spec2, _ = kreg.build("model.transformer-tiny.mlp:v02")
    assert spec2.source == "model.transformer-tiny.mlp:v02"
    # model families are derived, not listed: the static registry
    # surface (tune --all's default scope) must not grow
    assert not any(n.startswith("model.") for n in kreg.names())


def test_model_refs_reject_unknowns():
    from repro import kernels as kreg

    with pytest.raises(KeyError):
        kreg.get("model.transformer-tiny")  # malformed: no kind
    with pytest.raises(KeyError):
        kreg.get("model.nope.mlp")  # unknown model
    with pytest.raises(KeyError):
        kreg.get("model.mamba-tiny.mlp")  # kind the layout doesn't use


def test_model_refs_lint_cleanly_enough_to_tune():
    # lint must accept model-derived refs (the tuner pre-screen relies
    # on it); statically priced, no kernel runs
    from repro.core.lint import lint_ref

    for ref in ("model.transformer-tiny.mlp:v01",
                "model.transformer-tiny.mlp:v02",
                "model.mamba-tiny.ssm:chunk"):
        rep = lint_ref(ref)
        assert rep.static_transactions is not None, ref
        assert not any(f.pattern == "nonaffine" for f in rep.findings), ref


def test_every_model_kind_has_a_ladder_improvement_or_single_rung():
    # the tune-acceptance precondition: for each registered model and
    # kind, the optimized rung (when one exists) strictly lowers the
    # statically priced transfer count
    from repro import kernels as kreg
    from repro.core.lint import lint_ref

    for model_name in MODELS:
        for kind in kernel_kinds(MODELS[model_name].config):
            entry = kreg.get(f"model.{model_name}.{kind}")
            costs = []
            for v in entry.variants:
                rep = lint_ref(f"{entry.name}:{v.name}")
                assert rep.static_transactions is not None
                costs.append(rep.static_transactions)
            if len(costs) > 1:
                assert min(costs[1:]) < costs[0], (model_name, kind, costs)


# ---------------------------------------------------------------------------
# end to end: profile_model persists a v5 artifact
# ---------------------------------------------------------------------------


def test_profile_model_end_to_end(tmp_path):
    it = mp.profile_model(
        "mamba-tiny", tmp_path / "sess", hlo=False
    )
    assert it.layers is not None
    assert it.layers["model"] == "mamba-tiny"
    assert "hlo" not in it.layers
    table = it.layers["table"]
    assert [row["path"] for row in table] == ["layer0", "layer1", "head"]
    rollup = sum(row["transactions"] for row in table)
    total = mp.iteration_transactions(it)
    assert rollup == total > 0
    # the artifact round-trips: reload and compare bit-for-bit
    again = load_iteration(it.path)
    assert again.layers == it.layers
    for a, b in zip(it.kernels, again.kernels):
        assert heatmaps_equal(a.heatmap, b.heatmap)
