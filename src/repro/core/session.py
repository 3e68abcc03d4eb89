"""Persistent profiling sessions: the paper's tuning loop as an artifact.

CUTHERMO's workflow (Fig. 2) is iterative — profile, read the heat map,
optimize, re-profile — and its headline results (up to 721.79% speedup)
come from *sequences* of such iterations.  This module makes that loop a
first-class, on-disk object:

* ``ProfileSession`` owns a session directory and appends numbered
  *iterations* (``iter0``, ``iter1``, ...).  One iteration profiles any
  number of kernels (``KernelSpec``s) and persists, per kernel, the full
  columnar heat map plus the derived pattern reports and advisor actions.
* The artifact format is versioned: each iteration directory holds one
  ``manifest.json`` (metadata, patterns, actions — readable without
  numpy) and one ``<kernel>.npz`` per kernel (the exact ``int64``
  temperature arrays).  Reloading reproduces bit-identical temperatures;
  loading a manifest stamped with an unknown version fails loudly.
* ``ProfileSession.diff`` aligns two iterations kernel-by-kernel through
  :mod:`repro.core.diff` and emits per-kernel verdicts (improved /
  regressed / unchanged / added / removed) — the artifact a tuning
  iteration reviews before the next change.

Layout on disk (see ``docs/file-format.md``)::

    sess/
      session.json          # {"format": "cuthermo-session", "version": 6,
                            #  "iterations": ["iter0", "iter1"]}
      iter0/
        manifest.json       # version stamp + per-kernel metadata
        gemm.npz            # r{i}_tags / r{i}_word_temps / r{i}_sector_temps
      iter1/ ...

Writes are *crash safe*: every file of an iteration is committed
atomically (temp + fsync + rename) under a journal sidecar, so a kill
at any instant leaves either a complete iteration, a completable one
(everything durable, only the final manifest rename missing), or a torn
one that :meth:`ProfileSession.recover` quarantines — never a directory
that half-loads.  See ``docs/robustness.md``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from pathlib import Path
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from .advisor import Action, advise
from .cache import CacheKeyError, CollectionCache, spec_content_hash
from .collector import ShardedCollector, analyze
from .diff import HeatmapDiff, diff as diff_heatmaps
from .heatmap import Heatmap, RegionHeatmap
from .patterns import PatternReport, detect_all
from .render import dedupe_stem, slugify
from .resilience import FaultEvent
from .spec import KernelSpec
from .tiles import TileGeometry
from .trace import GridSampler, RegionInfo, ShardInfo

#: Version stamp written into every manifest.  Bump on any change to the
#: npz key layout or the manifest schema; loaders reject versions they
#: do not know how to read.
#:
#: v1  (PR 2) initial format
#: v2  (sharded collection) adds optional per-shard provenance to each
#:     kernel's heatmap metadata ("shards": [{shard, lo, hi, programs,
#:     records, dropped, wall_s}, ...]).  Backward compatible on read:
#:     v1 artifacts simply load with empty shard provenance.
#: v3  (autotuner) adds an optional top-level "tuning" mapping to the
#:     iteration manifest: which tuning step this iteration is, and
#:     which advisor Action spawned which candidate (see
#:     ``repro.core.tuner`` and docs/file-format.md).  Backward
#:     compatible on read: v1/v2 artifacts load with no tuning
#:     provenance.
#: v4  (regression gating) adds the derived "scratch_words" metric to
#:     each kernel entry so manifest-only consumers (session history
#:     queries, ``cuthermo check`` anomaly bands) can track scratch
#:     growth without loading the arrays.  Backward compatible on read:
#:     v1-v3 entries load with the metric absent (``None`` in history
#:     points; recomputed from the arrays by full loads).  The v1/v2/v3
#:     load paths are pinned by the golden fixtures under
#:     ``tests/fixtures/``.
#: v5  (whole-model profiling) adds an optional top-level "layers"
#:     mapping to the iteration manifest: per-layer attribution of the
#:     iteration's kernels ({"model": name, "table": [{"path",
#:     "kernels", "transactions", ...}], "hlo": {...}}), written by
#:     ``cuthermo model`` / ``repro.core.model_profile``.  The table is
#:     validated on write as an exact partition — every kernel in
#:     exactly one row, each row's transactions equal to the sum over
#:     its member kernels — so per-layer totals always sum to the
#:     iteration total by construction.  Backward compatible on read:
#:     v1-v4 artifacts load with ``Iteration.layers`` = None (layer
#:     attribution absent, not an error).
#: v6  (fault tolerance) adds recovery provenance: each kernel's
#:     heatmap metadata gains a "faults" list (structured FaultEvent
#:     records of every recovery the collection performed — worker
#:     crashes survived, hung shards expired, retries, pool rebuilds),
#:     and iterations whose collections recovered carry a top-level
#:     "faults" block ([{... , "kernel": name}, ...]) so manifest-only
#:     consumers can see at a glance that a run was degraded.  Fault
#:     events are provenance, not state: a recovered heat map is
#:     bit-identical to the clean one (set-union merge algebra) and
#:     equality/diff ignore them.  Backward compatible on read: v1-v5
#:     artifacts load with empty fault provenance.
ARTIFACT_VERSION = 6

#: Versions this build can load.  v1 lacks shard provenance, v2 lacks
#: tuning provenance, v3 lacks the scratch_words manifest metric, v4
#: lacks per-layer attribution, v5 lacks fault provenance; all are
#: otherwise identical and load with the missing fields empty.  Writers
#: always stamp ARTIFACT_VERSION.
SUPPORTED_VERSIONS = (1, 2, 3, 4, 5, 6)

SESSION_FORMAT = "cuthermo-session"
ITERATION_FORMAT = "cuthermo-iteration"


class SessionError(RuntimeError):
    """Raised for malformed, missing, or version-incompatible artifacts."""


# ---------------------------------------------------------------------------
# heat-map (de)serialization
# ---------------------------------------------------------------------------


def heatmap_to_arrays(hm: Heatmap) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Split a Heatmap into (JSON-ready metadata, named int64 arrays).

    The arrays carry the exact columnar state of every region
    (``r{i}_tags``, ``r{i}_word_temps``, ``r{i}_sector_temps``); the
    metadata dict carries everything needed to rebuild ``RegionInfo``
    geometry.  ``arrays_to_heatmap`` inverts this losslessly.
    """
    meta = {
        "kernel": hm.kernel,
        "grid": list(hm.grid),
        "sampler": hm.sampler,
        "n_records": hm.n_records,
        "dropped": hm.dropped,
        # per-shard collection provenance (v2; empty for serial builds)
        "shards": [s.as_dict() for s in hm.shards],
        # recovery provenance (v6; empty for clean collections)
        "faults": [e.as_dict() for e in hm.faults],
        "regions": [],
    }
    arrays: Dict[str, np.ndarray] = {}
    for i, rh in enumerate(hm.regions):
        geom = rh.region.geometry
        meta["regions"].append(
            {
                "name": rh.region.name,
                "space": rh.region.space,
                "shape": list(geom.shape),
                "itemsize": geom.itemsize,
                "n_programs": rh.n_programs,
            }
        )
        arrays[f"r{i}_tags"] = rh.tags_array
        arrays[f"r{i}_word_temps"] = rh.word_temps_matrix
        arrays[f"r{i}_sector_temps"] = rh.sector_temps_array
    return meta, arrays


def arrays_to_heatmap(meta: Mapping, arrays: Mapping[str, np.ndarray]) -> Heatmap:
    """Rebuild a Heatmap from ``heatmap_to_arrays`` output (exact inverse)."""
    regions: List[RegionHeatmap] = []
    for i, rmeta in enumerate(meta["regions"]):
        geom = TileGeometry(
            shape=tuple(int(s) for s in rmeta["shape"]),
            itemsize=int(rmeta["itemsize"]),
            name=rmeta["name"],
        )
        info = RegionInfo(rmeta["name"], geom, space=rmeta["space"])
        regions.append(
            RegionHeatmap(
                region=info,
                n_programs=int(rmeta["n_programs"]),
                tags=np.asarray(arrays[f"r{i}_tags"], dtype=np.int64),
                word_temps=np.asarray(
                    arrays[f"r{i}_word_temps"], dtype=np.int64
                ),
                sector_temps=np.asarray(
                    arrays[f"r{i}_sector_temps"], dtype=np.int64
                ),
            )
        )
    return Heatmap(
        kernel=meta["kernel"],
        grid=tuple(int(g) for g in meta["grid"]),
        sampler=meta["sampler"],
        regions=tuple(regions),
        n_records=int(meta["n_records"]),
        dropped=int(meta["dropped"]),
        # v1 manifests carry no shard provenance: loads as unsharded
        shards=tuple(
            ShardInfo.from_dict(d) for d in meta.get("shards", [])
        ),
        # pre-v6 manifests carry no fault provenance: loads as clean
        faults=tuple(
            FaultEvent.from_dict(d) for d in meta.get("faults", [])
        ),
    )


def heatmaps_equal(a: Heatmap, b: Heatmap) -> bool:
    """True when two heat maps carry bit-identical temperature state."""
    if (
        a.kernel != b.kernel
        or a.grid != b.grid
        or a.sampler != b.sampler
        or a.n_records != b.n_records
        or a.dropped != b.dropped
        or a.region_names() != b.region_names()
    ):
        return False
    for ra, rb in zip(a.regions, b.regions):
        if (
            ra.region != rb.region
            or ra.n_programs != rb.n_programs
            or not np.array_equal(ra.tags_array, rb.tags_array)
            or not np.array_equal(ra.word_temps_matrix, rb.word_temps_matrix)
            or not np.array_equal(
                ra.sector_temps_array, rb.sector_temps_array
            )
        ):
            return False
    return True


# ---------------------------------------------------------------------------
# iteration records
# ---------------------------------------------------------------------------


def profile_kernel(
    spec: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Mapping[str, np.ndarray]] = None,
    *,
    name: Optional[str] = None,
    variant: Optional[str] = None,
    region_map: Sequence[Tuple[str, str]] = (),
    workers: int = 1,
    collector: Optional[ShardedCollector] = None,
    cache: Optional[CollectionCache] = None,
) -> "ProfiledKernel":
    """Profile one spec into a ProfiledKernel (the single assembly point).

    Runs collect+analyze under the given sampler (full-grid by default,
    see :meth:`ProfileSession.profile`), derives patterns and actions,
    and stamps the wall time.  ``name`` defaults to the spec's own name;
    every profiling entry point (session, CLI, examples) goes through
    here so the derivation never diverges.

    ``collector`` (a :class:`~repro.core.collector.ShardedCollector`,
    reusable across kernels) or ``workers > 1`` routes collection
    through the sharded path; the heat map is bit-identical either way,
    and the sharded one carries per-shard provenance that the session
    artifact persists.

    ``cache`` (a :class:`~repro.core.cache.CollectionCache`) makes the
    collection content-addressed: the spec+sampler+context are hashed
    (:func:`~repro.core.cache.spec_content_hash`), a hit skips the grid
    walk and returns the cached heat map (bit-identical to fresh
    collection; no shard provenance — the cache stores the canonical
    path-independent form), a miss collects and stores.  Specs whose
    callables cannot be content-hashed profile uncached.
    """
    sampler = sampler or GridSampler(None)
    t0 = time.perf_counter()
    key = ""
    hm = None
    cached = False
    if cache is not None:
        try:
            key = spec_content_hash(spec, sampler, dynamic_context)
        except CacheKeyError:
            cache.note_uncacheable()
        else:
            hm = cache.get(key)
            cached = hm is not None
    if hm is None:
        if collector is not None:
            hm = collector.analyze(spec, sampler, dynamic_context)
        elif workers > 1:
            with ShardedCollector(workers) as sc:
                hm = sc.analyze(spec, sampler, dynamic_context)
        else:
            hm = analyze(
                spec, sampler=sampler, dynamic_context=dynamic_context
            )
        # a truncated trace is not a pure function of the spec (record
        # admission depends on the collection path) — never cache it
        if cache is not None and key and hm.dropped == 0:
            cache.put(key, hm)
    wall = time.perf_counter() - t0
    return ProfiledKernel(
        name=name or spec.name,
        variant=variant or spec.name,
        heatmap=hm,
        reports=tuple(detect_all(hm)),
        actions=tuple(advise(hm)),
        wall_s=wall,
        region_map=tuple(region_map),
        cached=cached,
        cache_key=key,
    )


@dataclasses.dataclass(frozen=True)
class ProfiledKernel:
    """One kernel's results inside an iteration (heat map + derived views)."""

    name: str  # registry/display name (manifest key, unique per iteration)
    variant: str
    heatmap: Heatmap
    reports: Tuple[PatternReport, ...]
    actions: Tuple[Action, ...]
    wall_s: float = 0.0
    # known region renames an optimization of this kernel performs
    # (e.g. q -> qT); persisted so later diffs align automatically
    region_map: Tuple[Tuple[str, str], ...] = ()
    # collection-cache provenance: True when the heat map came from a
    # CollectionCache hit (no grid walk, no shard provenance); the key
    # is the spec's content hash ("" when profiled without a cache or
    # the spec was uncacheable)
    cached: bool = False
    cache_key: str = ""

    @property
    def shards(self) -> Tuple[ShardInfo, ...]:
        """Per-shard collection provenance (empty for serial profiles)."""
        return self.heatmap.shards

    @property
    def transactions(self) -> int:
        """Modeled HBM<->VMEM tile transfers of this kernel's heat map."""
        return self.heatmap.sector_transactions()

    @property
    def waste_ratio(self) -> float:
        """Moved/demanded words of this kernel's heat map (1.0 = perfect)."""
        return self.heatmap.waste_ratio()

    @property
    def scratch_words(self) -> int:
        """Word touches on this kernel's VMEM-scratch regions."""
        return self.heatmap.scratch_words()


@dataclasses.dataclass(frozen=True)
class Iteration:
    """One loaded tuning iteration: a label plus its profiled kernels."""

    path: Path
    label: str
    created: float
    kernels: Tuple[ProfiledKernel, ...]
    note: str = ""
    # v3 tuning provenance: which autotuner step produced this iteration
    # and which advisor Action spawned the candidate (None when the
    # iteration was not written by the tuner)
    tuning: Optional[Mapping] = None
    # v5 per-layer attribution (None when the iteration was not written
    # by whole-model profiling, and for every pre-v5 artifact)
    layers: Optional[Mapping] = None
    # v6 recovery provenance: the manifest's top-level "faults" block —
    # one entry per FaultEvent with the owning kernel's name attached
    # (empty for clean collections and every pre-v6 artifact)
    faults: Tuple[Mapping, ...] = ()

    def kernel(self, name: str) -> ProfiledKernel:
        """Look up one profiled kernel by manifest name."""
        for pk in self.kernels:
            if pk.name == name:
                return pk
        raise KeyError(name)

    def kernel_names(self) -> List[str]:
        """Manifest names of every kernel profiled in this iteration."""
        return [pk.name for pk in self.kernels]


@dataclasses.dataclass(frozen=True)
class KernelVerdict:
    """Per-kernel outcome of diffing two iterations."""

    kernel: str
    verdict: str  # 'improved' | 'regressed' | 'unchanged' | 'added' | 'removed'
    diff: Optional[HeatmapDiff] = None

    @property
    def speedup_estimate(self) -> float:
        """Modeled transaction speedup (1.0 when not comparable)."""
        return self.diff.speedup_estimate if self.diff else 1.0


@dataclasses.dataclass(frozen=True)
class SessionDiff:
    """Kernel-aligned diff of two iterations."""

    before_label: str
    after_label: str
    verdicts: Tuple[KernelVerdict, ...]

    @property
    def regressed(self) -> Tuple[KernelVerdict, ...]:
        """Verdicts whose kernels regressed between the two iterations."""
        return tuple(v for v in self.verdicts if v.verdict == "regressed")

    @property
    def improved(self) -> Tuple[KernelVerdict, ...]:
        """Verdicts whose kernels improved between the two iterations."""
        return tuple(v for v in self.verdicts if v.verdict == "improved")

    def summary(self) -> str:
        """Multi-line human-readable summary (the ``cuthermo diff`` body)."""
        lines = [
            f"== session diff: {self.before_label} -> {self.after_label} =="
        ]
        for v in self.verdicts:
            if v.diff is None:
                lines.append(f"[{v.verdict:>9}] {v.kernel}")
                continue
            d = v.diff
            lines.append(
                f"[{v.verdict:>9}] {v.kernel}: transfers "
                f"{d.tx_before} -> {d.tx_after} ({d.speedup_estimate:.2f}x)"
            )
            for tag, items in (
                ("fixed", d.fixed),
                ("INTRODUCED", d.introduced),
                ("persisting", d.persisting),
            ):
                for region, pattern in items:
                    lines.append(f"      [{tag}] {pattern} on {region}")
        n_imp, n_reg = len(self.improved), len(self.regressed)
        lines.append(
            f"{len(self.verdicts)} kernels compared: "
            f"{n_imp} improved, {n_reg} regressed"
        )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# manifest-level history (the anomaly-band substrate)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HistoryPoint:
    """One kernel's manifest-level metrics in one session iteration.

    Built from ``manifest.json`` alone — no numpy arrays are loaded —
    so history queries over long-running sessions (hundreds of
    iterations) stay cheap.  ``scratch_words`` is ``None`` for
    artifacts written before format v4; consumers must skip the metric
    rather than assume zero.  ``tuning_role`` / ``tuning_accepted``
    carry the iteration's autotuner provenance so rolling-history
    consumers (``cuthermo check --anomaly``) can exclude deliberately
    bad candidates the tuner already rejected.
    """

    iteration: str
    label: str
    created: float
    kernel: str
    variant: str
    transactions: int
    waste_ratio: float
    patterns: Tuple[Tuple[str, str], ...]  # (region, pattern), sorted
    scratch_words: Optional[int] = None
    tuning_role: Optional[str] = None  # 'baseline' | 'candidate' | None
    tuning_accepted: Optional[bool] = None

    @property
    def n_patterns(self) -> int:
        """Count of detected inefficiency patterns at this point."""
        return len(self.patterns)


def _history_points_from_manifest(
    manifest: Mapping, iteration: str
) -> List[HistoryPoint]:
    """Extract one HistoryPoint per kernel entry of a loaded manifest."""
    tuning = manifest.get("tuning") or {}
    points: List[HistoryPoint] = []
    for entry in manifest.get("kernels", []):
        try:
            patterns = tuple(
                sorted(
                    (str(p.get("region", "")), str(p.get("pattern", "")))
                    for p in entry.get("patterns", [])
                )
            )
            scratch = entry.get("scratch_words")
            points.append(
                HistoryPoint(
                    iteration=iteration,
                    label=str(manifest.get("label", iteration)),
                    created=float(manifest.get("created", 0.0)),
                    kernel=str(entry["name"]),
                    variant=str(entry.get("variant", "")),
                    transactions=int(entry.get("transactions", 0)),
                    waste_ratio=float(entry.get("waste_ratio", 1.0)),
                    patterns=patterns,
                    scratch_words=None if scratch is None else int(scratch),
                    tuning_role=tuning.get("role"),
                    tuning_accepted=tuning.get("accepted"),
                )
            )
        except (KeyError, TypeError, ValueError) as e:
            raise SessionError(
                f"{iteration}: malformed kernel entry in manifest ({e!r})"
            ) from e
    return points


# ---------------------------------------------------------------------------
# on-disk writers / readers
# ---------------------------------------------------------------------------


def _check_version(manifest: Mapping, path: Path) -> None:
    version = manifest.get("version")
    if version not in SUPPORTED_VERSIONS:
        supported = ", ".join(str(v) for v in SUPPORTED_VERSIONS)
        raise SessionError(
            f"{path}: unsupported artifact version {version!r}; this build "
            f"reads versions {supported} and writes {ARTIFACT_VERSION}.  "
            "Re-profile with this version of cuthermo (or load with the "
            "version that wrote it)."
        )


def _validate_layers(
    layers: Mapping, kernels: Sequence[ProfiledKernel]
) -> None:
    """Validate v5 per-layer attribution against the iteration's kernels.

    The layer table must be an exact partition: every profiled kernel
    appears in exactly one row, every row references only profiled
    kernels, and each row's ``transactions`` equals the sum over its
    members — which makes "per-layer totals sum to the iteration total"
    an invariant of the artifact, not a property a reader must check.
    """
    table = layers.get("table")
    if not isinstance(table, (list, tuple)):
        raise SessionError(
            "layers attribution needs a 'table' list of rows"
        )
    tx_by_name = {pk.name: pk.transactions for pk in kernels}
    seen: Dict[str, str] = {}
    for row in table:
        try:
            path_ = str(row["path"])
            members = list(row["kernels"])
            row_tx = int(row["transactions"])
        except (KeyError, TypeError, ValueError) as e:
            raise SessionError(
                f"malformed layer row ({e!r}); every row needs 'path', "
                "'kernels' and 'transactions'"
            ) from e
        total = 0
        for name in members:
            if name not in tx_by_name:
                raise SessionError(
                    f"layer {path_!r} references kernel {name!r} not "
                    "profiled in this iteration"
                )
            if name in seen:
                raise SessionError(
                    f"kernel {name!r} attributed to both layer "
                    f"{seen[name]!r} and {path_!r}; the layer table must "
                    "partition the iteration's kernels"
                )
            seen[name] = path_
            total += tx_by_name[name]
        if total != row_tx:
            raise SessionError(
                f"layer {path_!r} claims {row_tx} transactions but its "
                f"kernels sum to {total}"
            )
    missing = sorted(set(tx_by_name) - set(seen))
    if missing:
        raise SessionError(
            f"kernel(s) {missing} profiled but missing from the layer "
            "table; the layer table must partition the iteration's kernels"
        )


#: Name of the write-in-progress journal sidecar inside an iteration
#: directory.  It exists from the first byte of an iteration write to
#: after the manifest commit; a directory holding one was torn by a
#: crash (or is being written right now by another process) and is the
#: input to :meth:`ProfileSession.recover`.
JOURNAL_NAME = ".journal.json"

#: Hooks called around every atomic file commit of an iteration write:
#: ``hook(path, event)`` with ``event`` = ``"staged"`` (the temp file is
#: durable, the rename has not happened) or ``"committed"`` (renamed
#: into place).  The fault-injection harness installs
#: :class:`repro.core.faultinject.WriteKillPoint` here to model
#: ``kill -9`` at exact points of the commit sequence; production code
#: leaves the list empty.
_write_commit_hooks: List = []


def _notify_hooks(path: Path, event: str) -> None:
    for hook in list(_write_commit_hooks):
        hook(path, event)


def _commit_bytes(path: Path, data: bytes, *, notify: bool = True) -> None:
    """Atomically commit ``data`` at ``path`` (temp + fsync + rename).

    After this returns, ``path`` holds the complete new content; if the
    process dies at any instant, ``path`` holds either its complete old
    content or nothing — never a prefix.  The temp file is
    ``<name>.tmp`` *in the same directory* (rename must not cross
    filesystems), which is what :meth:`ProfileSession.recover` looks
    for when completing a write that died between fsync and rename.
    """
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    if notify:
        _notify_hooks(path, "staged")
    os.replace(tmp, path)
    if notify:
        _notify_hooks(path, "committed")


def _commit_json(path: Path, obj: Mapping, *, notify: bool = True) -> None:
    _commit_bytes(
        path, json.dumps(obj, indent=2).encode("utf-8"), notify=notify
    )


def _commit_npz(path: Path, arrays: Mapping[str, np.ndarray]) -> None:
    import io

    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    _commit_bytes(path, buf.getvalue())


def write_iteration(
    path: Union[str, Path],
    kernels: Sequence[ProfiledKernel],
    label: Optional[str] = None,
    note: str = "",
    tuning: Optional[Mapping] = None,
    *,
    layers: Optional[Mapping] = None,
) -> Path:
    """Persist one iteration (manifest.json + one npz per kernel).

    ``path`` is created (parents included); an existing manifest there is
    overwritten — iterations are append-only at the *session* level, but
    re-profiling into the same directory is allowed and replaces it.

    Kernel names must be unique within an iteration (they are the
    alignment keys of ``Iteration.kernel`` and cross-iteration diffs);
    duplicates raise :class:`SessionError` instead of silently shadowing
    each other.

    ``tuning`` is the optional v3 autotuner provenance mapping (must be
    JSON-serializable; see ``repro.core.tuner`` for the shape) stored
    verbatim under the manifest's ``tuning`` key.  ``layers`` is the
    optional v5 per-layer attribution mapping; its table is validated
    as an exact partition of ``kernels`` (see :func:`_validate_layers`)
    and stored under the manifest's ``layers`` key.

    The write is crash safe: a :data:`JOURNAL_NAME` sidecar is committed
    first, every npz and the manifest are committed atomically (temp +
    fsync + rename, manifest last), and the journal is removed only
    after the manifest rename.  A kill at any instant therefore leaves
    the journal pointing at a directory that
    :meth:`ProfileSession.recover` can classify exactly: complete
    (journal removal lost), completable (all content durable, manifest
    rename lost), or torn (quarantine).
    """
    path = Path(path)
    if layers is not None:
        _validate_layers(layers, kernels)
    names_seen = [pk.name for pk in kernels]
    dupes = sorted({n for n in names_seen if names_seen.count(n) > 1})
    if dupes:
        raise SessionError(
            f"duplicate kernel name(s) {dupes} in one iteration; kernel "
            "names are alignment keys and must be unique (disambiguate "
            "with e.g. 'gemm:v00' / 'gemm:v01')"
        )
    path.mkdir(parents=True, exist_ok=True)
    label = label or path.name
    # plan the write up front so the journal can name every file the
    # recovery pass should expect
    seen: Dict[str, int] = {}
    stems = [dedupe_stem(slugify(pk.name), seen) for pk in kernels]
    journal = {
        "format": "cuthermo-journal",
        "version": ARTIFACT_VERSION,
        "label": label,
        "npz": [f"{stem}.npz" for stem in stems],
    }
    _commit_json(path / JOURNAL_NAME, journal, notify=False)
    entries = []
    fault_block: List[dict] = []
    for stem, pk in zip(stems, kernels):
        meta, arrays = heatmap_to_arrays(pk.heatmap)
        npz_name = f"{stem}.npz"
        _commit_npz(path / npz_name, arrays)
        for ev in pk.heatmap.faults:
            fault_block.append(dict(ev.as_dict(), kernel=pk.name))
        entries.append(
            {
                "name": pk.name,
                "variant": pk.variant,
                "npz": npz_name,
                "wall_s": pk.wall_s,
                "transactions": pk.transactions,
                "waste_ratio": pk.waste_ratio,
                # v4: manifest-only consumers (history queries, anomaly
                # bands) read this without touching the arrays
                "scratch_words": pk.scratch_words,
                "heatmap": meta,
                "region_map": {old: new for old, new in pk.region_map},
                # derived views, stored for numpy-free consumers; loaders
                # recompute them from the arrays (single source of truth)
                "patterns": [r.as_dict() for r in pk.reports],
                "actions": [a.as_dict() for a in pk.actions],
            }
        )
    manifest = {
        "format": ITERATION_FORMAT,
        "version": ARTIFACT_VERSION,
        "label": label,
        "note": note,
        "created": time.time(),
        "kernels": entries,
    }
    if fault_block:
        # v6: manifest-only consumers see degraded runs without loading
        # the per-kernel heatmap metadata
        manifest["faults"] = fault_block
    if tuning is not None:
        manifest["tuning"] = dict(tuning)
    if layers is not None:
        manifest["layers"] = dict(layers)
    _commit_json(path / "manifest.json", manifest)
    (path / JOURNAL_NAME).unlink(missing_ok=True)
    return path


def load_iteration(path: Union[str, Path]) -> Iteration:
    """Load one iteration directory back into memory.

    Raises :class:`SessionError` when the directory has no manifest or the
    manifest's version stamp is not :data:`ARTIFACT_VERSION`.  Pattern
    reports and advisor actions are *recomputed* from the reloaded arrays
    (they are pure functions of the heat map), which doubles as an
    integrity check: a corrupted npz cannot silently keep stale verdicts.
    """
    path = Path(path)
    mpath = path / "manifest.json"
    if not mpath.is_file():
        raise SessionError(
            f"{path}: not an iteration directory (no manifest.json)"
        )
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SessionError(f"{mpath}: unreadable manifest ({e})") from e
    if manifest.get("format") not in (None, ITERATION_FORMAT):
        raise SessionError(
            f"{mpath}: format {manifest.get('format')!r} is not "
            f"{ITERATION_FORMAT!r}"
        )
    _check_version(manifest, mpath)
    kernels: List[ProfiledKernel] = []
    for entry in manifest.get("kernels", []):
        # a syntactically-valid manifest can still be malformed (missing
        # keys, wrong types); that is a LOAD error (SessionError -> CLI
        # exit 2), never an uncaught traceback that a CI gate would
        # mistake for a regression verdict (exit 1)
        try:
            npz_path = path / entry["npz"]
        except (KeyError, TypeError) as e:
            raise SessionError(
                f"{mpath}: malformed kernel entry ({e!r}); every entry "
                "needs at least 'name' and 'npz'"
            ) from e
        if not npz_path.is_file():
            raise SessionError(f"{npz_path}: referenced by manifest, missing")
        try:
            with np.load(npz_path) as data:
                hm = arrays_to_heatmap(entry["heatmap"], data)
        except SessionError:
            raise
        except Exception as e:  # corrupt npz / missing keys / bad metadata
            raise SessionError(
                f"{npz_path}: corrupt or inconsistent artifact ({e})"
            ) from e
        try:
            kernels.append(
                ProfiledKernel(
                    name=entry["name"],
                    variant=entry.get("variant", ""),
                    heatmap=hm,
                    reports=tuple(detect_all(hm)),
                    actions=tuple(advise(hm)),
                    wall_s=float(entry.get("wall_s", 0.0)),
                    region_map=tuple(
                        sorted(entry.get("region_map", {}).items())
                    ),
                )
            )
        except (KeyError, TypeError, ValueError, AttributeError) as e:
            raise SessionError(
                f"{mpath}: malformed kernel entry ({e!r})"
            ) from e
    return Iteration(
        path=path,
        label=manifest.get("label", path.name),
        created=float(manifest.get("created", 0.0)),
        kernels=tuple(kernels),
        note=manifest.get("note", ""),
        # v1/v2 manifests carry no tuning key: loads as a plain iteration
        tuning=manifest.get("tuning"),
        # pre-v5 manifests carry no layers key: attribution absent
        layers=manifest.get("layers"),
        # pre-v6 manifests carry no faults block: clean collection
        faults=tuple(manifest.get("faults", [])),
    )


def _effective_region_map(
    rename: Mapping[str, str], before_hm: Heatmap, after_hm: Heatmap
) -> Dict[str, str]:
    """Keep only renames that actually apply to this pair of heat maps.

    A stored rename like ``q -> qT`` must be a no-op when diffing two
    un-renamed profiles (both sides still have ``q``) or two already-
    renamed ones (both have ``qT``): applying it blindly would orphan
    regions or mislabel patterns.  A rename is live only when the before
    side has the old name and the after side has the new name but not
    the old one.
    """
    before = set(before_hm.region_names())
    after = set(after_hm.region_names())
    return {
        old: new
        for old, new in rename.items()
        if old in before and new in after and old not in after
    }


def diff_iterations(
    before: Iteration,
    after: Iteration,
    region_maps: Optional[Mapping[str, Mapping[str, str]]] = None,
) -> SessionDiff:
    """Align two iterations kernel-by-kernel and attach verdicts.

    Kernels are matched by manifest name; region renames (an optimization
    often renames buffers, e.g. ``q`` -> ``qT``) come from each before-
    kernel's persisted ``region_map``, overridable per kernel through the
    ``region_maps`` argument, and are applied only where the after side
    actually carries the renamed region.  Kernels present on only one
    side get 'added' / 'removed' verdicts instead of a heat-map diff.
    """
    region_maps = region_maps or {}
    verdicts: List[KernelVerdict] = []
    after_names = set(after.kernel_names())
    for pk in before.kernels:
        if pk.name not in after_names:
            verdicts.append(KernelVerdict(kernel=pk.name, verdict="removed"))
            continue
        after_pk = after.kernel(pk.name)
        rename = region_maps.get(pk.name)
        if rename is None:
            rename = dict(pk.region_map)
        d = diff_heatmaps(
            pk.heatmap,
            after_pk.heatmap,
            region_map=_effective_region_map(
                rename, pk.heatmap, after_pk.heatmap
            ),
        )
        verdicts.append(
            KernelVerdict(kernel=pk.name, verdict=d.verdict, diff=d)
        )
    before_names = set(before.kernel_names())
    for pk in after.kernels:
        if pk.name not in before_names:
            verdicts.append(KernelVerdict(kernel=pk.name, verdict="added"))
    return SessionDiff(
        before_label=before.label,
        after_label=after.label,
        verdicts=tuple(verdicts),
    )


# ---------------------------------------------------------------------------
# the session object
# ---------------------------------------------------------------------------

_ITER_RE = re.compile(r"^iter(\d+)$")


class ProfileSession:
    """A directory of numbered tuning iterations (the paper's Fig. 2 loop).

    Typical use::

        sess = ProfileSession("sess/")
        sess.profile([gemm_v00_spec(1024, 1024, 1024)])   # -> sess/iter0
        # ... optimize the kernel ...
        sess.profile([gemm_v01_spec(1024, 1024, 1024)],
                     names={"gemm_v01": "gemm_v00"})      # -> sess/iter1
        print(sess.diff(0, 1).summary())

    Iterations are append-only: each ``profile`` call creates the next
    ``iterN`` directory.  Everything is reloadable by any later process
    (and by the ``cuthermo`` CLI) from the directory alone.
    """

    def __init__(
        self,
        root: Union[str, Path],
        create: bool = True,
        workers: int = 1,
        cache: Union[None, str, Path, CollectionCache] = None,
        fault_plan=None,
    ):
        """Open (and by default create) the session at ``root``.

        ``workers > 1`` collects every subsequent :meth:`profile` call
        through ONE sharded process pool that persists across the
        session's profile/tune calls (spawn + import paid once; close
        it with :meth:`close` or use the session as a context manager).
        Results are bit-identical to serial profiling; the artifacts
        additionally record per-shard provenance.

        ``cache`` backs every profile with a content-addressed
        :class:`~repro.core.cache.CollectionCache`: pass an existing
        cache, or a directory path to create an on-disk one.  Unchanged
        kernels and repeated tuner candidates then return bit-identical
        cached heat maps instead of re-tracing.

        ``fault_plan`` (a :class:`repro.core.faultinject.FaultPlan`)
        threads deterministic fault injection into every sharded
        collector this session creates — the ``--inject-faults`` wiring.
        """
        self.workers = max(1, int(workers))
        self.fault_plan = fault_plan
        if cache is None or isinstance(cache, CollectionCache):
            self.cache = cache
        else:
            self.cache = CollectionCache(cache)
        self._collector: Optional[ShardedCollector] = None
        self.root = Path(root)
        spath = self.root / "session.json"
        if spath.is_file():
            try:
                with open(spath) as f:
                    manifest = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                raise SessionError(
                    f"{spath}: unreadable session manifest ({e})"
                ) from e
            if manifest.get("format") != SESSION_FORMAT:
                raise SessionError(
                    f"{spath}: format {manifest.get('format')!r} is not "
                    f"{SESSION_FORMAT!r}"
                )
            _check_version(manifest, spath)
        elif create:
            self.root.mkdir(parents=True, exist_ok=True)
            self._write_session_manifest([])
        else:
            raise SessionError(f"{self.root}: no session.json (create=False)")

    # -- collector lifecycle -----------------------------------------------
    def collector(
        self, workers: Optional[int] = None
    ) -> Optional[ShardedCollector]:
        """The session's persistent shard pool (None when serial).

        Lazily created on first use and reused by every subsequent
        profile/tune call — re-profiling a candidate no longer pays a
        pool spin-up.  Asking for a different worker count replaces the
        pool.  Callers must not close the returned collector; the
        session owns it (:meth:`close`).
        """
        n = self.workers if workers is None else max(1, int(workers))
        if n <= 1:
            return None
        if self._collector is None or self._collector.workers != n:
            if self._collector is not None:
                self._collector.close()
            self._collector = ShardedCollector(n, fault_plan=self.fault_plan)
        return self._collector

    def close(self) -> None:
        """Shut down the session's persistent shard pool (idempotent)."""
        if self._collector is not None:
            self._collector.close()
            self._collector = None

    def __enter__(self) -> "ProfileSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- manifest ----------------------------------------------------------
    def _write_session_manifest(self, iterations: List[str]) -> None:
        # atomic for the same reason iteration files are: a kill during
        # this write must not leave a half-written session.json that
        # poisons every later open of the session
        _commit_json(
            self.root / "session.json",
            {
                "format": SESSION_FORMAT,
                "version": ARTIFACT_VERSION,
                "iterations": iterations,
            },
            notify=False,
        )

    def iteration_names(self) -> List[str]:
        """Names of this session's iterations, ordered by iteration number."""
        spath = self.root / "session.json"
        try:
            with open(spath) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise SessionError(
                f"{spath}: unreadable session manifest ({e})"
            ) from e
        _check_version(manifest, spath)
        names = set(manifest.get("iterations", []))
        # pick up directories written by other processes since last update
        names.update(
            d.name
            for d in self.root.iterdir()
            if d.is_dir() and _ITER_RE.match(d.name)
            and (d / "manifest.json").is_file()
        )
        # numeric order == creation order (add_iteration claims ascending
        # iterN slots), regardless of which writer updated the manifest last
        return sorted(
            names,
            key=lambda n: (
                int(_ITER_RE.match(n).group(1)) if _ITER_RE.match(n) else -1,
                n,
            ),
        )

    # -- crash recovery ----------------------------------------------------
    def recover(self) -> List[FaultEvent]:
        """Complete or quarantine iterations torn by a crash or kill.

        Scans every ``iterN`` directory for the :data:`JOURNAL_NAME`
        sidecar an interrupted :func:`write_iteration` leaves behind and
        resolves each one exactly:

        * journal present, manifest loads — the write finished and only
          the journal removal was lost: the journal is removed.
        * journal present, ``manifest.json.tmp`` durable and every npz
          it references present — the write died between the manifest
          fsync and its rename: the rename is performed and the
          iteration **completed** (its content was already fully
          durable, nothing is reconstructed).
        * anything else — the iteration is torn beyond repair and is
          moved to ``<root>/quarantine/`` where it cannot half-load,
          freeing its ``iterN`` slot.

        Returns one ``torn-iteration`` :class:`FaultEvent` per resolved
        directory (empty when the session was clean).  NOT called
        automatically on open: a journal is also what a *concurrently
        running* writer looks like, so recovery is an explicit decision
        of the CLI resume paths and of operators who know the session
        is quiescent.
        """
        events: List[FaultEvent] = []
        for d in sorted(self.root.iterdir()):
            if not d.is_dir() or not _ITER_RE.match(d.name):
                continue
            jpath = d / JOURNAL_NAME
            mpath = d / "manifest.json"
            tpath = d / "manifest.json.tmp"
            if not jpath.is_file():
                if mpath.is_file():
                    continue  # healthy (or pre-journal legacy): leave it
                # claimed (mkdir) but killed before the journal commit:
                # an empty husk wasting its slot
                events.append(self._quarantine(d, "no journal, no manifest"))
                continue
            if mpath.is_file() and self._iteration_loads(d):
                jpath.unlink(missing_ok=True)
                self._sweep_tmps(d)
                events.append(
                    FaultEvent(
                        kind="torn-iteration",
                        where="session",
                        detail=(
                            f"{d.name}: write completed, journal removal "
                            "lost; journal removed"
                        ),
                    )
                )
                continue
            if tpath.is_file():
                # the manifest temp was fsync'd before the rename, so if
                # it parses and its npz files exist the iteration content
                # is fully durable — finish the rename
                try:
                    manifest = json.loads(tpath.read_text())
                    npz_ok = all(
                        (d / e["npz"]).is_file()
                        for e in manifest.get("kernels", [])
                    )
                except (OSError, json.JSONDecodeError, KeyError, TypeError):
                    npz_ok = False
                if npz_ok:
                    os.replace(tpath, mpath)
                    if self._iteration_loads(d):
                        jpath.unlink(missing_ok=True)
                        self._sweep_tmps(d)
                        events.append(
                            FaultEvent(
                                kind="torn-iteration",
                                where="session",
                                detail=(
                                    f"{d.name}: completed from durable "
                                    "temp manifest"
                                ),
                            )
                        )
                        continue
            events.append(self._quarantine(d, "torn write (incomplete)"))
        self._write_session_manifest(self.iteration_names())
        return events

    @staticmethod
    def _iteration_loads(d: Path) -> bool:
        try:
            load_iteration(d)
            return True
        except SessionError:
            return False

    @staticmethod
    def _sweep_tmps(d: Path) -> None:
        for tmp in d.glob("*.tmp"):
            tmp.unlink(missing_ok=True)

    def _quarantine(self, d: Path, why: str) -> FaultEvent:
        qroot = self.root / "quarantine"
        qroot.mkdir(exist_ok=True)
        target = qroot / d.name
        k = 1
        while target.exists():
            k += 1
            target = qroot / f"{d.name}-{k}"
        d.rename(target)
        return FaultEvent(
            kind="torn-iteration",
            where="session",
            detail=f"{d.name}: {why}; quarantined to {target.name}",
        )

    # -- profiling ---------------------------------------------------------
    def profile(
        self,
        specs: Iterable[KernelSpec],
        sampler: Optional[GridSampler] = None,
        dynamic_contexts: Optional[Mapping[str, Mapping[str, np.ndarray]]] = None,
        names: Optional[Mapping[str, str]] = None,
        variants: Optional[Mapping[str, str]] = None,
        region_maps: Optional[Mapping[str, Mapping[str, str]]] = None,
        label: Optional[str] = None,
        note: str = "",
        workers: Optional[int] = None,
    ) -> Iteration:
        """Profile every spec and persist the results as the next iteration.

        ``names`` maps a spec's own name to the manifest name used for
        cross-iteration alignment (so ``gemm_v01`` in iter1 can diff
        against ``gemm_v00`` in iter0 under the shared name ``gemm``);
        ``dynamic_contexts``, ``variants`` and ``region_maps`` are keyed
        the same way, by ``KernelSpec.name``.  Returns the loaded
        :class:`Iteration`.

        The default sampler is FULL-GRID (unlike ``api.heatmap``'s
        block-sampling default): iteration diffs compare absolute
        transfer totals, which only align when both sides cover the
        whole problem.  Pass an explicit window sampler to trade
        coverage for speed on very large grids.

        ``workers`` overrides the session's worker count for this call;
        with more than one worker, collection is sharded across the
        session's persistent process pool (bit-identical results,
        per-shard provenance in the artifact).
        """
        sampler = sampler or GridSampler(None)
        dynamic_contexts = dynamic_contexts or {}
        names = names or {}
        variants = variants or {}
        region_maps = region_maps or {}
        collector = self.collector(workers)
        profiled = [
            profile_kernel(
                spec,
                sampler,
                dynamic_contexts.get(spec.name),
                name=names.get(spec.name),
                variant=variants.get(spec.name),
                region_map=sorted(region_maps.get(spec.name, {}).items()),
                collector=collector,
                cache=self.cache,
            )
            for spec in specs
        ]
        return self.add_iteration(profiled, label=label, note=note)

    def add_iteration(
        self,
        kernels: Sequence[ProfiledKernel],
        label: Optional[str] = None,
        note: str = "",
        tuning: Optional[Mapping] = None,
        *,
        layers: Optional[Mapping] = None,
    ) -> Iteration:
        """Persist already-profiled kernels as the next ``iterN`` directory.

        The directory is claimed with an *exclusive* mkdir, so two
        processes profiling into the same session race to distinct
        ``iterN`` numbers instead of silently overwriting each other.
        ``tuning`` is stored as the iteration's autotuner provenance and
        ``layers`` as its v5 per-layer attribution (validated; see
        :func:`write_iteration`).
        """
        existing = self.iteration_names()
        nums = [int(_ITER_RE.match(n).group(1)) for n in existing
                if _ITER_RE.match(n)]
        n = max(nums) + 1 if nums else 0
        while True:
            name = f"iter{n}"
            try:
                (self.root / name).mkdir(parents=True, exist_ok=False)
                break
            except FileExistsError:
                n += 1  # another writer claimed it; take the next slot
        path = write_iteration(
            self.root / name, kernels, label=label or name, note=note,
            tuning=tuning, layers=layers,
        )
        if name not in existing:
            existing.append(name)
        self._write_session_manifest(existing)
        return load_iteration(path)

    # -- autotuning --------------------------------------------------------
    def tune(
        self,
        kernel: str,
        budget: Optional[int] = None,
        target_patterns: Optional[Sequence[str]] = None,
        seed: int = 0,
        use_generated: bool = True,
        static_prescreen: bool = True,
        workers: Optional[int] = None,
        progress=None,
    ):
        """Close the tuning loop for one kernel family into this session.

        Thin front end over :func:`repro.core.tuner.tune`: the baseline
        profile and every candidate re-profile are persisted as numbered
        iterations of this session, each manifest carrying the tuning
        provenance (which advisor Action spawned which candidate, which
        candidates the static pre-screen skipped).  ``budget`` defaults
        to :data:`repro.core.tuner.DEFAULT_BUDGET`.  Returns the
        :class:`~repro.core.tuner.TuneResult`; the stored trajectory is
        recoverable later with
        :func:`repro.core.tuner.trajectories_from_session`.
        """
        from .tuner import DEFAULT_BUDGET, tune as _tune

        return _tune(
            kernel,
            budget=DEFAULT_BUDGET if budget is None else budget,
            target_patterns=target_patterns,
            seed=seed,
            use_generated=use_generated,
            static_prescreen=static_prescreen,
            session=self,
            collector=self.collector(workers),
            cache=self.cache,
            progress=progress,
        )

    # -- access ------------------------------------------------------------
    def iterations(self) -> List[Iteration]:
        """Load every iteration of this session, in creation order."""
        return [self.iteration(n) for n in self.iteration_names()]

    def iteration(self, which: Union[int, str]) -> Iteration:
        """Load one iteration by index (0, -1, ...) or directory name."""
        names = self.iteration_names()
        if isinstance(which, int):
            try:
                which = names[which]
            except IndexError:
                raise SessionError(
                    f"session has {len(names)} iterations, asked for "
                    f"index {which}"
                ) from None
        if which not in names:
            raise SessionError(
                f"{self.root}: no iteration {which!r} (have {names})"
            )
        return load_iteration(self.root / which)

    # -- history queries ---------------------------------------------------
    def history(
        self, include_rejected: bool = True
    ) -> Dict[str, List[HistoryPoint]]:
        """Per-kernel metric history across every iteration, in order.

        Reads only the iteration manifests (no numpy arrays), so this
        stays cheap on long-running sessions.  Returns a mapping from
        manifest kernel name to its :class:`HistoryPoint` sequence in
        iteration order.  ``include_rejected=False`` drops iterations
        the autotuner profiled and *rejected* — deliberately bad
        candidates that would otherwise pollute a rolling anomaly band
        (``cuthermo check --anomaly`` excludes them by default).
        """
        out: Dict[str, List[HistoryPoint]] = {}
        for name in self.iteration_names():
            mpath = self.root / name / "manifest.json"
            try:
                with open(mpath) as f:
                    manifest = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                raise SessionError(
                    f"{mpath}: unreadable manifest ({e})"
                ) from e
            _check_version(manifest, mpath)
            for pt in _history_points_from_manifest(manifest, name):
                if not include_rejected and pt.tuning_accepted is False:
                    continue
                out.setdefault(pt.kernel, []).append(pt)
        return out

    def kernel_history(
        self, kernel: str, include_rejected: bool = True
    ) -> List[HistoryPoint]:
        """One kernel's :meth:`history` row (empty when never profiled)."""
        return self.history(include_rejected=include_rejected).get(
            kernel, []
        )

    def diff(
        self,
        before: Union[int, str, Iteration],
        after: Union[int, str, Iteration],
        region_maps: Optional[Mapping[str, Mapping[str, str]]] = None,
    ) -> SessionDiff:
        """Diff two iterations of this session (see :func:`diff_iterations`)."""
        if not isinstance(before, Iteration):
            before = self.iteration(before)
        if not isinstance(after, Iteration):
            after = self.iteration(after)
        return diff_iterations(before, after, region_maps=region_maps)


__all__ = [
    "ARTIFACT_VERSION",
    "JOURNAL_NAME",
    "SUPPORTED_VERSIONS",
    "HistoryPoint",
    "Iteration",
    "KernelVerdict",
    "ProfileSession",
    "ProfiledKernel",
    "SessionDiff",
    "SessionError",
    "arrays_to_heatmap",
    "diff_iterations",
    "heatmap_to_arrays",
    "heatmaps_equal",
    "load_iteration",
    "profile_kernel",
    "write_iteration",
]
