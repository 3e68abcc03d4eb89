"""Trace collectors: Level 1 (BlockSpec walker) and Level 2 (in-kernel).

Level 1 — the NVBit analogue for TPU.  On GPU, memory transactions are
only observable at runtime, hence binary instrumentation.  On TPU the
HBM<->VMEM transfer schedule of a ``pallas_call`` is *static*: it is
fully determined by (grid, BlockSpec.index_map, block_shape).  The
collector therefore "instruments" a kernel by evaluating every operand's
``index_map`` for every sampled grid program — an exact, zero-overhead
reconstruction of the transfers the hardware will issue.

The walk is columnar: the sampled grid is materialized as one (P, ndim)
coordinate array, each operand's ``index_map`` is evaluated for the
whole batch (vectorized when the map is arithmetic, per-program
fallback otherwise), programs are grouped by distinct block key with
``np.unique``, and ONE broadcast ``TraceChunk`` is emitted per key —
the touch set is computed once and shared by every program mapping to
that block.  This is what makes full-grid traces of production-sized
kernels practical (see ``benchmarks/bench_overhead.py``).

Level 2 — for data-dependent addressing (gathers/scatters), where the
BlockSpec view is incomplete, kernels compiled with ``trace=True`` write
touched indices into an extra output buffer (CUTHERMO's GPU-queue trace
packer, realized as a normal kernel output).  ``drain_dynamic`` converts
the concrete index arrays into trace records via bulk ``divmod`` /
``np.unique`` over the whole (programs x slots) index matrix.

Sharded collection — because heat maps are a merge monoid (distinct
visited program counts = set unions, see :mod:`repro.core.heatmap`),
the sampled grid can be partitioned into contiguous program runs and
collected by independent workers, then merged *exactly*.
``ShardedCollector`` runs the shards on a spawn-safe process pool:
worker processes rebuild the kernel context from the registry's seeded
specs (``KernelSpec.source`` carries the ``name:variant`` ref — the
spec objects themselves hold index-map lambdas and cannot cross a
process boundary), collect their ``sampled[lo:hi]`` slice into a
shard-stamped ``TraceBuffer``, and ship the compact columnar chunks
back.  The parent re-keys the worker-local disjointness tokens (one
fresh token per site across all shards — sound because the shards
partition the grid, so pids stay pairwise disjoint per site) and
flushes ONE Analyzer over the union of chunks, which the golden suite
pins bit-identical to the serial single-pass build.  The global record
cap is split across the shards, so the sharded walk admits at most as
many records as the serial one; if the cap actually truncates, the
drop TOTALS remain exact but the surviving record set differs from
serial (and ``ShardedCollector.analyze`` warns).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .heatmap import Analyzer, Heatmap
from .resilience import DEFAULT_POLICY, FaultEvent, ResiliencePolicy
from .spec import IndexMap, KernelSpec, OperandSpec
from .tiles import block_to_2d
from .trace import (
    GridSampler,
    RegionInfo,
    ShardInfo,
    SiteInfo,
    TraceBuffer,
    linearize_array,
    sampled_grid_array,
    sampled_grid_size,
    sampled_grid_slice,
    unique_pairs,
)

#: Exception types an index map / access model is *expected* to raise
#: when it cannot evaluate a probe (non-broadcastable arithmetic, bad
#: arity, piecewise maps indexing out of range, ...).  The evaluation
#: fallbacks below catch exactly these: anything else (KeyboardInterrupt,
#: MemoryError, a bug in the collector itself) propagates instead of
#: being silently swallowed into the slow path or a None verdict.
_MAP_EVAL_ERRORS = (
    TypeError,
    ValueError,
    IndexError,
    KeyError,
    AttributeError,
    OverflowError,
    ZeroDivisionError,
    FloatingPointError,
)


class ShardError(RuntimeError):
    """A shard worker failed; the message carries shard + spec context.

    Raised (in the worker, so it crosses the process boundary as a
    picklable exception) when shard collection itself fails — rebuild
    guard violations (stale source) keep their original types, since
    they are usage errors, not transient faults.
    """


@dataclasses.dataclass
class CollectStats:
    records: int = 0
    programs: int = 0
    wall_s: float = 0.0
    touch_events: int = 0  # logical (record, touch) events represented


def _normalize_index(idx) -> Tuple:
    if isinstance(idx, tuple):
        return idx
    return (idx,)


def _eval_index_map_batch(
    index_map: IndexMap, pids: np.ndarray
) -> np.ndarray:
    """Evaluate an index_map for a (P, ndim) batch of program coords.

    Tries one vectorized call with array arguments (exact for the
    arithmetic lambdas BlockSpecs are made of), validated against scalar
    evaluation at the batch's first, middle, and last program — a
    piecewise map whose vectorized form happens to agree at both
    endpoints must not silently miscollect the interior; falls back to
    the per-program loop for maps that don't broadcast.
    Returns (P, k) int64 block coordinates.
    """
    p, ndim = pids.shape

    def _scalar(row: np.ndarray) -> Tuple[int, ...]:
        idx = _normalize_index(index_map(*[int(x) for x in row]))
        return tuple(int(i) for i in idx)

    if p > 1:
        try:
            out = _normalize_index(index_map(*[pids[:, d] for d in range(ndim)]))
            cols = [
                np.broadcast_to(np.asarray(o, dtype=np.int64), (p,))
                for o in out
            ]
            arr = np.stack(cols, axis=1)
            ok = True
            for i in sorted({0, p // 2, p - 1}):
                want = _scalar(pids[i])
                if (
                    len(want) != arr.shape[1]
                    or tuple(arr[i].tolist()) != want
                ):
                    ok = False
                    break
            if ok:
                return arr
        except _MAP_EVAL_ERRORS:
            pass  # map doesn't broadcast: take the per-program loop
    rows = [_scalar(pids[i]) for i in range(p)]
    return np.asarray(rows, dtype=np.int64).reshape(p, -1)


@dataclasses.dataclass(frozen=True)
class AffineModel:
    """Affine index-map model ``f(pid)[c] = base[c] + Σ_a coeffs[c][a]·pid[a]``.

    Extracted by :func:`probe_affine_map` and consumed by the static
    linter (:mod:`repro.core.lint`): the coefficient matrix is the
    "adjacent-pid delta" table every geometric rule reads — how the
    block key moves when one grid coordinate advances by one.
    """

    base: Tuple[int, ...]
    coeffs: Tuple[Tuple[int, ...], ...]  # coeffs[c][a]: d out[c] / d pid[a]

    @property
    def n_out(self) -> int:
        """Number of output components (the block-key arity)."""
        return len(self.base)

    def predict(self, pid: Sequence[int]) -> Tuple[int, ...]:
        """Evaluate the model at one program coordinate."""
        return tuple(
            b + sum(c * int(x) for c, x in zip(row, pid))
            for b, row in zip(self.base, self.coeffs)
        )

    def predict_batch(self, pids: np.ndarray) -> np.ndarray:
        """(P, n_out) model predictions for a (P, ndim) coordinate batch."""
        base = np.asarray(self.base, dtype=np.int64)
        coef = np.asarray(self.coeffs, dtype=np.int64)
        return base[None, :] + np.asarray(pids, dtype=np.int64) @ coef.T


def _affine_probe_points(grid: Tuple[int, ...]) -> List[Tuple[int, ...]]:
    """Sparse corner/edge/middle validation points of one grid."""
    ndim = len(grid)
    origin = (0,) * ndim
    last = tuple(g - 1 for g in grid)
    mid = tuple(g // 2 for g in grid)
    points = {origin, last, mid}
    for a in range(ndim):
        for v in (grid[a] - 1, grid[a] // 2):
            lo = list(origin)
            lo[a] = v
            points.add(tuple(lo))
            hi = list(last)
            hi[a] = v
            points.add(tuple(hi))
    return sorted(points)


def probe_affine_map(
    index_map: IndexMap, grid: Sequence[int]
) -> Optional[AffineModel]:
    """Extract an affine model of ``index_map`` over ``grid``, or ``None``.

    Reads the base off ``f(0, ..., 0)`` and each axis coefficient off
    the unit-vector probe ``f(e_a) - f(0)``, then validates the model by
    scalar evaluation (the collector's ground truth) at sparse corner,
    edge, and middle points of the grid.  Maps that raise, change output
    arity, or disagree with the model anywhere probed are reported as
    non-affine (``None``) — the caller must fall back to exhaustive
    evaluation or an explicit ``nonaffine`` verdict.  Axes of extent 1
    contribute coefficient 0 (the map is never evaluated off-grid).
    """
    grid = tuple(int(g) for g in grid)
    ndim = len(grid)

    def at(pid: Sequence[int]) -> Tuple[int, ...]:
        idx = _normalize_index(index_map(*[int(x) for x in pid]))
        return tuple(int(i) for i in idx)

    try:
        base = at((0,) * ndim)
        coeffs = [[0] * ndim for _ in base]
        for a in range(ndim):
            if grid[a] < 2:
                continue
            probe = [0] * ndim
            probe[a] = 1
            out = at(probe)
            if len(out) != len(base):
                return None
            for c in range(len(base)):
                coeffs[c][a] = out[c] - base[c]
        model = AffineModel(
            base=base, coeffs=tuple(tuple(row) for row in coeffs)
        )
        for pt in _affine_probe_points(grid):
            if at(pt) != model.predict(pt):
                return None
    except _MAP_EVAL_ERRORS:
        # a map that raises on any probe point is non-affine by
        # definition here; anything unexpected propagates to the caller
        return None
    return model


def _touch_arrays_for_key(
    spec: OperandSpec, idx: Tuple[int, ...]
) -> Tuple[np.ndarray, np.ndarray]:
    """(tags, words) touched by one block key (vectorized geometry walk)."""
    geom = spec.geometry
    if len(spec.shape) == 1:
        # 1-D operand: a contiguous element run walking (1,128) lane rows.
        # origin[1] models a misaligned view (e.g. rowOffsets shifted by +1).
        start = int(idx[0]) * int(spec.block_shape[-1]) + spec.origin[1]
        return geom.run_to_touch_arrays(start, start + int(spec.block_shape[-1]))
    r0, r1, c0, c1 = block_to_2d(spec.shape, idx, spec.block_shape)
    orow, ocol = spec.origin
    return geom.slice_to_touch_arrays(r0 + orow, r1 + orow, c0 + ocol, c1 + ocol)


def _dedupe_touches(
    tags: np.ndarray, words: np.ndarray, sublanes: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Unique (tag, word) pairs in ascending (tag, word) order."""
    key = np.unique(tags * sublanes + words)
    return key // sublanes, key % sublanes


def collect(
    kernel: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Dict[str, np.ndarray]] = None,
    max_records: int = 2_000_000,
    *,
    pids: Optional[np.ndarray] = None,
    owns_once: bool = True,
    shard_id: Optional[int] = None,
) -> Tuple[TraceBuffer, CollectStats]:
    """Level-1 collection: walk the sampled grid and record every transfer.

    ``pids`` overrides the walked program set (a ``(P, ndim)`` slice of
    ``sampled_grid_array`` — how a shard walks only its partition);
    ``owns_once`` says whether this walk owns ``once=True`` operands
    (exactly one shard — the one holding the globally first sampled
    program — must emit them, or a merged map would double-count their
    single contributor); ``shard_id`` stamps every emitted chunk.
    """
    sampler = sampler or GridSampler()
    buf = TraceBuffer(max_records=max_records, shard_id=shard_id)
    stats = CollectStats()
    t0 = time.perf_counter()

    for op in kernel.operands:
        buf.register_region(RegionInfo(op.name, op.geometry, space=op.space))
    for sc in kernel.scratch:
        buf.register_region(
            RegionInfo(sc.name, sc.geometry, space="vmem_scratch")
        )
    dynamic_names = {name for name, _ in kernel.dynamic}
    dyn_fns = dict(kernel.dynamic)

    if pids is None:
        pids = sampled_grid_array(kernel.grid, sampler)
    else:
        pids = np.asarray(pids, dtype=np.int64)
    n_programs = int(pids.shape[0])
    stats.programs = n_programs
    if n_programs == 0:
        stats.wall_s = time.perf_counter() - t0
        return buf, stats

    # -- static operands: group programs by distinct block key ---------------
    for op in kernel.operands:
        if op.name in dynamic_names:
            continue  # handled below with concrete indices
        if op.once and not owns_once:
            continue  # another shard owns the single-program operand
        site = SiteInfo(op.name, f"{kernel.name}/{op.name}", op.space, op.kind)
        group = TraceBuffer.new_group()
        sel = pids[:1] if op.once else pids
        keys = _eval_index_map_batch(op.index_map, sel)
        ukeys, inverse = np.unique(keys, axis=0, return_inverse=True)
        order = np.argsort(inverse, kind="stable")
        counts = np.bincount(inverse, minlength=len(ukeys))
        bounds = np.zeros(len(ukeys) + 1, dtype=np.int64)
        np.cumsum(counts, out=bounds[1:])
        for g in range(len(ukeys)):
            gsel = sel[order[bounds[g] : bounds[g + 1]]]
            tags, words = _touch_arrays_for_key(
                op, tuple(int(x) for x in ukeys[g])
            )
            buf.append_block(site, gsel, tags, words, group=group)

    # -- scratch: group programs by their access-model slice set -------------
    for sc in kernel.scratch:
        site = SiteInfo(sc.name, f"{kernel.name}/{sc.name}", "vmem_scratch",
                        sc.kind)
        group = TraceBuffer.new_group()
        geom = sc.geometry
        if sc.access_model is None:
            r, c = geom.shape2d
            tags, words = geom.slice_to_touch_arrays(0, r, 0, c)
            buf.append_block(site, pids, tags, words, group=group)
        else:
            by_slices: Dict[Tuple, List[int]] = {}
            for i in range(n_programs):
                pid = tuple(int(x) for x in pids[i])
                key = tuple(
                    tuple(int(v) for v in s) for s in sc.access_model(pid)
                )
                by_slices.setdefault(key, []).append(i)
            for slices, idxs in by_slices.items():
                parts = [
                    geom.slice_to_touch_arrays(r0, r1, c0, c1)
                    for r0, r1, c0, c1 in slices
                ]
                if parts:
                    tags = np.concatenate([t for t, _ in parts])
                    words = np.concatenate([w for _, w in parts])
                else:
                    tags = np.empty(0, np.int64)
                    words = np.empty(0, np.int64)
                tags, words = _dedupe_touches(tags, words, geom.sublanes)
                buf.append_block(site, pids[idxs], tags, words, group=group)

    # -- dynamic operands: concrete per-program indices (CSR chunk) ----------
    for op in kernel.operands:
        fn = dyn_fns.get(op.name)
        if fn is None:
            continue
        site = SiteInfo(op.name, f"{kernel.name}/{op.name}", op.space, op.kind)
        group = TraceBuffer.new_group()
        geom = op.geometry
        ctx = dynamic_context or {}
        tag_parts: List[np.ndarray] = []
        word_parts: List[np.ndarray] = []
        ptr = np.zeros(n_programs + 1, dtype=np.int64)
        for i in range(n_programs):
            pid = tuple(int(x) for x in pids[i])
            flat = np.asarray(list(fn(pid, **ctx)), dtype=np.int64)
            tags, words = geom.flat_to_touch_arrays(flat, op.origin)
            tags, words = _dedupe_touches(tags, words, geom.sublanes)
            tag_parts.append(tags)
            word_parts.append(words)
            ptr[i + 1] = ptr[i] + tags.shape[0]
        buf.append_block(
            site,
            pids,
            np.concatenate(tag_parts) if tag_parts else np.empty(0, np.int64),
            np.concatenate(word_parts) if word_parts else np.empty(0, np.int64),
            ptr=ptr,
            group=group,
        )

    stats.records = len(buf)
    stats.touch_events = buf.n_touch_events
    stats.wall_s = time.perf_counter() - t0
    return buf, stats


def analyze(
    kernel: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Dict[str, np.ndarray]] = None,
) -> Heatmap:
    """collect + drain + flush in one call (the common path)."""
    sampler = sampler or GridSampler()
    buf, _ = collect(kernel, sampler, dynamic_context)
    an = Analyzer(kernel.name, kernel.grid, sampler.describe())
    an.ingest(buf)
    return an.flush()


# ---------------------------------------------------------------------------
# sharded collection: partition the sampled grid, collect on a process pool,
# merge exactly (the heat-map algebra makes the merge a set union)
# ---------------------------------------------------------------------------


def split_budget(total: int, shards: int) -> List[int]:
    """Split a global record budget into near-equal per-shard budgets.

    Sums exactly to ``total``, so sharded collection admits at most as
    many records as the serial cap.  When the cap actually bites, the
    *specific* records admitted differ from serial (serial truncates an
    operand-major stream, shards truncate program-partitioned ones), so
    bit-identity is only guaranteed for traces within the cap —
    ``ShardedCollector.analyze`` warns loudly when any shard dropped.
    """
    shards = max(1, int(shards))
    base, extra = divmod(int(total), shards)
    return [base + (1 if i < extra else 0) for i in range(shards)]


def shard_bounds(total: int, shards: int) -> List[Tuple[int, int]]:
    """Contiguous, near-equal [lo, hi) partitions of ``total`` programs.

    Never returns empty shards: the shard count is clipped to ``total``
    (a 3-program grid sharded 8 ways is 3 shards of one program each).
    ``total == 0`` yields one empty shard so downstream bookkeeping
    still sees a shard record.
    """
    shards = max(1, min(int(shards), max(total, 1)))
    edges = np.linspace(0, total, shards + 1).astype(np.int64)
    return [(int(edges[i]), int(edges[i + 1])) for i in range(shards)]


def collect_shard(
    kernel: KernelSpec,
    sampler: GridSampler,
    dynamic_context: Optional[Dict[str, np.ndarray]],
    lo: int,
    hi: int,
    shard: int,
    max_records: int = 2_000_000,
) -> Tuple[TraceBuffer, ShardInfo]:
    """Collect one contiguous sampled-grid shard ``sampled[lo:hi]``.

    Pure function of its arguments — the unit both the in-process
    fallback and the pool workers execute.  The shard holding the
    globally first sampled program (``lo == 0``) owns ``once=True``
    operands.  The shard's coordinate rows are computed directly
    (``sampled_grid_slice``), so per-shard cost is O(hi - lo), not
    O(total grid).
    """
    t0 = time.perf_counter()
    pids = sampled_grid_slice(kernel.grid, sampler, lo, hi)
    buf, _ = collect(
        kernel,
        sampler,
        dynamic_context,
        max_records,
        pids=pids,
        owns_once=(lo == 0),
        shard_id=shard,
    )
    # pack one-chunk-per-key runs before the buffer crosses a process
    # boundary: per-chunk pickle + flush costs would otherwise dominate
    buf.consolidate()
    info = ShardInfo(
        shard=shard,
        lo=int(lo),
        hi=int(hi),
        programs=int(pids.shape[0]),
        records=len(buf),
        dropped=buf.dropped,
        wall_s=time.perf_counter() - t0,
    )
    return buf, info


def _hold_jax_to_cpu() -> None:
    """Pool initializer: workers walk grids with NumPy, never a device.

    The parent may hold the accelerator (one process per chip), so a
    worker's JAX is pinned to the CPU before it can start a backend, and
    so is any process the worker starts.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def _warm_worker(_: int) -> bool:
    """Pool warmup: pay the kernel-registry import once per worker."""
    from repro import kernels  # noqa: F401  (import is the work)

    return True


def sourced_spec(fn_ref: str, *args, **kwargs) -> KernelSpec:
    """Build a spec from a ``"module:function"`` ref and stamp its source.

    The ref plus plain args is picklable, so the resulting spec can be
    collected by a ``ShardedCollector`` pool at ANY shape — not just the
    registry's defaults.  Example::

        sourced_spec("repro.kernels.gemm:gemm_v01_spec", 4096, 4096, 4096)
    """
    spec = _build_from_ref(fn_ref, args, kwargs)
    return dataclasses.replace(spec, source=(fn_ref, args, kwargs))


def _build_from_ref(fn_ref: str, args, kwargs) -> KernelSpec:
    import importlib

    mod_name, _, fn_name = fn_ref.partition(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    return fn(*args, **(kwargs or {}))


def _rebuild_spec(source) -> Tuple[KernelSpec, Optional[Dict[str, np.ndarray]]]:
    """Worker-side spec reconstruction from either source form."""
    if isinstance(source, str):
        from repro import kernels as kreg

        return kreg.build(source)
    fn_ref, args, kwargs = source
    return _build_from_ref(fn_ref, args, kwargs), None


def _spec_fingerprint(spec: KernelSpec) -> Tuple:
    """Cheap picklable structural identity of a spec.

    Guards the source round trip: a worker rebuilds the spec from its
    source ref, so a parent spec whose STRUCTURE was modified after
    stamping (shapes, blocks, operand set, ...) must be rejected, not
    silently replaced by the pristine rebuild.  Index-map *code* cannot
    be fingerprinted — mutating only a lambda while keeping the stale
    source is the one hole this cannot close.
    """
    return (
        spec.name,
        tuple(spec.grid),
        tuple(
            (op.name, tuple(op.shape), np.dtype(op.dtype).str,
             tuple(op.block_shape), op.kind, op.space,
             tuple(op.origin), op.once)
            for op in spec.operands
        ),
        tuple(
            (sc.name, tuple(sc.shape), np.dtype(sc.dtype).str, sc.kind,
             sc.access_model is None)
            for sc in spec.scratch
        ),
        tuple(name for name, _ in spec.dynamic),
    )


#: Worker-process memo of rebuilt (spec, seeded context) pairs, keyed by
#: the pickled (source, fingerprint) pair.  A warm worker collecting the
#: same kernel across tune steps / bench reps pays the registry rebuild
#: (and, for seeded families, the RNG context generation) exactly once.
#: Entries are only stored AFTER the fingerprint guard passes, so a
#: stale-source rejection can never be cached away.
_REBUILD_MEMO: Dict[bytes, Tuple[KernelSpec, Optional[Dict[str, np.ndarray]]]] = {}

_REBUILD_MEMO_MAX = 16


def _rebuild_spec_cached(
    source, fingerprint: Tuple
) -> Tuple[KernelSpec, Optional[Dict[str, np.ndarray]]]:
    """Fingerprint-guarded :func:`_rebuild_spec` with a per-process memo."""
    import pickle

    try:
        key = pickle.dumps((source, fingerprint))
    except Exception:  # noqa: BLE001 — unpicklable key: just don't memoize
        key = None
    if key is not None:
        hit = _REBUILD_MEMO.get(key)
        if hit is not None:
            return hit
    spec, ctx = _rebuild_spec(source)
    if _spec_fingerprint(spec) != fingerprint:
        raise ValueError(
            f"shard worker rebuilt {source!r} into a spec that "
            "does not structurally match the parent's (grid, operand, "
            "or scratch layout differs); the parent spec was modified "
            "after source stamping — collect it serially instead"
        )
    if key is not None:
        if len(_REBUILD_MEMO) >= _REBUILD_MEMO_MAX:
            _REBUILD_MEMO.pop(next(iter(_REBUILD_MEMO)))
        _REBUILD_MEMO[key] = (spec, ctx)
    return spec, ctx


def _collect_shard_task(task: dict) -> Tuple[TraceBuffer, ShardInfo]:
    """Pool entry point: rebuild the spec from its source ref, collect.

    Spawn-safe by construction — nothing unpicklable crosses the
    process boundary.  The spec (and, for registry refs, its seeded
    dynamic context) is rebuilt from ``task['source']`` — memoized per
    worker process, so repeated collects of one kernel (a tuning loop,
    a benchmark's reps) rebuild once; an explicit dynamic context
    (plain numpy arrays) overrides the seeded one.

    ``task['inject']`` (optional) is a fault-injection directive
    executed before collection (see :mod:`repro.core.faultinject`);
    collection failures are re-raised as :class:`ShardError` carrying
    shard + spec context — the rebuild guard's stale-source error keeps
    its own type (a usage error, not a shard fault).
    """
    if task.get("inject"):
        from .faultinject import apply_worker_directive

        apply_worker_directive(task["inject"])
    spec, ctx = _rebuild_spec_cached(task["source"], task["fingerprint"])
    if task["dynamic_context"] is not None:
        ctx = task["dynamic_context"]
    try:
        return collect_shard(
            spec,
            task["sampler"],
            ctx,
            task["lo"],
            task["hi"],
            task["shard"],
            task["max_records"],
        )
    except ShardError:
        raise
    except Exception as e:
        raise ShardError(
            f"shard {task['shard']} [{task['lo']}:{task['hi']}) of "
            f"{spec.name!r} (source {task['source']!r}): "
            f"{type(e).__name__}: {e}"
        ) from e


def _unify_shard_groups(bufs: Sequence[TraceBuffer]) -> None:
    """Re-key worker-local disjointness tokens across shard buffers.

    Each worker process numbers its group tokens from 1, so tokens from
    different shards collide numerically without meaning anything.
    Every chunk of one *site* gets one fresh parent token across all
    shards — sound only because the shards partition the sampled grid,
    which keeps record pids pairwise disjoint per site (the token's
    contract) and lets the Analyzer keep its weighted fast path.
    Chunks without a token stay exact-path.
    """
    tokens: Dict[SiteInfo, int] = {}
    for buf in bufs:
        for chunk in buf.chunks:
            if chunk.group is None:
                continue
            token = tokens.get(chunk.site)
            if token is None:
                token = TraceBuffer.new_group()
                tokens[chunk.site] = token
            chunk.group = token


class ShardedCollector:
    """Partition a sampled grid and collect it on a process pool.

    The pool is lazy and persistent: it spins up on first use (spawn
    start method by default — fork after jax initialization is not
    safe) and is reused across ``collect``/``analyze`` calls until
    :meth:`close`, so a multi-kernel profiling run pays worker startup
    once.  Use as a context manager, or call :meth:`close` yourself.

    Specs without a registry ``source`` ref cannot cross the process
    boundary (their index maps are lambdas); those are sharded and
    merged **in-process** — the same algebra, no parallelism — so the
    call never silently changes semantics, it only loses speed.

    Collection is *fault tolerant* under ``policy`` (a
    :class:`~repro.core.resilience.ResiliencePolicy`):

    * a shard that fails cleanly is resubmitted with exponential
      backoff, up to ``policy.attempts`` deliveries;
    * a dead worker (``BrokenProcessPool``) tears the pool down,
      respawns it, and resubmits every unfinished shard — after
      ``policy.max_pool_failures`` consecutive broken rounds the
      collector degrades to serial in-process collection;
    * a shard still running ``policy.shard_timeout_s`` after its round
      started is declared hung: its worker is killed and the shard
      re-runs in process, re-split into ``policy.resplit`` smaller pid
      runs.

    Every recovery is recorded as a structured
    :class:`~repro.core.resilience.FaultEvent`; :meth:`analyze`
    attaches them to ``Heatmap.faults`` (v6 artifact provenance).  The
    set-union merge algebra makes re-executed shards exact, so the
    recovered heat map stays bit-identical to the clean serial build.
    ``fault_plan`` (a :class:`~repro.core.faultinject.FaultPlan`)
    deterministically injects worker crashes and hangs for tests and
    the chaos CI job.
    """

    def __init__(
        self,
        workers: int,
        *,
        max_records: int = 2_000_000,
        start_method: str = "spawn",
        policy: Optional[ResiliencePolicy] = None,
        fault_plan=None,
    ):
        self.workers = max(1, int(workers))
        self.max_records = max_records
        self.start_method = start_method
        self.fault_plan = fault_plan
        if policy is not None:
            self.policy = policy
        elif fault_plan is not None:
            # injected hangs must expire in test time, not production time
            self.policy = fault_plan.policy()
        else:
            self.policy = DEFAULT_POLICY
        self._pool = None
        # pool creation must be race-free: the concurrent tune
        # scheduler shares one collector across profiling threads
        self._pool_lock = threading.Lock()
        # fault events are per-collect and per-thread (the concurrent
        # tune scheduler profiles on several threads at once)
        self._tls = threading.local()

    @property
    def last_fault_events(self) -> Tuple[FaultEvent, ...]:
        """Recovery events of this thread's most recent :meth:`collect`."""
        return getattr(self._tls, "events", ())

    # -- pool lifecycle -----------------------------------------------------
    def _ensure_pool(self):
        with self._pool_lock:
            if self._pool is None:
                import concurrent.futures
                import multiprocessing

                self._pool = concurrent.futures.ProcessPoolExecutor(
                    max_workers=self.workers,
                    mp_context=multiprocessing.get_context(self.start_method),
                    initializer=_hold_jax_to_cpu,
                )
            return self._pool

    def _warm(self, pool) -> None:
        """Pay worker spawn + imports BEFORE a watchdog-timed round.

        The hang watchdog is meant to time *shard execution*; on a cold
        pool the first round would otherwise also absorb process spawn
        and registry imports, and a tight watchdog (as fault-injection
        plans install) would declare healthy-but-booting workers hung.
        Warming is idempotent per pool instance.
        """
        if getattr(pool, "_cuthermo_warm", False):
            return
        list(pool.map(_warm_worker, range(self.workers)))
        pool._cuthermo_warm = True

    def warmup(self) -> float:
        """Pre-import the kernel registry in every worker (pays the
        spawn + import cost up front, outside any timed section).
        Returns the warm-up wall time in seconds (benchmarks record
        it); near-zero when the pool is already warm."""
        t0 = time.perf_counter()
        pool = self._ensure_pool()
        list(pool.map(_warm_worker, range(self.workers)))
        return time.perf_counter() - t0

    def close(self) -> None:
        """Shut the pool down (idempotent)."""
        with self._pool_lock:
            if self._pool is not None:
                self._pool.shutdown()
                self._pool = None

    def _kill_pool(self) -> None:
        """Tear the pool down the hard way (hung or broken workers).

        ``shutdown`` alone would block behind a hung worker, so worker
        processes are terminated best-effort first; a fresh pool is
        spun up lazily by the next :meth:`_ensure_pool`.
        """
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is None:
            return
        for p in list(getattr(pool, "_processes", {}).values() or []):
            try:
                if p.is_alive():
                    p.terminate()
            except (OSError, ValueError, AttributeError):
                pass  # already dead / already closed
        try:
            pool.shutdown(wait=False, cancel_futures=True)
        except (OSError, RuntimeError):
            pass

    def __enter__(self) -> "ShardedCollector":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- collection ---------------------------------------------------------
    def collect(
        self,
        kernel: KernelSpec,
        sampler: Optional[GridSampler] = None,
        dynamic_context: Optional[Dict[str, np.ndarray]] = None,
    ) -> Tuple[List[TraceBuffer], Tuple[ShardInfo, ...]]:
        """Collect every shard; returns (shard buffers, shard infos).

        The returned buffers have already had their group tokens
        unified — ingesting them all into one Analyzer flushes the
        exact single-pass heat map.  Recovery events of the call are
        exposed as :attr:`last_fault_events` (empty for a clean run);
        a shard re-split by the hang watchdog contributes one buffer
        and one ``ShardInfo`` per sub-run, all under its shard id.
        """
        sampler = sampler or GridSampler()
        total = sampled_grid_size(kernel.grid, sampler)
        bounds = shard_bounds(total, self.workers)
        # the GLOBAL record cap is divided across shards, so a sharded
        # collect never admits more records than the serial one would
        budgets = split_budget(self.max_records, len(bounds))
        events: List[FaultEvent] = []
        if kernel.source is None or len(bounds) == 1:
            results = {
                i: [collect_shard(
                    kernel, sampler, dynamic_context, lo, hi, i, budgets[i]
                )]
                for i, (lo, hi) in enumerate(bounds)
            }
        else:
            results = self._collect_resilient(
                kernel, sampler, dynamic_context, bounds, budgets, events
            )
        pairs = [pair for i in sorted(results) for pair in results[i]]
        bufs = [b for b, _ in pairs]
        infos = tuple(i for _, i in pairs)
        self._tls.events = tuple(events)
        _unify_shard_groups(bufs)
        return bufs, infos

    def _collect_resilient(
        self,
        kernel: KernelSpec,
        sampler: GridSampler,
        dynamic_context: Optional[Dict[str, np.ndarray]],
        bounds: List[Tuple[int, int]],
        budgets: List[int],
        events: List[FaultEvent],
    ) -> Dict[int, List[Tuple[TraceBuffer, ShardInfo]]]:
        """The recovery loop: submit rounds of shards until all complete.

        Each round submits every unfinished shard to the pool and waits
        under the hang watchdog.  Clean per-shard failures retry with
        backoff (bounded by ``policy.attempts``); a broken pool is
        rebuilt and the round repeated (bounded by
        ``policy.max_pool_failures``, then serial fallback); hung
        shards are expired by the watchdog and re-run in process —
        which always terminates — so the loop converges.
        """
        import concurrent.futures
        from concurrent.futures.process import BrokenProcessPool

        policy = self.policy
        plan = self.fault_plan
        fingerprint = _spec_fingerprint(kernel)
        n = len(bounds)

        def task_for(i: int, attempt: int) -> dict:
            lo, hi = bounds[i]
            inject = (
                plan.directive(kernel.name, n, i, attempt)
                if plan is not None
                else None
            )
            return {
                "source": kernel.source,
                "fingerprint": fingerprint,
                "sampler": sampler,
                "dynamic_context": dynamic_context,
                "lo": lo,
                "hi": hi,
                "shard": i,
                "max_records": budgets[i],
                "inject": inject,
            }

        results: Dict[int, List[Tuple[TraceBuffer, ShardInfo]]] = {}
        attempts = {i: 0 for i in range(n)}
        pool_failures = 0
        remaining = set(range(n))
        while remaining:
            if pool_failures >= policy.max_pool_failures:
                # graceful degradation: no parallelism, but the run and
                # its bit-identical heat map still complete
                events.append(
                    FaultEvent(
                        kind="serial-fallback",
                        where="collector",
                        detail=(
                            f"{len(remaining)} shard(s) collected serially "
                            f"after {pool_failures} consecutive pool failures"
                        ),
                    )
                )
                for i in sorted(remaining):
                    results[i] = self._run_shard_local(
                        kernel, sampler, dynamic_context, bounds[i],
                        budgets[i], i, events,
                    )
                remaining.clear()
                break
            pool = self._ensure_pool()
            try:
                self._warm(pool)
            except BrokenProcessPool:
                # a worker died while booting (genuine environment
                # failure — injection never targets warm-up): count it
                # against the pool-failure budget and respin
                pool_failures += 1
                self._kill_pool()
                events.append(
                    FaultEvent(
                        kind="worker-crash",
                        where="collector",
                        detail="process pool broke during warm-up",
                    )
                )
                continue
            round_start = time.monotonic()
            futs = {}
            for i in sorted(remaining):
                futs[pool.submit(_collect_shard_task,
                                 task_for(i, attempts[i]))] = i
                attempts[i] += 1
            done, not_done = concurrent.futures.wait(
                futs, timeout=policy.shard_timeout_s
            )
            broken = False
            retry_backoff = 0.0
            for fut in sorted(done, key=lambda f: futs[f]):
                i = futs[fut]
                try:
                    results[i] = [fut.result()]
                    remaining.discard(i)
                except BrokenProcessPool:
                    # one dead worker fails every pending future; record
                    # the crash once, rebuild below, resubmit next round
                    if not broken:
                        events.append(
                            FaultEvent(
                                kind="worker-crash",
                                where="collector",
                                shard=i,
                                attempt=attempts[i] - 1,
                                wall_s=time.monotonic() - round_start,
                                detail="process pool broke (worker died)",
                            )
                        )
                    broken = True
                except Exception as e:
                    if attempts[i] >= policy.attempts:
                        raise
                    events.append(
                        FaultEvent(
                            kind="shard-retry",
                            where="collector",
                            shard=i,
                            attempt=attempts[i] - 1,
                            detail=f"{type(e).__name__}: {e}"[:200],
                        )
                    )
                    retry_backoff = max(
                        retry_backoff, policy.backoff_s(attempts[i])
                    )
            if not_done:
                # the hang watchdog: kill the wedged workers, re-run the
                # hung shards in process (re-split into smaller pid runs)
                hung = sorted(futs[f] for f in not_done)
                for f in not_done:
                    f.cancel()
                self._kill_pool()
                for i in hung:
                    events.append(
                        FaultEvent(
                            kind="shard-timeout",
                            where="collector",
                            shard=i,
                            attempt=attempts[i] - 1,
                            wall_s=time.monotonic() - round_start,
                            detail=(
                                f"no result within "
                                f"{policy.shard_timeout_s:.1f}s; "
                                "worker killed, shard re-run in process"
                            ),
                        )
                    )
                    results[i] = self._run_shard_local(
                        kernel, sampler, dynamic_context, bounds[i],
                        budgets[i], i, events, resplit=policy.resplit,
                    )
                    remaining.discard(i)
            if broken:
                pool_failures += 1
                self._kill_pool()
                if remaining and pool_failures < policy.max_pool_failures:
                    events.append(
                        FaultEvent(
                            kind="pool-rebuild",
                            where="collector",
                            detail=(
                                f"respawning {self.workers} workers "
                                f"(consecutive failure {pool_failures})"
                            ),
                        )
                    )
                    time.sleep(policy.backoff_s(pool_failures))
            else:
                if retry_backoff:
                    time.sleep(retry_backoff)
                if remaining:
                    pool_failures = 0  # progress without breakage: reset
        return results

    def _run_shard_local(
        self,
        kernel: KernelSpec,
        sampler: GridSampler,
        dynamic_context: Optional[Dict[str, np.ndarray]],
        bound: Tuple[int, int],
        budget: int,
        shard: int,
        events: List[FaultEvent],
        resplit: int = 1,
    ) -> List[Tuple[TraceBuffer, ShardInfo]]:
        """Re-run one shard in process, optionally re-split.

        Sub-runs keep the shard's id and partition its ``[lo, hi)``
        exactly, so group-token unification and the merge algebra are
        unaffected; the globally-first sub-run (``lo == 0``) owns
        ``once=`` operands automatically (``collect_shard`` derives
        ownership from the global ``lo``).  Injected directives never
        reach this path — the in-process re-run is the recovery, so it
        must be clean by construction.
        """
        from ..fault import retry as _retry

        lo, hi = bound
        k = max(1, min(int(resplit), max(hi - lo, 1)))
        pieces = [(lo + a, lo + b) for a, b in shard_bounds(hi - lo, k)]
        if len(pieces) > 1:
            events.append(
                FaultEvent(
                    kind="shard-resplit",
                    where="collector",
                    shard=shard,
                    detail=(
                        f"re-running [{lo}:{hi}) in process as "
                        f"{len(pieces)} smaller runs"
                    ),
                )
            )
        sub_budgets = split_budget(budget, len(pieces))
        out: List[Tuple[TraceBuffer, ShardInfo]] = []
        for j, (plo, phi) in enumerate(pieces):
            def _run(plo=plo, phi=phi, j=j):
                return collect_shard(
                    kernel, sampler, dynamic_context, plo, phi, shard,
                    sub_budgets[j],
                )

            def _note(attempt, exc):
                events.append(
                    FaultEvent(
                        kind="shard-retry",
                        where="collector",
                        shard=shard,
                        attempt=attempt,
                        detail=(
                            f"in-process re-run: "
                            f"{type(exc).__name__}: {exc}"
                        )[:200],
                    )
                )

            out.append(
                _retry(
                    _run,
                    attempts=self.policy.attempts,
                    base_delay=self.policy.base_delay,
                    retryable=(Exception,),
                    on_retry=_note,
                )()
            )
        return out

    def analyze(
        self,
        kernel: KernelSpec,
        sampler: Optional[GridSampler] = None,
        dynamic_context: Optional[Dict[str, np.ndarray]] = None,
    ) -> Heatmap:
        """Sharded collect + merge + flush: the parallel ``analyze``.

        Bit-identical to :func:`analyze` on the same arguments for any
        trace within the record cap (pinned by the golden-equivalence
        suite), with per-shard provenance in ``Heatmap.shards`` and
        any recovery provenance in ``Heatmap.faults``.  When the cap
        bites, drop *totals* stay exact (each drop is counted in
        exactly one shard) but the surviving record set differs from
        serial truncation — a RuntimeWarning flags it.
        """
        sampler = sampler or GridSampler()
        bufs, infos = self.collect(kernel, sampler, dynamic_context)
        dropped = sum(i.dropped for i in infos)
        if dropped:
            import warnings

            warnings.warn(
                f"{kernel.name}: {dropped} records dropped at the "
                f"max_records={self.max_records} cap; a truncated "
                "sharded heat map is not bit-identical to the serial "
                "build (raise max_records or sample a window)",
                RuntimeWarning,
                stacklevel=2,
            )
        an = Analyzer(kernel.name, kernel.grid, sampler.describe())
        for buf in bufs:
            an.ingest(buf)
        return dataclasses.replace(
            an.flush(), shards=infos, faults=self.last_fault_events
        )


def analyze_sharded(
    kernel: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Dict[str, np.ndarray]] = None,
    workers: int = 2,
) -> Heatmap:
    """One-shot sharded :func:`analyze` (owns a pool for the call)."""
    with ShardedCollector(workers) as sc:
        return sc.analyze(kernel, sampler, dynamic_context)


# ---------------------------------------------------------------------------
# Level 2: drain an in-kernel trace buffer (concrete indices from a real run)
# ---------------------------------------------------------------------------

def drain_dynamic(
    kernel_name: str,
    grid: Sequence[int],
    operand: OperandSpec,
    index_trace: np.ndarray,
    sampler: Optional[GridSampler] = None,
    valid_mask: Optional[np.ndarray] = None,
) -> TraceBuffer:
    """Convert an in-kernel index trace into records.

    ``index_trace`` has shape (n_programs, k): flat element indices written
    by the instrumented kernel (one row per grid program, row-major grid
    order); negative entries (or masked-out ones) are padding.  The whole
    matrix is converted in one vectorized pass (bulk divmod + per-program
    ``np.unique`` dedup via lexsort).
    """
    sampler = sampler or GridSampler()
    grid = tuple(int(g) for g in grid)
    buf = TraceBuffer()
    buf.register_region(
        RegionInfo(operand.name, operand.geometry, space=operand.space)
    )
    geom = operand.geometry
    pids = sampled_grid_array(grid, sampler)
    p = int(pids.shape[0])
    if p == 0:
        return buf
    lin = linearize_array(pids, grid)
    index_trace = np.asarray(index_trace)
    rows = index_trace[lin].reshape(p, -1)
    keep = rows >= 0
    if valid_mask is not None:
        keep &= np.asarray(valid_mask)[lin].reshape(p, -1).astype(bool)
    rec = np.broadcast_to(
        np.arange(p, dtype=np.int64)[:, None], rows.shape
    )[keep]
    flat = rows[keep]
    tags, words = geom.flat_to_touch_arrays(flat)
    key = tags * geom.sublanes + words
    rs, ks = unique_pairs(rec, key)
    counts = np.bincount(rs, minlength=p)
    ptr = np.zeros(p + 1, dtype=np.int64)
    np.cumsum(counts, out=ptr[1:])
    buf.append_block(
        SiteInfo(
            operand.name,
            f"{kernel_name}/{operand.name}#trace",
            operand.space,
            operand.kind,
        ),
        pids,
        ks // geom.sublanes,
        ks % geom.sublanes,
        ptr=ptr,
        group=TraceBuffer.new_group(),
    )
    return buf
