"""The seed per-record profiling engine, preserved verbatim-in-spirit.

This module is the *golden reference* for the columnar engine in
``collector.py`` / ``heatmap.py``: one ``AccessRecord`` object per
(grid program x operand), per-word Python-int bitmasks updated one
touch at a time (the paper's literal ``mask |= 1 << id``).  It exists
for two reasons:

  1. the golden-equivalence suite (``tests/test_golden_equivalence.py``)
     asserts the vectorized engine produces bit-identical heat maps;
  2. ``benchmarks/bench_overhead.py`` measures the vectorized engine's
     collection+analysis throughput against it.

Do not optimize this module — its slowness is the point.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .collector import CollectStats
from .heatmap import Heatmap, HeatRow, RegionHeatmap, SectorHistory
from .spec import KernelSpec, OperandSpec
from .tiles import TileGeometry, block_to_2d
from .trace import (
    AccessRecord,
    GridSampler,
    RegionInfo,
    linearize,
    sampled_grid,
)


class ReferenceTraceBuffer:
    """Seed append-only record-object buffer (one AccessRecord per event)."""

    def __init__(self, max_records: int = 2_000_000):
        self.records: List[AccessRecord] = []
        self.regions: Dict[str, RegionInfo] = {}
        self.max_records = max_records
        self.dropped = 0

    def register_region(self, region: RegionInfo) -> None:
        self.regions[region.name] = region

    def append(self, rec: AccessRecord) -> None:
        if len(self.records) >= self.max_records:
            self.dropped += 1
            return
        self.records.append(rec)

    def __len__(self) -> int:
        return len(self.records)


def _touches_for_block(
    spec: OperandSpec, program_id: Tuple[int, ...]
) -> Tuple[Tuple[int, int], ...]:
    idx = spec.index_map(*program_id)
    if isinstance(idx, int):
        idx = (idx,)
    geom = TileGeometry(
        shape=spec.shape, itemsize=np.dtype(spec.dtype).itemsize, name=spec.name
    )
    if len(spec.shape) == 1:
        start = int(idx[0]) * int(spec.block_shape[-1]) + spec.origin[1]
        return tuple(geom.run_to_touches(start, start + int(spec.block_shape[-1])))
    r0, r1, c0, c1 = block_to_2d(spec.shape, idx, spec.block_shape)
    orow, ocol = spec.origin
    return tuple(geom.slice_to_touches(r0 + orow, r1 + orow, c0 + ocol, c1 + ocol))


def collect_reference(
    kernel: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Dict[str, np.ndarray]] = None,
    max_records: int = 2_000_000,
) -> Tuple[ReferenceTraceBuffer, CollectStats]:
    """Seed Level-1 collection: one Python loop iteration per program."""
    sampler = sampler or GridSampler()
    buf = ReferenceTraceBuffer(max_records=max_records)
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

    touch_cache: Dict[Tuple[str, Tuple[int, ...]], Tuple[Tuple[int, int], ...]] = {}

    first_pid = True
    for pid in sampled_grid(kernel.grid, sampler):
        stats.programs += 1
        for op in kernel.operands:
            if op.name in dynamic_names:
                continue
            if op.once and not first_pid:
                continue
            idx = op.index_map(*pid)
            if isinstance(idx, int):
                idx = (idx,)
            key = (op.name, tuple(int(i) for i in idx))
            touches = touch_cache.get(key)
            if touches is None:
                touches = _touches_for_block(op, pid)
                touch_cache[key] = touches
            buf.append(
                AccessRecord(
                    array=op.name,
                    site=f"{kernel.name}/{op.name}",
                    space=op.space,
                    kind=op.kind,
                    program_id=pid,
                    touches=touches,
                )
            )
        for sc in kernel.scratch:
            geom = sc.geometry
            slices: Iterable[Tuple[int, int, int, int]]
            if sc.access_model is None:
                r, c = geom.shape2d
                slices = [(0, r, 0, c)]
            else:
                slices = sc.access_model(pid)
            touches_list: List[Tuple[int, int]] = []
            for r0, r1, c0, c1 in slices:
                touches_list.extend(geom.slice_to_touches(r0, r1, c0, c1))
            buf.append(
                AccessRecord(
                    array=sc.name,
                    site=f"{kernel.name}/{sc.name}",
                    space="vmem_scratch",
                    kind=sc.kind,
                    program_id=pid,
                    touches=tuple(touches_list),
                )
            )
        for op in kernel.operands:
            fn = dyn_fns.get(op.name)
            if fn is None:
                continue
            ctx = dynamic_context or {}
            flat_idx = np.asarray(list(fn(pid, **ctx)), dtype=np.int64)
            geom = op.geometry
            rows, cols = geom.shape2d
            touches_set = set()
            for fi in flat_idx:
                r, c = divmod(int(fi), cols) if cols else (0, 0)
                r += op.origin[0]
                c += op.origin[1]
                touches_set.add((geom.sector_tag(r, c), geom.word_offset(r, c)))
            buf.append(
                AccessRecord(
                    array=op.name,
                    site=f"{kernel.name}/{op.name}",
                    space=op.space,
                    kind=op.kind,
                    program_id=pid,
                    touches=tuple(sorted(touches_set)),
                )
            )
        first_pid = False
    stats.records = len(buf)
    stats.wall_s = time.perf_counter() - t0
    return buf, stats


class ReferenceAnalyzer:
    """Seed Analyzer: per-touch bitmask updates, object-row flush."""

    def __init__(self, kernel: str, grid, sampler_desc: str):
        self.kernel = kernel
        self.grid = tuple(int(g) for g in grid)
        self.sampler_desc = sampler_desc
        self._maps: Dict[str, Dict[int, SectorHistory]] = {}
        self._regions: Dict[str, RegionInfo] = {}
        self._contributors: Dict[str, set] = {}
        self._n_records = 0
        self._dropped = 0

    def ingest(self, buf: ReferenceTraceBuffer) -> None:
        for region in buf.regions.values():
            self._regions.setdefault(region.name, region)
            self._maps.setdefault(region.name, {})
            self._contributors.setdefault(region.name, set())
        for rec in buf.records:
            self._ingest_record(rec)
        self._dropped += buf.dropped

    def _ingest_record(self, rec: AccessRecord) -> None:
        self._n_records += 1
        smap = self._maps.setdefault(rec.array, {})
        region = self._regions.get(rec.array)
        words = region.geometry.sublanes if region else 8
        pid = linearize(rec.program_id, self.grid)
        self._contributors.setdefault(rec.array, set()).add(pid)
        for tag, woff in rec.touches:
            hist = smap.get(tag)
            if hist is None:
                hist = SectorHistory(words=words)
                smap[tag] = hist
            hist.update(woff, pid)

    def flush(self) -> Heatmap:
        region_maps: List[RegionHeatmap] = []
        for name, smap in sorted(self._maps.items()):
            region = self._regions.get(name)
            if region is None:
                region = RegionInfo(
                    name=name,
                    geometry=TileGeometry(shape=(8, 128), itemsize=4, name=name),
                )
            rows = tuple(
                HeatRow(
                    region=name,
                    tag=tag,
                    word_temps=tuple(h.word_temps()),
                    sector_temp=h.sector_temp(),
                )
                for tag, h in sorted(smap.items())
            )
            region_maps.append(
                RegionHeatmap(
                    region=region,
                    rows=rows,
                    n_programs=len(self._contributors.get(name, ())),
                )
            )
        return Heatmap(
            kernel=self.kernel,
            grid=self.grid,
            sampler=self.sampler_desc,
            regions=tuple(region_maps),
            n_records=self._n_records,
            dropped=self._dropped,
        )


def analyze_reference(
    kernel: KernelSpec,
    sampler: Optional[GridSampler] = None,
    dynamic_context: Optional[Dict[str, np.ndarray]] = None,
) -> Heatmap:
    """Seed collect + ingest + flush (the golden path)."""
    sampler = sampler or GridSampler()
    buf, _ = collect_reference(kernel, sampler, dynamic_context)
    an = ReferenceAnalyzer(kernel.name, kernel.grid, sampler.describe())
    an.ingest(buf)
    return an.flush()


def drain_dynamic_reference(
    kernel_name: str,
    grid,
    operand: OperandSpec,
    index_trace: np.ndarray,
    sampler: Optional[GridSampler] = None,
    valid_mask: Optional[np.ndarray] = None,
) -> ReferenceTraceBuffer:
    """Seed Level-2 drain: per-index Python divmod loop."""
    sampler = sampler or GridSampler()
    grid = tuple(int(g) for g in grid)
    buf = ReferenceTraceBuffer()
    buf.register_region(
        RegionInfo(operand.name, operand.geometry, space=operand.space)
    )
    geom = operand.geometry
    rows, cols = geom.shape2d
    for pid in sampled_grid(grid, sampler):
        lin = int(np.ravel_multi_index(pid, grid)) if grid else 0
        row = np.asarray(index_trace[lin])
        if valid_mask is not None:
            row = row[np.asarray(valid_mask[lin])]
        row = row[row >= 0]
        touches = set()
        for fi in row:
            r, c = divmod(int(fi), cols) if cols else (0, 0)
            touches.add((geom.sector_tag(r, c), geom.word_offset(r, c)))
        buf.append(
            AccessRecord(
                array=operand.name,
                site=f"{kernel_name}/{operand.name}#trace",
                space=operand.space,
                kind=operand.kind,
                program_id=pid,
                touches=tuple(sorted(touches)),
            )
        )
    return buf
