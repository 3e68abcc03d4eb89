"""Kernel spec types: what the profiler knows about one ``pallas_call``.

A :class:`KernelSpec` is the static description the Level-1 walker
(:mod:`repro.core.collector`) reads: the grid, every operand's block
shape and ``index_map``, VMEM scratch with its access model, and how to
rebuild the spec in another process.  The kernel modules build these
next to their ``pallas_call``, so this module is a leaf: it imports
only :mod:`repro.core.tiles` and numpy.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Optional, Tuple

import numpy as np

from .tiles import TileGeometry

__all__ = ["IndexMap", "KernelSpec", "OperandSpec", "ScratchSpec"]

IndexMap = Callable[..., Tuple[int, ...]]


@dataclasses.dataclass(frozen=True)
class OperandSpec:
    """Describes one pallas_call operand for the Level-1 walker."""

    name: str
    shape: Tuple[int, ...]
    dtype: np.dtype
    block_shape: Tuple[int, ...]
    index_map: IndexMap
    kind: str = "load"  # 'load' | 'store' | 'accum'
    space: str = "hbm"  # 'hbm' | 'vmem_scratch'
    # element offset of the array's origin inside its backing buffer —
    # models misaligned sub-array views (SpMV rowOffsets[r+1] analogue)
    origin: Tuple[int, int] = (0, 0)
    # True when the kernel touches this operand from ONE program only
    # (e.g. a pl.when(last)-guarded final store of a scratch accumulator)
    once: bool = False

    @property
    def geometry(self) -> TileGeometry:
        return TileGeometry(
            shape=self.shape, itemsize=np.dtype(self.dtype).itemsize, name=self.name
        )


@dataclasses.dataclass(frozen=True)
class ScratchSpec:
    """User-managed VMEM scratch (the SMEM analogue) with an access model.

    ``access_model(program_id)`` returns (row_start, row_stop, col_start,
    col_stop) slices the program touches, or None for "whole buffer".
    """

    name: str
    shape: Tuple[int, ...]
    dtype: np.dtype
    access_model: Optional[Callable[..., Iterable[Tuple[int, int, int, int]]]] = None
    kind: str = "accum"

    @property
    def geometry(self) -> TileGeometry:
        return TileGeometry(
            shape=self.shape, itemsize=np.dtype(self.dtype).itemsize, name=self.name
        )


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the Level-1 walker needs about one kernel launch."""

    name: str
    grid: Tuple[int, ...]
    operands: Tuple[OperandSpec, ...]
    scratch: Tuple[ScratchSpec, ...] = ()
    # optional dynamic access models keyed by operand name:
    # fn(program_id, **context_arrays) -> iterable of flat element indices
    dynamic: Tuple[Tuple[str, Callable[..., Iterable[int]]], ...] = ()
    # how to rebuild this spec in another process, if known.  Specs hold
    # index-map lambdas and cannot be pickled, so a ShardedCollector
    # worker rebuilds from this instead: either a registry ref
    # ("gemm:v01" — also rebuilds the seeded dynamic context) or a
    # ("module:function", args, kwargs) builder triple (see
    # ``sourced_spec``).
    source: Optional[object] = None
