"""Tile geometry: the TPU analogue of CUTHERMO's word/sector granularity.

CUTHERMO (GPU): a 128 B cache line splits into four 32 B *sectors* (the
memory-transaction unit); each sector holds eight 4 B *words* (the
thread-access unit).  Distinct-warp counts are kept per word AND per
sector.

TPU: the HBM<->VMEM transfer/layout unit is the *native tile* —
(8, 128) for 4-byte dtypes, (16, 128) for 2-byte, (32, 128) for 1-byte.
The lane-vector a VPU op touches is one *sublane row*: (1, 128).  So:

    sector  -> native tile      (8/16/32 sublane rows x 128 lanes)
    word    -> sublane row      ((1,128) vector, 512/256/128 bytes)

and an f32 tile has exactly 8 words per sector, mirroring NVIDIA's
8 x 4 B words per 32 B sector.  A grid program that touches one sublane
of a tile still drags the whole tile across the HBM boundary — the same
economics as a warp touching one word of a sector.

Addresses here are *element* offsets inside a logical array, flattened
to the last-two-dims tiled layout; a "sector tag" identifies one tile of
one array; word offsets index sublane rows within that tile.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Sequence, Tuple

import numpy as np

LANES = 128

# sublanes per native tile, keyed by dtype itemsize (bytes)
SUBLANES_BY_ITEMSIZE = {8: 4, 4: 8, 2: 16, 1: 32}


def sublanes_for(itemsize: int) -> int:
    """Sublane count of the native tile for a dtype of ``itemsize`` bytes."""
    try:
        return SUBLANES_BY_ITEMSIZE[int(itemsize)]
    except KeyError as e:
        raise ValueError(f"unsupported itemsize {itemsize}") from e


def tile_rows(n_rows: int, itemsize: int) -> int:
    """Rows of the block a kernel fetches to reach one row of an array.

    One tile row of sublanes; or all ``n_rows`` when they are not a whole
    number of tile rows, since a block must then span the array (Mosaic's
    (8, 128) block rule).
    """
    sub = sublanes_for(itemsize)
    return sub if n_rows % sub == 0 else n_rows


def words_per_sector(itemsize: int) -> int:
    """Number of 'words' (sublane rows) per 'sector' (native tile)."""
    return sublanes_for(itemsize)


@dataclasses.dataclass(frozen=True)
class TileGeometry:
    """Geometry of one logical array as a word/sector grid.

    The last two array dims map to (sublane, lane); leading dims are
    flattened into rows of tiles.  1-D arrays are treated as (1, n).
    """

    shape: Tuple[int, ...]
    itemsize: int
    name: str = "array"

    @property
    def shape2d(self) -> Tuple[int, int]:
        if len(self.shape) == 0:
            return (1, 1)
        if len(self.shape) == 1:
            # 1-D arrays are stored as rows of 128 lanes: element i lives at
            # (i // 128, i % 128).  A contiguous run therefore walks sublane
            # rows — this is what makes the SpMV rowOffsets misalignment
            # (paper Fig. 7) visible at word granularity.
            return (max(1, math.ceil(self.shape[0] / LANES)), LANES)
        rows = int(np.prod(self.shape[:-1], dtype=np.int64))
        return (rows, self.shape[-1])

    @property
    def sublanes(self) -> int:
        return sublanes_for(self.itemsize)

    @property
    def lane_tiles(self) -> int:
        """Tiles along the lane (minor) dimension, padded up."""
        return max(1, math.ceil(self.shape2d[1] / LANES))

    @property
    def sublane_tiles(self) -> int:
        """Tiles along the sublane (major) dimension, padded up."""
        return max(1, math.ceil(self.shape2d[0] / self.sublanes))

    @property
    def n_sectors(self) -> int:
        return self.lane_tiles * self.sublane_tiles

    @property
    def sector_bytes(self) -> int:
        return self.sublanes * LANES * self.itemsize

    @property
    def word_bytes(self) -> int:
        return LANES * self.itemsize

    # -- address mapping ---------------------------------------------------

    def sector_tag(self, row: int, col: int) -> int:
        """Sector tag for element (row, col) of the 2-D view."""
        st = row // self.sublanes
        lt = col // LANES
        return st * self.lane_tiles + lt

    def word_offset(self, row: int, col: int) -> int:  # noqa: ARG002
        """Word (sublane-row) offset within the sector for element (row, col)."""
        return row % self.sublanes

    def tag_to_coords(self, tag: int) -> Tuple[int, int]:
        """Inverse of sector_tag: top-left element (row, col) of the tile."""
        st, lt = divmod(tag, self.lane_tiles)
        return st * self.sublanes, lt * LANES

    def slice_to_touch_arrays(
        self,
        row_start: int,
        row_stop: int,
        col_start: int,
        col_stop: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized ``slice_to_touches``: (tags, words) int64 arrays.

        Row-major order (row outer, lane tile inner), identical to the
        generator version; each (tag, word) pair appears exactly once.
        """
        rows, cols = self.shape2d
        row_start = max(0, row_start)
        col_start = max(0, col_start)
        row_stop = min(rows, row_stop)
        col_stop = min(cols, col_stop)
        if row_stop <= row_start or col_stop <= col_start:
            z = np.empty(0, dtype=np.int64)
            return z, z
        lt0 = col_start // LANES
        lt1 = (col_stop - 1) // LANES
        r = np.arange(row_start, row_stop, dtype=np.int64)
        lt = np.arange(lt0, lt1 + 1, dtype=np.int64)
        tags = ((r // self.sublanes) * self.lane_tiles)[:, None] + lt[None, :]
        words = np.broadcast_to((r % self.sublanes)[:, None], tags.shape)
        return tags.reshape(-1), words.reshape(-1).copy()

    def slice_to_touches(
        self,
        row_start: int,
        row_stop: int,
        col_start: int,
        col_stop: int,
    ) -> Iterable[Tuple[int, int]]:
        """Yield (sector_tag, word_offset) pairs touched by a 2-D slice.

        The slice is clipped to the array bounds.  This enumerates *words*
        (sublane rows), not elements: touching any lane of a sublane row
        touches the whole (1,128) word, exactly as touching any byte of a
        GPU word touches the word.
        """
        tags, words = self.slice_to_touch_arrays(
            row_start, row_stop, col_start, col_stop
        )
        for t, w in zip(tags.tolist(), words.tolist()):
            yield (t, w)

    def run_to_touch_arrays(
        self, start: int, stop: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized ``run_to_touches``: (tags, words) int64 arrays."""
        n = self.shape[0] if len(self.shape) == 1 else int(np.prod(self.shape))
        start = max(0, start)
        stop = min(n, stop)
        if stop <= start:
            z = np.empty(0, dtype=np.int64)
            return z, z
        row = np.arange(start // LANES, (stop - 1) // LANES + 1, dtype=np.int64)
        tags = (row // self.sublanes) * self.lane_tiles
        return tags, row % self.sublanes

    def run_to_touches(self, start: int, stop: int) -> Iterable[Tuple[int, int]]:
        """(sector_tag, word) pairs touched by a contiguous 1-D element run."""
        tags, words = self.run_to_touch_arrays(start, stop)
        for t, w in zip(tags.tolist(), words.tolist()):
            yield (t, w)

    def flat_to_touch_arrays(
        self, flat: np.ndarray, origin: Tuple[int, int] = (0, 0)
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized flat-element-index -> (tags, words), with an origin
        shift (the Level-2 / dynamic-gather address path)."""
        flat = np.asarray(flat, dtype=np.int64).reshape(-1)
        _, cols = self.shape2d
        r = flat // cols + origin[0]
        c = flat % cols + origin[1]
        tags = (r // self.sublanes) * self.lane_tiles + c // LANES
        return tags, r % self.sublanes

    def is_aligned_slice(
        self, row_start: int, row_stop: int, col_start: int, col_stop: int
    ) -> bool:
        """True iff the slice starts/ends on tile boundaries (or array edge)."""
        rows, cols = self.shape2d
        ok_r = (row_start % self.sublanes == 0) and (
            row_stop % self.sublanes == 0 or row_stop >= rows
        )
        ok_c = (col_start % LANES == 0) and (
            col_stop % LANES == 0 or col_stop >= cols
        )
        return ok_r and ok_c


def block_to_2d(
    shape: Sequence[int], index: Sequence[int], block_shape: Sequence[int]
) -> Tuple[int, int, int, int]:
    """Map an N-D block (block coords * block_shape) to a 2-D slice.

    Leading dims are flattened row-major into the sublane axis, matching
    TileGeometry.shape2d.  Returns (row_start, row_stop, col_start,
    col_stop).  Only exact when at most the last two dims are blocked or
    leading blocked dims have block size 1 or full — the collector checks
    and falls back to per-element enumeration otherwise.
    """
    shape = tuple(int(s) for s in shape)
    index = tuple(int(i) for i in index)
    block_shape = tuple(int(b) for b in block_shape)
    if len(shape) == 0:
        return (0, 1, 0, 1)
    if len(shape) == 1:
        c0 = index[0] * block_shape[0]
        return (0, 1, c0, c0 + block_shape[0])
    # column (lane) dim
    c0 = index[-1] * block_shape[-1]
    c1 = c0 + block_shape[-1]
    # row (sublane) dim: flatten leading dims
    lead_shape = shape[:-1]
    lead_index = index[:-1]
    lead_block = block_shape[:-1]
    # starting flattened row of the block
    starts = [i * b for i, b in zip(lead_index, lead_block)]
    row0 = 0
    for s, dim in zip(starts, lead_shape):
        row0 = row0 * dim + s
    # size of the block in flattened rows: exact iff all leading blocked
    # dims except possibly the last leading dim are size-1 blocks, or the
    # trailing leading dims are full.
    nrows = int(np.prod(lead_block, dtype=np.int64))
    contiguous = True
    # block is contiguous in flattened rows iff for every leading dim i
    # with block>1, all dims after i (within leading dims) are fully blocked
    for i, b in enumerate(lead_block):
        if b > 1:
            for j in range(i + 1, len(lead_block)):
                if lead_block[j] != lead_shape[j]:
                    contiguous = False
    if not contiguous:
        raise ValueError("non-contiguous leading block; enumerate per-dim")
    return (row0, row0 + nrows, c0, c1)
