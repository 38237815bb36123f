"""Tile shapes and the constraint system that filters candidate tilings.

The paper sweeps CUDA block dims (e.g. 4x8 vs 8x4 vs 32x4) subject to the
hardware's constraints (<=512 threads/block, active-thread ceilings). The TPU
analogue implemented here: a :class:`TileShape` is a tuple of block dims for a
Pallas ``BlockSpec``; :class:`TileConstraints` encodes the hardware's legality
and efficiency rules (VMEM working-set fit, lane/sublane alignment, MXU
divisibility); :func:`enumerate_tiles` generates the legal candidate space the
autotuner sweeps — the exact counterpart of the paper's tile-dimension axis in
Fig. 3.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.core.hardware import HardwareModel

DTYPE_BYTES = {
    "float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
    "int32": 4, "uint8": 1, "float64": 8,
}


def dtype_bytes(dtype) -> int:
    return DTYPE_BYTES[str(dtype)]


@dataclasses.dataclass(frozen=True, order=True)
class TileShape:
    """A block shape for one operand-tiling decision, e.g. (bm, bk, bn)."""

    dims: Tuple[int, ...]

    def __iter__(self):
        return iter(self.dims)

    def __getitem__(self, i):
        return self.dims[i]

    def __len__(self):
        return len(self.dims)

    @property
    def size(self) -> int:
        return math.prod(self.dims)

    def __str__(self) -> str:
        return "x".join(str(d) for d in self.dims)


@dataclasses.dataclass(frozen=True)
class TileConstraints:
    """Legality/efficiency constraints for a kernel's tile space on given hw.

    ``vmem_operands`` maps a candidate tile to the per-grid-step VMEM working
    set in bytes; kernels provide it since only they know which operands a
    tile touches (e.g. matmul holds bm*bk + bk*bn + bm*bn).
    """

    rank: int
    # Per-dim upper bounds (problem dims; tiles never exceed the problem).
    max_dims: Tuple[int, ...]
    # Dims that feed the MXU contraction want multiples of mxu_dim.
    mxu_dims: Tuple[int, ...] = ()
    # The minor (lane) dim index, wants multiples of lane_count.
    lane_dim: Optional[int] = None
    # The second-minor (sublane) dim index.
    sublane_dim: Optional[int] = None
    # Fraction of VMEM the tile working set may use (double-buffering => 0.5).
    vmem_fraction: float = 0.5
    # Dims the kernel cannot pad: the tile must divide the problem extent
    # and be a whole multiple of the dim's alignment (or the whole extent),
    # which is what a Pallas TPU BlockSpec accepts.
    exact_dims: Tuple[int, ...] = ()

    def alignment(self, hw: HardwareModel, dtype: str, dim_index: int) -> int:
        if dim_index == self.lane_dim:
            return hw.lane_count
        if dim_index == self.sublane_dim:
            return hw.sublane[dtype] if dtype in ("float32", "bfloat16") else 8
        if dim_index in self.mxu_dims:
            return hw.mxu_dim
        return 1

    def block_legal(self, tile: "TileShape", hw: HardwareModel,
                    dtype: str) -> bool:
        """Whether every ``exact_dims`` entry of ``tile`` divides its extent
        and is aligned (or spans the whole extent)."""
        return all(block_fits(tile[i], self.max_dims[i],
                              self.alignment(hw, dtype, i))
                   for i in self.exact_dims)


def _candidates_for_dim(limit: int, align: int) -> List[int]:
    """Powers-of-two multiples of ``align`` up to ``limit`` (plus limit itself)."""
    out = []
    v = align
    while v < limit:
        out.append(v)
        v *= 2
    out.append(limit)
    # Dedup while preserving order.
    seen, uniq = set(), []
    for x in out:
        if x not in seen:
            seen.add(x)
            uniq.append(x)
    return uniq


def enumerate_tiles(
    constraints: TileConstraints,
    hw: HardwareModel,
    dtype: str,
    vmem_bytes_fn,
    max_candidates: int = 512,
) -> List[TileShape]:
    """Generate the legal tile space — the sweep axis of the paper's Fig. 3.

    ``vmem_bytes_fn(tile) -> int`` gives the per-step VMEM working set.
    Candidates violating the VMEM budget are discarded, mirroring the paper's
    "threads per block <= 512" legality filter, and so are candidates whose
    ``exact_dims`` the kernel could not run (see
    :meth:`TileConstraints.block_legal`).
    """
    axes: List[List[int]] = []
    for i in range(constraints.rank):
        align = constraints.alignment(hw, dtype, i)
        limit = constraints.max_dims[i]
        if limit <= align:
            axes.append([limit])
        else:
            axes.append(_candidates_for_dim(limit, align))

    budget = hw.vmem_bytes * constraints.vmem_fraction
    tiles: List[TileShape] = []
    for dims in itertools.product(*axes):
        t = TileShape(tuple(dims))
        if (vmem_bytes_fn(t) <= budget
                and constraints.block_legal(t, hw, dtype)):
            tiles.append(t)
    # Prefer larger tiles first (fewer grid steps) as the tie-break ordering.
    tiles.sort(key=lambda t: (-t.size, t.dims))
    return tiles[:max_candidates]


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def block_fits(block: int, extent: int, align: int) -> bool:
    """Whether a Pallas TPU block of ``block`` can tile ``extent`` with no
    padding: it divides the extent and is a multiple of ``align`` or the
    whole extent (the chip's compiler refuses any other block shape)."""
    return extent % block == 0 and (block == extent or block % align == 0)


def padded_extent(extent: int, tile: int) -> int:
    """Problem extent after padding to a whole number of tiles."""
    return cdiv(extent, tile) * tile


def grid_for(shape: Sequence[int], tile: TileShape) -> Tuple[int, ...]:
    assert len(shape) == len(tile)
    return tuple(cdiv(s, t) for s, t in zip(shape, tile))
