"""Jit'd wrapper + registry declaration for the tiled matmul kernel.

Problem dims: {"m", "k", "n"}. Tile rank 3 = (bm, bk, bn). The VMEM working
set per grid step is a(bm,bk) + b(bk,bn) + out(bm,bn) + acc f32(bm,bn) — the
TPU analogue of the paper's threads-per-block legality bound.

The model stack tunes one cell per FF block: the up projections run the
tile as (bm, bk, bn) over [m, k] @ [k, n] and the down projection runs it
transposed, (bm, bn, bk) over [m, n] @ [n, k] (``models.transformer``). So
``bk`` and ``bn`` must divide the weight dims exactly (weights are never
padded; activation rows are, so ``bm`` need not divide ``m``), and the
working set is that of the larger orientation.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax

from repro.core import registry
from repro.core.cost_model import TileWorkload
from repro.core.tiling import TileConstraints, TileShape, cdiv, dtype_bytes, round_up
from repro.kernels.matmul.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def mm(a, b, tile=(256, 512, 256), interpret: bool = False, layer=None):
    return matmul(a, b, tile=tile, interpret=interpret, layer=layer)


def _constraints(problem: Mapping[str, int]) -> TileConstraints:
    m, k, n = problem["m"], problem["k"], problem["n"]
    return TileConstraints(
        rank=3, max_dims=(m, k, n),
        mxu_dims=(0, 1, 2), lane_dim=2, sublane_dim=0, exact_dims=(1, 2),
    )


def _vmem_bytes(tile: TileShape, problem: Mapping[str, int], dtype: str) -> float:
    bm, bk, bn = tile
    b = dtype_bytes(dtype)
    # a + b + out + f32 acc, for the larger of the two orientations.
    return bk * bn * b + bm * (bk + bn) * b + bm * max(bk, bn) * 4


def _workload(tile: TileShape, problem: Mapping[str, int], dtype: str) -> TileWorkload:
    bm, bk, bn = tile
    b = dtype_bytes(dtype)
    # MXU padding waste if block dims are not multiples of the MXU dim is
    # handled via pad_waste at sweep time using the lane count as a proxy.
    waste_m = round_up(bm, 8) / bm
    waste_n = round_up(bn, 128) / bn
    return TileWorkload(
        flops=2.0 * bm * bk * bn,
        hbm_bytes=float((bm * bk + bk * bn) * b)
        + float(bm * bn * b) / max(1, problem["k"] // bk),
        row_segments=bm,                      # A-tile rows (strided when bk < k)
        row_stride_bytes=float(problem["k"] * b),
        pad_waste=waste_m * waste_n,
    )


def _n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    bm, bk, bn = tile
    return (
        cdiv(problem["m"], bm) * cdiv(problem["k"], bk) * cdiv(problem["n"], bn)
    )


def _divisor_block(extent: int, cap: int) -> int:
    """The largest lane-aligned block <= ``cap`` dividing ``extent`` (the
    whole extent when none does)."""
    for block in range(cap - cap % 128, 0, -128):
        if extent % block == 0:
            return block
    return extent


def _default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    m, k, n = problem["m"], problem["k"], problem["n"]
    # Wide-minor-first heuristic (the 32x4 principle, MXU-scaled): large bn
    # for lane contiguity, bm sized to keep the f32 accumulator modest, bk
    # grown to amortize the accumulator over more MXU work. bk and bn divide
    # the weight dims (see the module docstring).
    bn = n if n <= 512 else _divisor_block(n, 512)
    bm = min(256, m)
    bk = k if k <= 512 else _divisor_block(k, 512)
    return TileShape((bm, bk, bn))


registry.register(registry.KernelSpec(
    name="matmul",
    constraints=_constraints,
    vmem_bytes=_vmem_bytes,
    workload=_workload,
    n_tiles=_n_tiles,
    default_tile=_default_tile,
))
