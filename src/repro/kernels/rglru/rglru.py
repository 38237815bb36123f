"""RG-LRU linear-recurrence Pallas TPU kernel, tunable (bt, bf).

The recurrence is elementwise over features and sequential over time — a
pure VPU/bandwidth workload. The kernel streams (time-block x feature-block)
tiles through VMEM while the recurrent state h stays VMEM-resident per
feature block; time is scanned with an in-kernel fori_loop over the tile's
rows. Tiles:

    bt — time rows per DMA (amortizes HBM descriptor cost; the paper's
         "wide tile" axis: the feature dim is lane-contiguous),
    bf — features per block (bounds the VMEM-resident state slice).

Grid: (B, F/bf, S/bt) with time innermost (carries state in scratch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, h0_ref, y_ref, hout_ref, h_ref, *, bt: int, n_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)   # [bt, bf] decay
    x = x_ref[0].astype(jnp.float32)   # [bt, bf] pre-gated input

    def step(t, h):
        h_new = a[t] * h + x[t]
        y_ref[0, t, :] = h_new.astype(y_ref.dtype)
        return h_new

    h = jax.lax.fori_loop(0, bt, step, h_ref[0])
    h_ref[...] = h[None]

    @pl.when(it == n_t - 1)
    def _():
        hout_ref[0] = h.astype(hout_ref.dtype)


def rglru_scan(
    a: jnp.ndarray,
    x: jnp.ndarray,
    h0: jnp.ndarray,
    tile: tuple[int, int] = (128, 512),
    interpret: bool = False,
):
    """Scan h_t = a_t * h_{t-1} + x_t.

    a, x: [B, S, F] (decay and pre-gated input); h0: [B, F].
    Returns (y [B, S, F], h_final [B, F]).
    """
    b, s, f = a.shape
    bt, bf = min(tile[0], s), min(tile[1], f)
    if s % bt or f % bf:
        raise ValueError(f"tile {(bt, bf)} must divide ({s}, {f})")
    n_t = s // bt

    kernel = functools.partial(_rglru_kernel, bt=bt, n_t=n_t)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(b, f // bf, n_t),
        in_specs=[
            pl.BlockSpec((1, bt, bf), lambda bb, jf, it: (bb, it, jf)),
            pl.BlockSpec((1, bt, bf), lambda bb, jf, it: (bb, it, jf)),
            pl.BlockSpec((1, bf), lambda bb, jf, it: (bb, jf)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bf), lambda bb, jf, it: (bb, it, jf)),
            pl.BlockSpec((1, bf), lambda bb, jf, it: (bb, jf)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), x.dtype),
            jax.ShapeDtypeStruct((b, f), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, x, h0)
    return y, h_last
