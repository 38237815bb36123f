"""Mamba-2 SSD Pallas TPU kernel, tunable chunk length Q.

Grid (B, H, S/Q) with the chunk dimension innermost ("arbitrary"): the
[N, P] state is carried across chunks in VMEM scratch, each chunk does three
MXU contractions (CB^T, intra-chunk combine, state update) plus VPU decay
math. Q is the tile knob: large Q amortizes state I/O and raises MXU
occupancy ([Q,Q] scores), small Q bounds the VMEM logits buffer — the same
working-set-vs-parallelism trade the paper sweeps.

Inputs are pre-arranged by ops.py: log_a [B, H, S]; dtx [B, S, H, P];
Bm, C [B, S, N]; h0 [B, H, N, P]. Outputs: y [B, S, H, P], h_last like h0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(la_ref, x_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, h_ref,
                *, q: int, n_c: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    la = la_ref[0, 0].astype(jnp.float32)       # [Q]
    x = x_ref[0, :, 0, :].astype(jnp.float32)   # [Q, P]
    bm = b_ref[0].astype(jnp.float32)           # [Q, N]
    cm = c_ref[0].astype(jnp.float32)           # [Q, N]

    cum = jnp.cumsum(la)                        # [Q] inclusive
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(ii >= jj, jnp.exp(cum[:, None] - cum[None, :]), 0.0)

    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )                                           # [Q, Q]
    scores = cb * decay
    y_intra = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )                                           # [Q, P]

    h_prev = h_ref[...]                         # [N, P]
    y_inter = jax.lax.dot_general(
        cm, h_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ) * jnp.exp(cum)[:, None]                   # [Q, P]

    total = cum[q - 1]
    w = jnp.exp(total - cum)                    # [Q]
    h_new = jnp.exp(total) * h_prev + jax.lax.dot_general(
        bm * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # [N, P]
    h_ref[...] = h_new
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(ic == n_c - 1)
    def _():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(
    log_a: jnp.ndarray,   # [B, H, S]
    dtx: jnp.ndarray,     # [B, S, H, P]
    Bm: jnp.ndarray,      # [B, S, N]
    C: jnp.ndarray,       # [B, S, N]
    h0: jnp.ndarray,      # [B, H, N, P]
    chunk: int = 128,
    interpret: bool = False,
):
    b, s, h, p = dtx.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} not divisible by chunk {q}")
    n_c = s // q

    kernel = functools.partial(_ssd_kernel, q=q, n_c=n_c)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(b, h, n_c),
        in_specs=[
            pl.BlockSpec((1, 1, q), lambda bb, hh, ic: (bb, hh, ic)),
            pl.BlockSpec((1, q, 1, p), lambda bb, hh, ic: (bb, ic, hh, 0)),
            pl.BlockSpec((1, q, n), lambda bb, hh, ic: (bb, ic, 0)),
            pl.BlockSpec((1, q, n), lambda bb, hh, ic: (bb, ic, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bb, hh, ic: (bb, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, 1, p), lambda bb, hh, ic: (bb, ic, hh, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bb, hh, ic: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), dtx.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), dtx.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(log_a, dtx, Bm, C, h0)
    return y, h_last
