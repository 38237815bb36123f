"""Flash attention Pallas TPU kernel with tunable (bq, bkv) block shapes.

Forward kernel: grid (B, Hq, Sq/bq, Skv/bkv) with the kv dimension innermost
("arbitrary"); online softmax carried in VMEM scratch (running max, running
denominator, f32 accumulator). Supports causal masking with a query offset,
sliding-window (local) attention, logit softcapping, and GQA via kv-head
index mapping. Fully-masked kv blocks are skipped with ``pl.when`` —
structurally visible in the lowered IR as predicated regions.

Tile roles, in the paper's terms: ``bkv`` is the lane-contiguous streaming
dimension (wide = fewer strided segments of the K/V HBM reads) and ``bq``
bounds the VMEM-resident accumulator — the same wide-first geometry as the
paper's 32x4, scaled to MXU/VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e30


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: Optional[int],
    softcap: Optional[float], q_offset: int, bq: int, bkv: int, n_kv: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_offset + iq * bq
    k_start = ik * bkv

    # Block-level relevance: skip kv blocks entirely above the causal
    # diagonal or entirely left of the window.
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + bq - 1)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + bkv - 1 > q_start - window
        )

    @pl.when(relevant)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # [bq, bkv]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), dtype=bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                  # [bq]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                       # [bq]
        p = jnp.exp(s - m_new[:, None])                       # [bq, bkv]
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)                   # [bkv, d]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # [bq, d]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _():
        out_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        ).astype(out_ref.dtype)


def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    softcap: Optional[float] = None, scale: Optional[float] = None,
    q_offset: int = 0, tile: tuple[int, int] = (512, 512),
    interpret: bool = False,
):
    """q [B, Hq, Sq, D] x k,v [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, bkv = min(tile[0], sq), min(tile[1], skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"tile {(bq, bkv)} must divide ({sq}, {skv})")
    n_kv = skv // bkv

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, bq=bq, bkv=bkv, n_kv=n_kv,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, hq, sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, bkv, d),
                lambda bb, h, iq, ik, n_rep=n_rep: (bb, h // n_rep, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, bkv, d),
                lambda bb, h, iq, ik, n_rep=n_rep: (bb, h // n_rep, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (lane-bcast)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
