"""Flash-decode (split-KV) attention: one query over a long KV cache.

Decode is the paper's "changed external condition" applied to attention: the
computation is the same dot-product attention as prefill, but the problem
geometry collapses to a single query row streaming over a cache of ``skv``
keys — a different cell of the (problem, hardware) grid, with its own
optimal tile. The tunable dimension here is ``bkv``, the KV split size: the
cache is processed in ``skv / bkv`` blocks with online-softmax statistics
carried across blocks and the partial results LSE-combined, exactly the
flash-decoding decomposition.

Two implementations with identical math:

``flash_decode``      — Pallas TPU kernel. Grid ``(B, Hkv, skv/bkv)`` with
    the KV dimension innermost ("arbitrary"); the grouped queries of one KV
    head ([rep, d], GQA without any kv-repeat materialization) stay resident
    in VMEM while K/V blocks stream; running max / denominator / accumulator
    live in VMEM scratch. Fully-masked KV blocks (beyond ``pos``, or left of
    the sliding window) are skipped with ``pl.when``.
``flash_decode_ref``  — the same online-softmax chunked over ``bkv`` in pure
    ``lax.scan``; differentiable, lowers on every backend, and is the decode
    lowering a resolved plan tile selects on non-TPU hosts.

Shared semantics: q ``[B, Hq, D]`` (one query per sequence), k/v caches
``[B, Hkv, S, D]``, ``pos`` the (traced) absolute position of the query.
``kv_pos`` optionally maps cache slot -> absolute key position (ring-buffer
caches; ``-1`` marks never-written slots); when omitted the cache is linear
(slot i holds position i). A key is visible iff ``0 <= kv_pos <= pos`` and,
with ``window``, ``kv_pos > pos - window``. Optional logit ``softcap``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import block_fits
from repro.kernels.flash_attention.ref import fit_bkv  # noqa: F401 (re-export)

NEG_INF = -2.0e30

# Grouped-query rows are padded up to one fp32 sublane so the [rep, bkv]
# logits block is a legal VPU/MXU operand even for MQA (rep == 1).
MIN_GROUP_ROWS = 8

# TPU vector lanes: the minor dim of a block must be a multiple of this or
# the whole array dim.
LANES = 128


def split_legal(bkv: int, s: int) -> bool:
    """Whether the Pallas kernel can run a ``bkv`` split of an ``s``-slot
    cache on a TPU: the split divides the cache, and the ``kv_pos`` block
    ``[1, bkv]`` is lane-aligned or spans the whole cache."""
    return block_fits(bkv, s, LANES)


def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, kp_ref, out_ref, m_ref, l_ref, acc_ref,
    *, scale: float, window: Optional[int], softcap: Optional[float],
    bkv: int, n_kv: int, monotonic: bool,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    k_start = ik * bkv
    # Block-level skipping needs slot order == position order; a ring cache
    # (monotonic=False) interleaves old and new positions, so every block is
    # visited and masking happens per-key.
    relevant = jnp.asarray(True)
    if monotonic:
        relevant = jnp.logical_and(relevant, k_start <= pos)
        if window is not None:
            relevant = jnp.logical_and(relevant, k_start + bkv - 1 > pos - window)

    @pl.when(relevant)
    def _():
        kp = kp_ref[0, :]                                     # [bkv] abs pos
        valid = jnp.logical_and(kp >= 0, kp <= pos)
        if window is not None:
            valid = jnp.logical_and(valid, kp > pos - window)
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [rep_p, d]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bkv, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # [rep_p, bkv]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid[None, :], s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)                   # [bkv, d]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _():
        out_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        ).astype(out_ref.dtype)


def flash_decode(
    q, k, v, *, pos, kv_pos=None, window: Optional[int] = None,
    softcap: Optional[float] = None, scale: Optional[float] = None,
    bkv: int = 512, interpret: bool = False,
):
    """q [B, Hq, D] x cache k/v [B, Hkv, S, D] -> [B, Hq, D].

    ``pos`` is the query's absolute position (traced scalar is fine);
    ``kv_pos`` [S] maps cache slots to absolute positions (ring caches),
    default linear. ``bkv`` must divide the cache length S; on a TPU it must
    also satisfy :func:`split_legal` (interpret mode does not check it).
    """
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
    n_rep = hq // hkv
    rep_p = max(n_rep, MIN_GROUP_ROWS)
    scale = scale if scale is not None else d ** -0.5
    bkv = min(bkv, s)
    if s % bkv:
        raise ValueError(f"decode tile bkv={bkv} must divide cache len {s}")
    n_kv = s // bkv

    monotonic = kv_pos is None
    if kv_pos is None:
        kv_pos = jnp.arange(s, dtype=jnp.int32)
    kp = jnp.asarray(kv_pos, jnp.int32).reshape(1, s)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    qg = q.reshape(b, hkv, n_rep, d)
    if rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - n_rep), (0, 0)))

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap,
        bkv=bkv, n_kv=n_kv, monotonic=monotonic,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # pos [1]
            pl.BlockSpec((1, 1, rep_p, d), lambda bb, h, ik: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bb, h, ik: (bb, h, ik, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda bb, h, ik: (bb, h, ik, 0)),
            pl.BlockSpec((1, bkv), lambda bb, h, ik: (0, ik)),  # kv_pos
        ],
        out_specs=pl.BlockSpec((1, 1, rep_p, d), lambda bb, h, ik: (bb, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep_p, 128), jnp.float32),   # running max
            pltpu.VMEM((rep_p, 128), jnp.float32),   # running denom
            pltpu.VMEM((rep_p, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos_arr, qg, k, v, kp)
    return out[:, :, :n_rep].reshape(b, hq, d)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "bkv"),
)
def flash_decode_ref(
    q, k, v, *, pos, kv_pos=None, window: Optional[int] = None,
    softcap: Optional[float] = None, scale: Optional[float] = None,
    bkv: int = 512,
):
    """Chunked online-softmax decode, scanned over KV splits of ``bkv``.

    Same math as the Pallas kernel (GQA grouped contraction, no kv repeat);
    a non-dividing ``bkv`` is snapped to the largest divisor of the cache
    length — callers that care about plan fidelity check divisibility first
    (see ``models.attention.attn_decode``).
    """
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bkv = fit_bkv(bkv, s)
    n_kv = s // bkv
    if kv_pos is None:
        kv_pos = jnp.arange(s, dtype=jnp.int32)

    qg = q.reshape(b, hkv, n_rep, d).astype(jnp.float32) * scale
    kc = k.reshape(b, hkv, n_kv, bkv, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_kv, bkv, d).transpose(2, 0, 1, 3, 4)
    pc = jnp.asarray(kv_pos, jnp.int32).reshape(n_kv, bkv)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, kp = xs
        s_blk = jnp.einsum(
            "bgrd,bgkd->bgrk", qg, k_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )                                                  # [B,Hkv,rep,bkv]
        if softcap is not None:
            s_blk = softcap * jnp.tanh(s_blk / softcap)
        valid = jnp.logical_and(kp >= 0, kp <= pos)
        if window is not None:
            valid = jnp.logical_and(valid, kp > pos - window)
        s_blk = jnp.where(valid[None, None, None], s_blk, NEG_INF)
        m_cur = jnp.max(s_blk, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bgrk,bgkd->bgrd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, n_rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, n_rep), jnp.float32)
    acc0 = jnp.zeros((b, hkv, n_rep, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged-pool indirection (serve/pool.py). Pages hold token rows of one
# request's cache behind a page table: ``pages`` [n_pages, Hkv, page, D],
# ``page_table`` [n_pt] int32 mapping each logical page index of the request
# to its physical page id. A scanned layer stack keeps its layers' pages in
# one stacked array ``[L, n_pages, Hkv, page, D]`` and passes ``layer``: the
# read and the write then index that layer directly, so neither ever moves
# more than the request's pages or rows. Reads and writes here; the
# attention math delegates to the refs above unchanged, so paged and dense
# lowerings cannot drift.
# ---------------------------------------------------------------------------

def paged_gather(pages, page_table, layer=None):
    """Dense [1, Hkv, n_pt*page, D] cache view of one request's pages
    (of layer ``layer`` when ``pages`` is stacked).

    Slots past the request's written length hold garbage (unallocated table
    entries point at physical page 0) — callers mask them positionally: the
    view is linear, so slot i is absolute position i and the usual
    ``kv_pos <= pos`` / ``kv_pos < start`` rules hide everything unwritten.

    Each page is read by its own ``dynamic_slice``. An XLA gather here lets
    the TPU compiler push the attention's KV split back through the gather
    into the pages operand, copying the whole pool (and relayouting it) on
    every call; a page-sized dynamic slice only ever reads its page.
    """
    n_pt = page_table.shape[0]
    hkv, page, d = pages.shape[-3:]
    lead = () if layer is None else (layer,)
    size = (1,) * len(lead) + (1, hkv, page, d)
    gathered = jnp.concatenate([
        jax.lax.dynamic_slice(pages, lead + (page_table[j], 0, 0, 0),
                              size).reshape(1, hkv, page, d)
        for j in range(n_pt)])                      # [n_pt, Hkv, page, D]
    return gathered.transpose(1, 0, 2, 3).reshape(1, hkv, n_pt * page, d)


def paged_write(pages, page_table, x, start, layer=None):
    """Write ``x`` [1, Hkv, c, D] into pages (of layer ``layer`` when
    ``pages`` is stacked) at positions ``start .. start+c-1``. Returns the
    updated pages array.

    Each write is a ``dynamic_update_slice`` of whole rows at
    ``(layer, phys, :, off, :)``: one per page the span touches when
    ``start`` is static (a chunk), one per row when it is traced (decode's
    ``pos``). Only the ``c`` rows change, so a donated or loop-carried
    array updates in place; a scatter indexing the page and row axes
    around the head axis makes XLA relayout the whole array instead."""
    rows = x[0].astype(pages.dtype)                 # [Hkv, c, D]
    c = rows.shape[1]
    page = pages.shape[-2]
    if isinstance(start, jax.Array):
        cuts = list(range(c + 1))
    else:
        start = int(start)
        cuts = sorted({0, c} | {b - start for b in range(
            (start // page + 1) * page, start + c, page)})
    for a, b in zip(cuts[:-1], cuts[1:]):
        pos = start + a
        idx = (page_table[pos // page], 0, pos % page, 0)
        upd = rows[None, :, a:b]                    # [1, Hkv, b-a, D]
        if layer is not None:
            idx, upd = (layer,) + idx, upd[None]
        pages = jax.lax.dynamic_update_slice(pages, upd, idx)
    return pages


def flash_decode_paged_ref(
    q, k_pages, v_pages, page_table, *, pos,
    window: Optional[int] = None, softcap: Optional[float] = None,
    scale: Optional[float] = None, bkv: int = 512,
):
    """``flash_decode_ref`` over a page-table-backed cache: gather the
    request's pages into the linear view and run the identical online
    softmax (slots beyond ``pos`` are masked by the linear position rule,
    which also hides unallocated-table garbage)."""
    k = paged_gather(k_pages, page_table)
    v = paged_gather(v_pages, page_table)
    return flash_decode_ref(q, k, v, pos=pos, window=window,
                            softcap=softcap, scale=scale, bkv=bkv)
