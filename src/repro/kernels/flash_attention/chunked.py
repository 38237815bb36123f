"""Chunked-prefill attention: one prompt chunk over the live KV cache.

Chunked prefill is the serving-side decomposition the warp/CUDA-tile papers
make at the kernel level: one large tiled launch (the whole-prompt prefill)
is split into schedulable sub-launches so the engine can interleave decode
steps between them. Each sub-launch is a *continuation*: chunk N's queries
sit at absolute positions ``start .. start+c-1`` and attend causally over
the KV written by chunks ``0..N-1`` plus the chunk itself — exactly the
whole-prompt computation restricted to those query rows.

Two lowerings share the math:

* **linear caches** reuse the existing ``q_offset`` continuation arithmetic
  of :mod:`repro.kernels.flash_attention.flash_attention` /
  :func:`~repro.kernels.flash_attention.ref.flash_attention_ref` — the
  caller slices the cache to the written prefix and passes
  ``q_offset=start`` (see ``models.attention.attn_prefill_chunk``);
* **ring-buffer caches** need an arbitrary slot -> absolute-position map,
  which static ``q_offset`` cannot express. :func:`flash_prefill_chunk_ref`
  below generalizes the online-softmax reference to traced ``q_pos`` /
  ``kv_pos`` arrays (the decode kernel's convention, lifted to ``Sq > 1``).

The tunable axes of the chunked-prefill *plan cell* are ``(chunk, bkv)``:
the chunk length (how much prompt one sub-launch covers — the resident
query block) and the KV split streamed under it. The cell is registered in
``ops.py``; VMEM capacity bounds the resident chunk per hardware model, so
the same prompt length compiles different chunk sizes on different models.

**Step packing** (:func:`flash_prefill_packed_ref`) lifts the chunk
continuation one level further: N independent requests' chunks are
segment-concatenated into ONE launch — queries carry a per-token segment id
next to their absolute position, keys carry the same pair, and visibility
requires segment equality on top of the causal position rule, so one
kernel invocation serves N requests without any cross-request attention.
This is the Model-Based-Warp-Overlapped-Tiling move applied at the serving
layer: independently-tiled work items overlap in one launch, and the
tunable ``(pack, bkv)`` cell (``packed_prefill`` in ``ops.py``) makes the
*pack width* — how many chunk tokens ride one step — a first-class
per-hardware-model tile axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.decode import paged_gather
from repro.kernels.flash_attention.ref import fit_bkv

NEG_INF = -2.0e30


def paged_prefix(k_pages, v_pages, page_table, n_prefix_pages: int, start,
                 layer=None):
    """Dense view of a chunk's visible cache prefix from the paged pool.

    Gathers the first ``n_prefix_pages`` table entries (a static count —
    ``cdiv(start, page)`` at trace time) and returns ``(k, v, kv_pos)``
    with k/v ``[1, Hkv, n_prefix_pages*page, D]`` and ``kv_pos`` marking
    slots at positions >= ``start`` as never written (-1). The mask does
    double duty: it hides the unwritten tail of a partially-filled last
    page AND a shared-prefix donor's own tokens past the shared length in
    a copy-on-write page (see serve/pool.py) — without it a prefix hit
    would attend the donor's divergent continuation. ``layer`` picks the
    layer of stacked pages (see ``decode.paged_gather``).
    """
    k = paged_gather(k_pages, page_table[:n_prefix_pages], layer)
    v = paged_gather(v_pages, page_table[:n_prefix_pages], layer)
    span = k.shape[2]
    pos = jnp.arange(span, dtype=jnp.int32)
    kv_pos = jnp.where(pos < start, pos, -1)
    return k, v, kv_pos


def flash_prefill_chunk_paged_ref(
    q, k_chunk, v_chunk, k_pages, v_pages, page_table, *,
    q_pos, start, n_prefix_pages: int,
    window: Optional[int] = None, softcap: Optional[float] = None,
    scale: Optional[float] = None, bkv: int = 512, layer=None,
):
    """``flash_prefill_chunk_ref`` over a paged cache prefix: gather the
    prefix pages (of ``layer`` when stacked), concatenate the chunk's own
    keys (positions ``q_pos``), and run the identical positioned online
    softmax."""
    if n_prefix_pages:
        kp, vp, pp = paged_prefix(
            k_pages, v_pages, page_table, n_prefix_pages, start, layer)
        k_all = jnp.concatenate([kp, k_chunk.astype(kp.dtype)], axis=2)
        v_all = jnp.concatenate([vp, v_chunk.astype(vp.dtype)], axis=2)
        kv_pos = jnp.concatenate([pp, jnp.asarray(q_pos, jnp.int32)])
    else:
        k_all, v_all = k_chunk, v_chunk
        kv_pos = jnp.asarray(q_pos, jnp.int32)
    return flash_prefill_chunk_ref(
        q, k_all, v_all, q_pos=q_pos, kv_pos=kv_pos,
        window=window, softcap=softcap, scale=scale, bkv=bkv)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "bkv"),
)
def flash_prefill_chunk_ref(
    q, k, v, *, q_pos, kv_pos=None, window: Optional[int] = None,
    softcap: Optional[float] = None, scale: Optional[float] = None,
    bkv: int = 512,
):
    """Online-softmax attention of a prompt chunk over positioned keys.

    q ``[B, Hq, Sq, D]`` — the chunk's queries at absolute positions
    ``q_pos`` [Sq] (traced ok). k/v ``[B, Hkv, Skv, D]`` — the keys visible
    to the chunk (cache history ++ the chunk's own keys); ``kv_pos`` [Skv]
    maps each key slot to its absolute position (``-1`` = never written;
    default linear ``arange``). A key is visible iff
    ``0 <= kv_pos <= q_pos`` (causal continuation) and, with ``window``,
    ``kv_pos > q_pos - window``.

    GQA grouped contraction (no kv-repeat materialization), scanned over KV
    splits of ``bkv`` — the same online-softmax update as
    ``flash_attention_ref`` with the static ``q_offset`` causal arithmetic
    generalized to arbitrary position maps, so ring-buffer caches chunk the
    same way linear ones do. A non-dividing ``bkv`` snaps to the largest
    divisor of ``Skv`` (``fit_bkv``).

    NOTE: ``flash_decode_ref`` (decode.py) is the ``Sq == 1`` special case
    of this scan. Those bodies are kept separate on purpose — each mirrors
    the structure of its Pallas kernel (decode: resident grouped rows;
    chunked: resident query block) — but a change to the masking or
    softmax-update rule in one almost certainly belongs in the other; the
    decode==prefill parity suites in tests/test_kernels_decode.py and
    tests/test_serve_chunked.py pin both. This single-segment case, by
    contrast, IS :func:`flash_prefill_packed_ref` with constant-zero
    segment ids (segment equality is then vacuously true), so it delegates
    rather than keeping a third hand-synced copy of the scan.
    """
    sq, skv = q.shape[2], k.shape[2]
    if kv_pos is None:
        kv_pos = jnp.arange(skv, dtype=jnp.int32)
    return flash_prefill_packed_ref(
        q, k, v, q_pos=q_pos, q_seg=jnp.zeros((sq,), jnp.int32),
        kv_pos=kv_pos, kv_seg=jnp.zeros((skv,), jnp.int32),
        window=window, softcap=softcap, scale=scale, bkv=bkv)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "bkv"),
)
def flash_prefill_packed_ref(
    q, k, v, *, q_pos, q_seg, kv_pos, kv_seg,
    window: Optional[int] = None, softcap: Optional[float] = None,
    scale: Optional[float] = None, bkv: int = 512,
):
    """Segment-packed online-softmax attention: N requests, one launch.

    q ``[B, Hq, Sq, D]`` concatenates the chunks of N independent requests
    along the sequence axis; ``q_pos`` [Sq] carries each token's absolute
    position *within its own request* and ``q_seg`` [Sq] tags which request
    (segment) it belongs to. k/v ``[B, Hkv, Skv, D]`` concatenate each
    segment's visible keys (its cache history ++ its own chunk keys), with
    ``kv_pos`` / ``kv_seg`` the matching per-key position and segment maps
    (``kv_pos == -1`` = never-written ring slot). A key is visible iff it
    belongs to the SAME segment (``kv_seg == q_seg``) and the causal
    continuation rule holds (``0 <= kv_pos <= q_pos``, plus the window
    bound when given) — so request i's queries never attend request j's
    keys, and within a segment the math is exactly
    :func:`flash_prefill_chunk_ref`.

    The scan streams KV in ``bkv`` splits like the single-segment reference
    (a non-dividing ``bkv`` snaps to the largest divisor of ``Skv``); the
    resident block is the whole packed query set — the ``pack`` axis of the
    ``packed_prefill`` plan cell, which VMEM capacity bounds per hardware
    model (wider packs on bigger-VMEM models; see ``ops.py``).
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    n_rep = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bkv = fit_bkv(bkv, skv)
    n_kv = skv // bkv

    qg = q.reshape(b, hkv, n_rep, sq, d).astype(jnp.float32) * scale
    qp = jnp.asarray(q_pos, jnp.int32)
    qs = jnp.asarray(q_seg, jnp.int32)
    kc = k.reshape(b, hkv, n_kv, bkv, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_kv, bkv, d).transpose(2, 0, 1, 3, 4)
    pc = jnp.asarray(kv_pos, jnp.int32).reshape(n_kv, bkv)
    sc = jnp.asarray(kv_seg, jnp.int32).reshape(n_kv, bkv)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, kp, ks = xs
        s_blk = jnp.einsum(
            "bgrqd,bgkd->bgrqk", qg, k_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )                                              # [B, Hkv, rep, Sq, bkv]
        if softcap is not None:
            s_blk = softcap * jnp.tanh(s_blk / softcap)
        valid = jnp.logical_and(kp[None, :] >= 0, kp[None, :] <= qp[:, None])
        valid = jnp.logical_and(valid, ks[None, :] == qs[:, None])
        if window is not None:
            valid = jnp.logical_and(valid, kp[None, :] > qp[:, None] - window)
        s_blk = jnp.where(valid[None, None, None], s_blk, NEG_INF)
        m_cur = jnp.max(s_blk, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bgrqk,bgkd->bgrqd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, n_rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, n_rep, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, n_rep, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, pc, sc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, d).astype(q.dtype)
