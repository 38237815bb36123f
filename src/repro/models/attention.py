"""Attention block: GQA with RoPE, optional SWA window / softcap / QKV bias /
q-k norms, head padding for TP, and KV caches (full and ring-buffer).

The full-sequence path lowers through the chunked flash reference (same math
as the Pallas kernel; see kernels/flash_attention). Decode dispatches on the
plan-resolved decode tile: with a tile it lowers through the split-KV
flash-decode kernel (Pallas on TPU, the chunked online-softmax reference
elsewhere — the tile's ``bkv`` is the KV split on both); without one it
attends densely over the cache (the pre-plan behavior). On real TPU
deployments the prefill path swaps in the Pallas kernel via
``impl="pallas"``.

Tile-dispatch observability: every call that received a plan tile emits a
trace-time event through :func:`capture_tile_events` saying whether the tile
legally applied or the lowering silently degraded (clamped to a
non-dividing block -> reference fallback / adjusted chunk). The serve
engine records these as ``tile_fallback`` plan-counter entries so
``plan_hit_rate`` reflects decode/prefill tile misses, not just plan-store
lookups.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.tiling import cdiv
from repro.models import flags
from repro.kernels.flash_attention.chunked import (
    flash_prefill_chunk_paged_ref, flash_prefill_chunk_ref,
    flash_prefill_packed_ref, paged_prefix,
)
from repro.kernels.flash_attention.decode import (
    fit_bkv, flash_decode, flash_decode_ref, paged_gather, paged_write,
    split_legal,
)
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models.layers import ParamDef, apply_rope, rms_norm

NEG_INF = -2.0e30

# ---------------------------------------------------------------------------
# Tile-dispatch events. Emitted at TRACE time (tile legality is a static
# shape decision), so a sink sees one event per compiled program per
# attention call site — cheap, and exactly when a plan tile goes unused.
# ---------------------------------------------------------------------------

_tile_event_sinks: Tuple[Callable[[Dict[str, Any]], None], ...] = ()


@contextlib.contextmanager
def capture_tile_events(sink: Callable[[Dict[str, Any]], None]):
    """Route tile-dispatch events emitted under this context to ``sink``.

    Events are dicts: ``kernel`` (flash_attention | flash_decode |
    chunked_prefill | packed_prefill | matmul), ``phase`` (prefill |
    decode), ``impl`` (the lowering actually used), ``tile`` (the requested
    dims), ``effective`` (the parameter the lowering really used) and
    ``fallback`` (True when the plan's tile did not legally apply).
    Captures nest: every enclosing sink sees each event, so a caller can
    watch the programs an engine traces under its own capture.
    """
    global _tile_event_sinks
    prev = _tile_event_sinks
    _tile_event_sinks = prev + (sink,)
    try:
        yield
    finally:
        _tile_event_sinks = prev


def emit_tile_event(**event) -> None:
    for sink in _tile_event_sinks:
        sink(dict(event))


def attn_defs(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, hd = cfg.d_model, cfg.head_dim_
    h, hkv = cfg.padded_heads, cfg.padded_kv_heads
    defs = {
        "wq": ParamDef((d, h, hd), ("d_model", "heads", None)),
        "wk": ParamDef((d, hkv, hd), ("d_model", "kv_heads", None)),
        "wv": ParamDef((d, hkv, hd), ("d_model", "kv_heads", None)),
        "wo": ParamDef((h, hd, d), ("heads", None, "d_model"), scale=1.0),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((hkv, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((hkv, hd), ("kv_heads", None), init="zeros")
    if cfg.use_qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="zeros")
        defs["k_norm"] = ParamDef((hd,), (None,), init="zeros")
    return defs


def make_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                  ring: bool = False) -> Dict[str, Any]:
    hkv, hd = cfg.padded_kv_heads, cfg.head_dim_
    cache = {
        "k": jnp.zeros((batch, hkv, max_len, hd), dtype),
        "v": jnp.zeros((batch, hkv, max_len, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if ring:
        cache["slot_pos"] = jnp.full((max_len,), -1, jnp.int32)
    return cache


def make_paged_kv_pages(cfg: ArchConfig, n_pages: int, page: int,
                        dtype) -> Dict[str, Any]:
    """One attention layer's slice of the shared paged KV pool: physical
    page arrays ``[n_pages, Hkv, page, hd]``. Requests index into them
    through their page tables (serve/pool.py); the per-request serve state
    keeps only the scalar write position (see ``transformer.make_caches``
    with ``paged=True``)."""
    hkv, hd = cfg.padded_kv_heads, cfg.head_dim_
    return {
        "k_pages": jnp.zeros((n_pages, hkv, page, hd), dtype),
        "v_pages": jnp.zeros((n_pages, hkv, page, hd), dtype),
    }


def _ring_write(cache, k, v, positions_1d, end_pos):
    """Write a chunk's K/V tail into a ring cache: the last
    ``min(chunk, W)`` positions land at ``pos % W`` with their absolute
    positions recorded in ``slot_pos``. ONE implementation shared by the
    full-sequence, chunked, and packed prefill paths — ring wraparound
    drift between them would break the chunk/pack parity suites."""
    max_len = cache["k"].shape[2]
    keep = min(k.shape[2], max_len)
    kk = k[:, :, -keep:]
    vv = v[:, :, -keep:]
    pos_tail = positions_1d[-keep:]
    slots = pos_tail % max_len
    ck = cache["k"].at[:, :, slots].set(kk.astype(cache["k"].dtype))
    cv = cache["v"].at[:, :, slots].set(vv.astype(cache["v"].dtype))
    sp = cache["slot_pos"].at[slots].set(pos_tail)
    return {"k": ck, "v": cv, "pos": jnp.asarray(end_pos, jnp.int32),
            "slot_pos": sp}


def _linear_write(cache, k, v, start, end_pos):
    """Write a chunk's K/V into a linear cache at its static offset
    (shared by the same three prefill paths as :func:`_ring_write`)."""
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, start, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, start, 0))
    return {"k": ck, "v": cv, "pos": jnp.asarray(end_pos, jnp.int32)}


def _project_qkv(p, cfg: ArchConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    # [B, H, S, hd]
    return (t.transpose(0, 2, 1, 3) for t in (q, k, v))


def _out_proj(p, cfg: ArchConfig, attn_out, x_dtype):
    # Mask padded heads so they are numerically inert (grads included).
    h = cfg.padded_heads
    if h != cfg.n_heads:
        mask = (jnp.arange(h) < cfg.n_heads).astype(attn_out.dtype)
        attn_out = attn_out * mask[None, :, None, None]
    return jnp.einsum(
        "bhsk,hkd->bsd", attn_out, p["wo"].astype(x_dtype)
    )


def attn_forward(
    p, cfg: ArchConfig, x, positions, *,
    window: Optional[int] = None,
    cache: Optional[Dict[str, Any]] = None,
    impl: str = "auto",
    tile=None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]]]:
    """Full-sequence attention (train/prefill). Fills ``cache`` if given.

    ``tile`` is the plan-resolved (bq, bkv) flash-attention block shape
    (``TileShape`` or 2-tuple). On the Pallas path it is the kernel's block
    spec; on the reference path ``bkv`` sets the online-softmax KV chunk, so
    a resolved plan changes the lowered computation on every backend.
    ``impl``: "auto" picks the Pallas kernel on TPU backends when a resolved
    tile legally divides the sequence, and the chunked reference otherwise
    (Pallas TPU kernels cannot lower to host HLO; without a plan the
    lowering is unchanged).
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    scale = cfg.query_scale or cfg.head_dim_ ** -0.5
    kwargs = dict(
        causal=True, window=window,
        softcap=cfg.attn_softcap or None, scale=scale,
    )
    t = (min(tile[0], s), min(tile[1], s)) if tile is not None else None
    divides = t is not None and s % t[0] == 0 and s % t[1] == 0
    if impl == "auto":
        impl = "pallas" if (flags.pallas_enabled() and divides) \
            else "reference"
    if impl == "pallas":
        out = flash_attention(q, k, v, tile=t or (512, 512),
                              interpret=flags.pallas_interpret(), **kwargs)
        if tile is not None:
            emit_tile_event(kernel="flash_attention", phase="prefill",
                             impl="pallas", tile=tuple(tile),
                             effective=t, fallback=False)
    else:
        if tile is not None:
            chunk = min(int(tile[1]), s)
            # The clamp can land on a non-dividing chunk; the reference
            # then snaps to the largest divisor, silently abandoning the
            # plan's bkv. Count it (and the Pallas-eligible-but-illegal
            # case) instead of hiding it.
            effective = fit_bkv(chunk, s)
            fallback = (effective != chunk
                        or (flags.pallas_enabled() and not divides))
            emit_tile_event(kernel="flash_attention", phase="prefill",
                             impl="reference", tile=tuple(tile),
                             effective=effective, fallback=fallback)
        else:
            chunk = 2048 if flags.ANALYSIS_UNROLL else 512
        out = flash_attention_ref(q, k, v, chunk=min(chunk, s), **kwargs)
    y = _out_proj(p, cfg, out, x.dtype)
    new_cache = None
    if cache is not None:
        if "slot_pos" in cache:
            # Ring prefill: keep the last ``max_len`` positions.
            new_cache = _ring_write(cache, k, v, positions[0], s)
        else:
            new_cache = _linear_write(cache, k, v, 0, s)
    return y, new_cache


def attn_prefill_chunk(
    p, cfg: ArchConfig, x, positions, *,
    cache: Dict[str, Any],
    start: int,
    window: Optional[int] = None,
    impl: str = "auto",
    tile=None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Continuation prefill of one prompt chunk over the live KV cache.

    ``x`` [B, c, D] holds the chunk's tokens at absolute positions
    ``start .. start+c-1`` (``positions`` carries them; ``start`` must be a
    static int — each (chunk length, start) pair is its own compiled
    program, which keeps the causal ``q_offset`` arithmetic and the cache
    prefix slice static). The chunk attends causally over the KV written by
    chunks ``0..N-1`` plus itself — the whole-prompt ``attn_forward``
    computation restricted to these query rows — and writes its K/V into
    the cache at the continuation offset.

    ``tile`` is the plan-resolved ``chunked_prefill`` tile ``(chunk, bkv)``.
    On TPU backends with a linear cache the Pallas ``flash_attention``
    kernel runs with the existing ``q_offset`` continuation math when the
    clamped tile legally divides ``(c, start+c)``; otherwise the chunked
    online-softmax reference runs with ``bkv`` as its KV split. Ring-buffer
    caches (sliding-window layers) always lower through
    :func:`~repro.kernels.flash_attention.chunked.flash_prefill_chunk_ref`,
    whose traced ``kv_pos`` map expresses slot wraparound that a static
    ``q_offset`` cannot.
    """
    b, c, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    scale = cfg.query_scale or cfg.head_dim_ ** -0.5
    softcap = cfg.attn_softcap or None

    if "k_pages" in cache:
        # Pool-backed cache: the chunk writes its K/V through the table at
        # the static start offset, then attends over the ``cdiv(start,
        # page)`` prefix pages its table maps (gathered to a positioned
        # linear view; positions >= start masked — unwritten page tails,
        # the rows just written and a shared-prefix donor's divergent
        # tokens alike) plus itself. Writing before reading, as decode
        # does, lets XLA update the pool in place: a read of the old pages
        # followed by a write makes it copy them. The engine resolves
        # copy-on-write BEFORE this runs (pool.prepare_span) so the written
        # span's pages are exclusively owned. A scanned stack hands the
        # layer its stacked pages plus ``layer``.
        layer = cache.get("layer")
        kp = paged_write(cache["k_pages"], cache["table"], k, start, layer)
        vp = paged_write(cache["v_pages"], cache["table"], v, start, layer)
        page = kp.shape[-2]
        n_pp = cdiv(start, page)
        skv = n_pp * page + c
        if tile is not None:
            requested = min(int(tile[-1]), skv)
            effective = fit_bkv(requested, skv)
            emit_tile_event(kernel="chunked_prefill", phase="prefill",
                             impl="reference", tile=tuple(tile),
                             effective=effective,
                             fallback=effective != requested)
            bkv = requested
        else:
            bkv = 512
        out = flash_prefill_chunk_paged_ref(
            q, k, v, kp, vp, cache["table"],
            q_pos=positions[0], start=start, n_prefix_pages=n_pp,
            window=window, softcap=softcap, scale=scale, bkv=bkv,
            layer=layer)
        y = _out_proj(p, cfg, out, x.dtype)
        return y, {"k_pages": kp, "v_pages": vp, "table": cache["table"],
                   "pos": jnp.asarray(start + c, jnp.int32)}

    if "slot_pos" in cache:
        # Ring cache: visible keys = the ring's survivors (window-bounded
        # history) ++ the chunk itself, each with its absolute position.
        max_len = cache["k"].shape[2]
        k_all = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=2)
        v_all = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=2)
        kv_pos = jnp.concatenate(
            [cache["slot_pos"], positions[0].astype(jnp.int32)])
        skv = max_len + c
        if tile is not None:
            requested = min(int(tile[-1]), skv)
            effective = fit_bkv(requested, skv)
            emit_tile_event(kernel="chunked_prefill", phase="prefill",
                             impl="reference", tile=tuple(tile),
                             effective=effective,
                             fallback=effective != requested)
            bkv = requested
        else:
            bkv = 512
        out = flash_prefill_chunk_ref(
            q, k_all, v_all, q_pos=positions[0], kv_pos=kv_pos,
            window=window, softcap=softcap, scale=scale, bkv=bkv)
        # Write the chunk's tail into the ring (mirrors attn_forward).
        new_cache = _ring_write(cache, k, v, positions[0], start + c)
    else:
        # Linear cache: the written prefix is exactly positions 0..start-1,
        # so the existing q_offset continuation math applies directly.
        skv = start + c
        if start:
            k_all = jnp.concatenate(
                [cache["k"][:, :, :start].astype(k.dtype), k], axis=2)
            v_all = jnp.concatenate(
                [cache["v"][:, :, :start].astype(v.dtype), v], axis=2)
        else:
            k_all, v_all = k, v
        t = (min(int(tile[0]), c), min(int(tile[1]), skv)) \
            if tile is not None else None
        divides = t is not None and c % t[0] == 0 and skv % t[1] == 0
        if impl == "auto":
            impl = "pallas" if (flags.pallas_enabled() and divides) \
                else "reference"
        kwargs = dict(causal=True, window=window, softcap=softcap,
                      scale=scale, q_offset=start)
        if impl == "pallas":
            out = flash_attention(q, k_all, v_all, tile=t or (512, 512),
                                  interpret=flags.pallas_interpret(),
                                  **kwargs)
            if tile is not None:
                emit_tile_event(kernel="chunked_prefill", phase="prefill",
                                 impl="pallas", tile=tuple(tile),
                                 effective=t, fallback=False)
        else:
            if tile is not None:
                requested = min(int(tile[1]), skv)
                effective = fit_bkv(requested, skv)
                emit_tile_event(
                    kernel="chunked_prefill", phase="prefill",
                    impl="reference", tile=tuple(tile), effective=effective,
                    fallback=(effective != requested
                              or (flags.pallas_enabled() and not divides)))
                chunk_kv = requested
            else:
                chunk_kv = 512
            out = flash_attention_ref(q, k_all, v_all,
                                      chunk=min(chunk_kv, skv), **kwargs)
        new_cache = _linear_write(cache, k, v, start, start + c)
    y = _out_proj(p, cfg, out, x.dtype)
    return y, new_cache


def attn_prefill_packed(
    p, cfg: ArchConfig, x, positions, *,
    caches,
    layout,
    window: Optional[int] = None,
    tile=None,
):
    """Packed continuation prefill: N requests' chunks, one attention call.

    ``x`` [1, S_packed, D] segment-concatenates the chunks of N independent
    requests; ``layout`` is the static tuple of per-segment ``(start, len)``
    pairs (sum of lens = S_packed) and ``positions`` [1, S_packed] carries
    each token's absolute position within its own request. ``caches`` is
    the matching tuple of per-request layer caches (each batch=1). Every
    segment attends causally over ITS OWN cache prefix plus its own chunk —
    never another segment's keys: the packed lowering concatenates each
    segment's visible KV with per-key segment tags and masks on segment
    equality (:func:`flash_prefill_packed_ref`), so the math per request is
    exactly :func:`attn_prefill_chunk` while the projections, the softmax
    scan, and the surrounding FF GEMMs run once over the whole pack — the
    occupancy win step packing exists for.

    ``tile`` is the plan-resolved ``packed_prefill`` tile ``(pack, bkv)``;
    ``bkv`` sets the packed KV stream split (the pack width itself is the
    scheduler's knob — by the time this runs, the pack is already built).
    Linear caches write each segment at its static start offset; ring
    caches take the chunked ring-write path per segment. Returns
    ``(y [1, S_packed, D], tuple of per-request new caches)``.
    """
    b, s_packed, _ = x.shape
    assert b == 1, "packed prefill packs segments, not batch rows"
    assert len(caches) == len(layout) and layout, (len(caches), len(layout))
    q, k, v = _project_qkv(p, cfg, x, positions)
    scale = cfg.query_scale or cfg.head_dim_ ** -0.5
    softcap = cfg.attn_softcap or None
    paged = "k_pages" in caches[0]
    ring = not paged and "slot_pos" in caches[0]
    if paged:
        # Pool-backed pack: by convention segment 0's cache carries the
        # SHARED page arrays (transformer.forward_packed merges them there);
        # every segment carries its own page table. Every segment writes
        # its rows first, then the prefix reads run (the chunked path's
        # order, which keeps the pool update in place): a segment's prefix
        # masks positions >= its start, and the engine's copy-on-write pass
        # makes written spans exclusive, so no read sees another segment's
        # write. A scanned stack passes its stacked pages with segment 0's
        # ``layer``.
        k_pool, v_pool = caches[0]["k_pages"], caches[0]["v_pages"]
        layer = caches[0].get("layer")
        page = k_pool.shape[-2]

    offs = [0]
    for _, ln in layout:
        offs.append(offs[-1] + ln)
    assert offs[-1] == s_packed, (offs, s_packed)
    if paged:
        for i, ((start, ln), cache) in enumerate(zip(layout, caches)):
            k_pool = paged_write(k_pool, cache["table"],
                                 k[:, :, offs[i]:offs[i] + ln], start, layer)
            v_pool = paged_write(v_pool, cache["table"],
                                 v[:, :, offs[i]:offs[i] + ln], start, layer)

    k_parts, v_parts, kvp_parts, kvs_parts = [], [], [], []
    for i, ((start, ln), cache) in enumerate(zip(layout, caches)):
        k_seg = k[:, :, offs[i]:offs[i] + ln]
        v_seg = v[:, :, offs[i]:offs[i] + ln]
        seg_pos = positions[0, offs[i]:offs[i] + ln].astype(jnp.int32)
        if paged:
            # Paged prefix: the segment's mapped pages up to its start
            # (static count), position-masked like the ring's slot_pos map.
            n_pp = cdiv(start, page)
            if n_pp:
                kp_, vp_, pp_ = paged_prefix(
                    k_pool, v_pool, cache["table"], n_pp, start, layer)
                k_parts += [kp_.astype(k.dtype), k_seg]
                v_parts += [vp_.astype(v.dtype), v_seg]
                kvp_parts += [pp_, seg_pos]
            else:
                k_parts += [k_seg]
                v_parts += [v_seg]
                kvp_parts += [seg_pos]
            prefix_len = n_pp * page
        elif ring:
            # Ring prefix: the whole window buffer, slot_pos mapping each
            # slot to its absolute position (-1 = never written).
            k_parts += [cache["k"].astype(k.dtype), k_seg]
            v_parts += [cache["v"].astype(v.dtype), v_seg]
            kvp_parts += [cache["slot_pos"], seg_pos]
            prefix_len = cache["k"].shape[2]
        else:
            # Linear prefix: exactly the positions 0..start-1 written by the
            # segment's earlier chunks (static slice — layout is static).
            k_parts += [cache["k"][:, :, :start].astype(k.dtype), k_seg]
            v_parts += [cache["v"][:, :, :start].astype(v.dtype), v_seg]
            kvp_parts += [jnp.arange(start, dtype=jnp.int32), seg_pos]
            prefix_len = start
        kvs_parts.append(jnp.full((prefix_len + ln,), i, jnp.int32))
    k_all = jnp.concatenate(k_parts, axis=2)
    v_all = jnp.concatenate(v_parts, axis=2)
    kv_pos = jnp.concatenate(kvp_parts)
    kv_seg = jnp.concatenate(kvs_parts)
    q_seg = jnp.concatenate([
        jnp.full((ln,), i, jnp.int32) for i, (_, ln) in enumerate(layout)
    ])

    skv = k_all.shape[2]
    if tile is not None:
        requested = min(int(tile[-1]), skv)
        effective = fit_bkv(requested, skv)
        emit_tile_event(kernel="packed_prefill", phase="prefill",
                         impl="reference", tile=tuple(tile),
                         effective=effective,
                         fallback=effective != requested)
        bkv = requested
    else:
        bkv = 512
    out = flash_prefill_packed_ref(
        q, k_all, v_all, q_pos=positions[0], q_seg=q_seg,
        kv_pos=kv_pos, kv_seg=kv_seg, window=window, softcap=softcap,
        scale=scale, bkv=bkv)

    new_caches = []
    for i, ((start, ln), cache) in enumerate(zip(layout, caches)):
        k_seg = k[:, :, offs[i]:offs[i] + ln]
        v_seg = v[:, :, offs[i]:offs[i] + ln]
        seg_pos = positions[0, offs[i]:offs[i] + ln]
        if paged:
            new_caches.append({"table": cache["table"],
                               "pos": jnp.asarray(start + ln, jnp.int32)})
        elif ring:
            new_caches.append(
                _ring_write(cache, k_seg, v_seg, seg_pos, start + ln))
        else:
            new_caches.append(
                _linear_write(cache, k_seg, v_seg, start, start + ln))
    if paged:
        # Segment 0 returns the (single) updated pool alongside its state.
        new_caches[0] = {**new_caches[0], "k_pages": k_pool,
                        "v_pages": v_pool}
    y = _out_proj(p, cfg, out, x.dtype)
    return y, tuple(new_caches)


def _decode_attn_sharded(cfg: ArchConfig, ctx, qd, k_new, v_new, cache,
                         window: Optional[int], scale: float):
    """Flash-decoding: LSE-combined attention over the seq-sharded KV cache.

    Each model shard attends over its local sequence chunk with the GQA
    grouped contraction (no kv repeat!), then partial softmax statistics
    combine with pmax/psum of [B, H]-sized tensors — collective bytes drop
    from cache-sized copies to KBs. The single-position cache update runs
    inside the shard_map on the owner shard only.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    ax = ctx.model_axis
    n_shards = mesh.shape[ax]
    b, hq, hd = qd.shape
    hkv, s_total = cache["k"].shape[1], cache["k"].shape[2]
    s_loc = s_total // n_shards
    n_rep = hq // hkv
    pos = cache["pos"]

    def batch_entry(n):
        use, rem = [], n
        for a in ctx.batch_axes:
            if rem % mesh.shape[a] == 0 and rem >= mesh.shape[a]:
                use.append(a)
                rem //= mesh.shape[a]
        return tuple(use) if len(use) > 1 else (use[0] if use else None)

    bent = batch_entry(b)
    q_spec = P(bent, None, None)
    new_spec = P(bent, None, None, None)
    cache_spec = P(bent, None, ax, None)

    def body(q_loc, kn, vn, k_loc, v_loc, pos_):
        i = jax.lax.axis_index(ax)
        # Owner shard writes the new K/V at the local offset.
        owner = pos_ // s_loc
        local = pos_ % s_loc
        k_upd = jax.lax.dynamic_update_slice(
            k_loc, kn.astype(k_loc.dtype), (0, 0, local, 0))
        v_upd = jax.lax.dynamic_update_slice(
            v_loc, vn.astype(v_loc.dtype), (0, 0, local, 0))
        k_loc = jnp.where(i == owner, k_upd, k_loc)
        v_loc = jnp.where(i == owner, v_upd, v_loc)

        k_pos = i * s_loc + jnp.arange(s_loc)
        valid = k_pos <= pos_
        if window is not None:
            valid &= k_pos > pos_ - window
        bl = q_loc.shape[0]
        qg = q_loc.reshape(bl, hkv, n_rep, hd).astype(k_loc.dtype)
        s = jnp.einsum(
            "bgrk,bgsk->bgrs", qg, k_loc,
            preferred_element_type=jnp.float32,
        ) * scale
        if cfg.attn_softcap:
            s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m, ax)                        # [B,Hkv,rep]
        prob = jnp.exp(s - m_g[..., None])
        l_g = jax.lax.psum(jnp.sum(prob, axis=-1), ax)
        pv = jnp.einsum(
            "bgrs,bgsk->bgrk", prob.astype(v_loc.dtype), v_loc,
            preferred_element_type=jnp.float32,
        )
        pv_g = jax.lax.psum(pv, ax)
        out = pv_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(bl, hq, hd), k_loc, v_loc

    out, ck, cv = shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, new_spec, new_spec, cache_spec, cache_spec, P()),
        out_specs=(q_spec, cache_spec, cache_spec),
        check_vma=False,
    )(qd, k_new, v_new, cache["k"], cache["v"], pos)
    out = out.astype(qd.dtype)
    return out[:, :, None], {"k": ck, "v": cv, "pos": pos + 1}


def attn_decode(
    p, cfg: ArchConfig, x, *, cache: Dict[str, Any],
    window: Optional[int] = None, ctx=None,
    tile=None, impl: str = "auto",
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Single-token decode: x [B, 1, D]; attend over the cache.

    ``tile`` is the plan-resolved decode tile (``TileShape`` or tuple whose
    last dim is ``bkv``, the split-KV chunk). ``impl``: "auto" picks the
    Pallas flash-decode kernel on TPU backends when the kernel can run the
    split over the cache (``decode.split_legal``), the chunked flash-decode reference when a tile is
    present elsewhere (``bkv`` sets the online-softmax KV split — a resolved
    plan changes the lowered computation on every backend), and the dense
    masked attend when no tile resolved (the pre-plan lowering). "dense" /
    "flash_ref" / "pallas" force a path. The sequence-sharded flash-decoding
    path (``flags.DECODE_ATTN_SHARDED``) keeps its own tiling — the split is
    the mesh axis — and ignores ``tile``.
    """
    b = x.shape[0]
    pos = cache["pos"]                                   # scalar int32
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)  # [B, H(kv), 1, hd]
    scale = cfg.query_scale or cfg.head_dim_ ** -0.5

    paged = "k_pages" in cache
    max_len = (cache["table"].shape[0] * cache["k_pages"].shape[-2]
               if paged else cache["k"].shape[2])
    if (flags.DECODE_ATTN_SHARDED and ctx is not None and ctx.mesh is not None
            and not paged and "slot_pos" not in cache
            and cfg.padded_kv_heads < ctx.mesh.shape[ctx.model_axis]
            and max_len % ctx.mesh.shape[ctx.model_axis] == 0):
        out, new_cache = _decode_attn_sharded(
            cfg, ctx, q[:, :, 0], k_new, v_new, cache, window, scale)
        y = _out_proj(p, cfg, out, x.dtype)
        return y, new_cache
    if paged:
        # Pool-backed cache (batch 1): scatter the new K/V into the page
        # the table maps position ``pos`` to, then attend over the table's
        # gathered linear view — the dispatch below (dense / flash_ref /
        # pallas) is the same as for a resident linear cache, so the paged
        # lowering changes where bytes live, not the math. Unwritten tail
        # slots of the view hold stale pages' data; ``k_pos <= pos`` masks
        # them exactly as it masks a linear cache's unwritten tail. A
        # scanned stack hands the layer its stacked pages plus ``layer``.
        layer = cache.get("layer")
        kp = paged_write(cache["k_pages"], cache["table"], k_new, pos, layer)
        vp = paged_write(cache["v_pages"], cache["table"], v_new, pos, layer)
        ck = paged_gather(kp, cache["table"], layer)
        cv = paged_gather(vp, cache["table"], layer)
        slot_pos = None
        k_pos = jnp.arange(max_len)
        valid = k_pos <= pos
    elif "slot_pos" in cache:
        slot = pos % max_len
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, slot, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, slot, 0))
        slot_pos = cache["slot_pos"].at[slot].set(pos)
        k_pos = slot_pos                                  # [W] absolute
        valid = k_pos >= 0
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, pos, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, pos, 0))
        slot_pos = None
        k_pos = jnp.arange(max_len)
        valid = k_pos <= pos

    bkv = int(tile[-1]) if tile is not None else None
    clamped = min(bkv, max_len) if bkv is not None else None
    legal = clamped is not None and split_legal(clamped, max_len)
    auto = impl == "auto"
    if auto:
        if bkv is None:
            impl = "dense"
        elif flags.pallas_enabled() and legal:
            impl = "pallas"
        else:
            impl = "flash_ref"
    if tile is not None:
        effective = fit_bkv(clamped, max_len)
        if impl == "pallas":
            fallback = False
        elif impl == "dense":
            fallback = True                 # forced dense ignores the tile
        else:                               # flash_ref: ran, but at the
            # snapped (not the plan's) split, or in place of a kernel the
            # split could not run.
            fallback = (effective != clamped
                        or (flags.pallas_enabled() and not legal))
        emit_tile_event(
            kernel="flash_decode", phase="decode", impl=impl,
            tile=tuple(tile), effective=effective, fallback=fallback,
        )

    softcap = cfg.attn_softcap or None
    if impl in ("pallas", "flash_ref"):
        fn = flash_decode if impl == "pallas" else flash_decode_ref
        extra = ({"interpret": flags.pallas_interpret()}
                 if impl == "pallas" else {})
        out = fn(
            q[:, :, 0], ck, cv, pos=pos, kv_pos=slot_pos, window=window,
            softcap=softcap, scale=scale, bkv=clamped or 512, **extra,
        )[:, :, None]                                      # [B, Hq, 1, hd]
        out = out.astype(x.dtype)
    else:
        mask = valid & (k_pos <= pos)
        if window is not None:
            mask &= k_pos > pos - window

        hq, hkv = cfg.padded_heads, cfg.padded_kv_heads
        n_rep = hq // hkv
        # GQA via kv repeat (gather) — partitions cleanly under head
        # sharding. Keep K/V in cache dtype: upcasting a 32k-seq cache to
        # f32 would materialize gigabytes per layer; the MXU accumulates in
        # f32 anyway (preferred_element_type).
        ke = jnp.repeat(ck, n_rep, axis=1) if n_rep > 1 else ck
        ve = jnp.repeat(cv, n_rep, axis=1) if n_rep > 1 else cv
        qd = q[:, :, 0].astype(ke.dtype)                  # [B, Hq, hd]
        s = jnp.einsum(
            "bhk,bhsk->bhs", qd, ke, preferred_element_type=jnp.float32,
        ) * scale                                         # [B, Hq, S] f32
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask[None, None], s, NEG_INF)
        pattn = jax.nn.softmax(s, axis=-1).astype(ve.dtype)
        out = jnp.einsum(
            "bhs,bhsk->bhk", pattn, ve, preferred_element_type=jnp.float32,
        )[:, :, None].astype(x.dtype)                      # [B, Hq, 1, hd]
    y = _out_proj(p, cfg, out, x.dtype)
    if paged:
        new_cache = {"k_pages": kp, "v_pages": vp, "table": cache["table"],
                     "pos": pos + 1}
    else:
        new_cache = {"k": ck, "v": cv, "pos": pos + 1}
        if slot_pos is not None:
            new_cache["slot_pos"] = slot_pos
    return y, new_cache
