"""Generic decoder stack builder — one builder for all ten architectures.

An ArchConfig's layer pattern is grouped into runs of identical LayerSpecs;
each run's parameters are stacked on a leading layer axis and the run is
executed with ``jax.lax.scan`` (+ remat), so a 94-layer model compiles as one
scanned superblock. Hybrid patterns (recurrentgemma's rglru/rglru/attn,
gemma2's local/global alternation) scan their repeat unit.

Decode/prefill use the same grouped structure with per-layer caches stacked
along the scan axis.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models import flags
from repro.core.tiling import block_fits, round_up
from repro.models.context import DistContext
from repro.models.layers import (
    ParamDef, act_fn, axes_tree, init_tree, layer_norm, rms_norm, softcap,
)


# ---------------------------------------------------------------------------
# Param definitions
# ---------------------------------------------------------------------------

def dense_ff_defs(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": ParamDef((d, f), ("d_model", "ff")),
        "w3": ParamDef((d, f), ("d_model", "ff")),
        "w2": ParamDef((f, d), ("ff", "d_model")),
    }


def _norm_defs(cfg: ArchConfig, name: str) -> Dict[str, ParamDef]:
    if cfg.norm_kind == "layernorm":
        return {
            f"{name}_w": ParamDef((cfg.d_model,), (None,), init="ones"),
            f"{name}_b": ParamDef((cfg.d_model,), (None,), init="zeros"),
        }
    return {f"{name}_w": ParamDef((cfg.d_model,), (None,), init="zeros")}


def _apply_norm(p, cfg: ArchConfig, x, name: str):
    if cfg.norm_kind == "layernorm":
        return layer_norm(x, p[f"{name}_w"], p[f"{name}_b"], cfg.norm_eps)
    return rms_norm(x, p[f"{name}_w"], cfg.norm_eps)


def layer_defs(cfg: ArchConfig, spec: LayerSpec) -> Dict[str, Any]:
    defs: Dict[str, Any] = {}
    defs.update(_norm_defs(cfg, "norm1"))
    if spec.mixer in ("attn", "local_attn"):
        defs["attn"] = attn_mod.attn_defs(cfg)
    elif spec.mixer == "rglru":
        defs["rglru"] = rglru_mod.rglru_defs(cfg)
    elif spec.mixer == "ssd":
        defs["ssm"] = ssm_mod.ssm_defs(cfg)
    else:
        raise ValueError(f"unknown mixer {spec.mixer}")
    if cfg.post_norms:
        defs.update(_norm_defs(cfg, "post1"))
    if spec.ff is not None:
        if not cfg.parallel_block:
            defs.update(_norm_defs(cfg, "norm2"))
        if spec.ff == "dense":
            defs["ff"] = dense_ff_defs(cfg)
        elif spec.ff == "moe":
            defs["moe"] = moe_mod.moe_defs(cfg)
        else:
            raise ValueError(f"unknown ff {spec.ff}")
        if cfg.post_norms:
            defs.update(_norm_defs(cfg, "post2"))
    return defs


def decompose(cfg: ArchConfig) -> List[Tuple]:
    """Split the layer pattern into scan-able segments.

    Returns a list of ("seq", (specs...)) and ("scan", unit_specs, reps)
    segments. A periodic pattern (gemma2's local/global alternation,
    recurrentgemma's rglru/rglru/attn unit) scans its repeat UNIT — one
    heterogeneous body over ``reps`` iterations — so alternating-layer
    models compile as one scanned superblock instead of unrolling (which
    costs compile time AND saved-residual memory: ~1.6 GiB/layer measured
    on gemma2 before this decomposition existed).
    """
    pattern = cfg.layers()
    n = len(pattern)
    best = None  # (scanned_layers, -unit_len, start, p, reps)
    for start in range(0, min(4, n)):
        for p in range(1, 9):
            if start + 2 * p > n:
                break
            reps = (n - start) // p
            if reps < 2:
                continue
            if all(pattern[start + i] == pattern[start + (i % p)]
                   for i in range(reps * p)):
                cand = (reps * p, -p, start, p, reps)
                if best is None or cand > best:
                    best = cand
    if best is None:
        return [("seq", tuple(pattern))] if pattern else []
    _, _, start, p, reps = best
    segments: List[Tuple] = []
    if start:
        segments.append(("seq", tuple(pattern[:start])))
    segments.append(("scan", tuple(pattern[start:start + p]), reps))
    rest = pattern[start + reps * p:]
    if rest:
        segments.append(("seq", tuple(rest)))
    return segments


def group_layers(cfg: ArchConfig) -> List[Tuple[LayerSpec, int]]:
    """Consecutive-run view (kept for tests/back-compat)."""
    groups: List[Tuple[LayerSpec, int]] = []
    for spec in cfg.layers():
        if groups and groups[-1][0] == spec:
            groups[-1] = (spec, groups[-1][1] + 1)
        else:
            groups.append((spec, 1))
    return groups


def model_defs(cfg: ArchConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.padded_vocab
    defs: Dict[str, Any] = {
        "embed": ParamDef((v, d), ("vocab", "d_model"), init="normal", scale=0.02),
    }
    defs.update(_norm_defs(cfg, "final_norm"))
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("d_model", "vocab"), init="normal",
                                   scale=0.02)
    segs = []
    for seg in decompose(cfg):
        if seg[0] == "seq":
            segs.append([layer_defs(cfg, spec) for spec in seg[1]])
        else:
            _, unit, reps = seg
            segs.append([_stack_defs(layer_defs(cfg, spec), reps)
                         for spec in unit])
    defs["segments"] = segs
    if cfg.encoder is not None and cfg.encoder.kind == "vision":
        defs["vit_proj"] = {
            "w": ParamDef((1024, d), (None, "d_model")),
            "b": ParamDef((d,), (None,), init="zeros"),
        }
    return defs


def _stack_defs(defs: Dict[str, Any], count: int) -> Dict[str, Any]:
    return jax.tree.map(
        lambda pd: ParamDef((count,) + pd.shape, (None,) + pd.axes,
                            init=pd.init, scale=pd.scale),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )


def init_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32):
    return init_tree(model_defs(cfg), key, dtype)


def param_logical_axes(cfg: ArchConfig):
    return axes_tree(model_defs(cfg))


# ---------------------------------------------------------------------------
# Layer forward
# ---------------------------------------------------------------------------

def _mixer(p, cfg: ArchConfig, spec: LayerSpec, x, positions, cache,
           decode: bool, ctx=None, tiles=None, chunk_start=None,
           pack_layout=None):
    tiles = tiles or {}
    if pack_layout is not None:
        return _mixer_packed(p, cfg, spec, x, positions, cache, tiles,
                             pack_layout)
    if spec.mixer in ("attn", "local_attn"):
        window = cfg.attn_window if spec.mixer == "local_attn" else None
        if decode:
            return attn_mod.attn_decode(p["attn"], cfg, x, cache=cache,
                                        window=window, ctx=ctx,
                                        tile=tiles.get("flash_decode"))
        if chunk_start is not None:
            return attn_mod.attn_prefill_chunk(
                p["attn"], cfg, x, positions, cache=cache,
                start=chunk_start, window=window,
                tile=tiles.get("chunked_prefill"))
        return attn_mod.attn_forward(p["attn"], cfg, x, positions,
                                     window=window, cache=cache,
                                     tile=tiles.get("flash_attention"))
    if spec.mixer == "rglru":
        return rglru_mod.rglru_forward(p["rglru"], cfg, x, state=cache)
    if spec.mixer == "ssd":
        ssd_tile = tiles.get("ssd")
        return ssm_mod.ssm_forward(p["ssm"], cfg, x, state=cache,
                                   chunk=ssd_tile[0] if ssd_tile else 0)
    raise ValueError(spec.mixer)


def _mixer_packed(p, cfg: ArchConfig, spec: LayerSpec, x, positions, caches,
                  tiles, layout):
    """One mixer over a packed (segment-concatenated) multi-request step.

    ``caches`` is a TUPLE of per-request layer caches/states (one per
    segment of the static ``layout``). Attention layers run the whole pack
    as ONE segment-masked launch (``attn_prefill_packed``); recurrent/SSD
    layers are sequence recurrences — a packed sequence would leak state
    across segment boundaries — so they run per segment on static slices,
    each continuing its own carried state (the surrounding norms/FF still
    run packed, which is where their win lives anyway).
    """
    if spec.mixer in ("attn", "local_attn"):
        window = cfg.attn_window if spec.mixer == "local_attn" else None
        return attn_mod.attn_prefill_packed(
            p["attn"], cfg, x, positions, caches=caches, layout=layout,
            window=window, tile=tiles.get("packed_prefill"))
    outs, news = [], []
    off = 0
    for (_, ln), cache in zip(layout, caches):
        seg = x[:, off:off + ln]
        if spec.mixer == "rglru":
            y, nc = rglru_mod.rglru_forward(p["rglru"], cfg, seg, state=cache)
        elif spec.mixer == "ssd":
            ssd_tile = tiles.get("ssd")
            y, nc = ssm_mod.ssm_forward(p["ssm"], cfg, seg, state=cache,
                                        chunk=ssd_tile[0] if ssd_tile else 0)
        else:
            raise ValueError(spec.mixer)
        outs.append(y)
        news.append(nc)
        off += ln
    return jnp.concatenate(outs, axis=1), tuple(news)


def _ff_blocks(tile, m: int, d: int, f: int):
    """Pallas blocks for the FF GEMM pair from the plan's matmul tile
    ``(bm, bk, bn)``: the up projections ``[m, d] @ [d, f]`` run
    ``(bm, bk, bn)`` and the down projection ``[m, f] @ [f, d]`` runs the
    transpose ``(bm, bn, bk)``. Returns ``(up, down, padded m)`` — rows are
    padded to a whole number of ``bm`` blocks — or None when ``bk``/``bn``
    cannot block the weights (they must divide them and be lane-aligned or
    whole, as the matmul cell's legality requires)."""
    bk, bn = min(tile[1], d), min(tile[2], f)
    if not (block_fits(bk, d, 128) and block_fits(bn, f, 128)):
        return None
    bm = min(round_up(min(tile[0], m), 8), m)
    return (bm, bk, bn), (bm, bn, bk), round_up(m, bm)


def _ff_runs_kernel(tile, x, p) -> bool:
    """Whether :func:`_dense_ff` runs ``x`` through the tiled Pallas matmul
    kernel with FF weights ``p`` (a layer's, or a stack of them)."""
    if tile is None or not flags.pallas_enabled():
        return False
    b, s, d = x.shape
    return _ff_blocks(tile, b * s, d, p["w1"].shape[-1]) is not None


def _dense_ff(p, cfg: ArchConfig, x, tile=None, phase: str = "prefill",
              layer=None):
    """SwiGLU FF. ``tile`` is the plan-resolved matmul tile (bm, bk, bn);
    on TPU backends (and under interpret-mode Pallas) the three GEMMs run
    through the tiled Pallas matmul kernel with the blocks of
    :func:`_ff_blocks` (inference paths), elsewhere the einsum lowering is
    kept (Pallas TPU kernels cannot lower to host HLO). Like the attention
    sites, a call with a tile emits a tile event (``kernel="matmul"``), so
    a tile the kernel could not run is counted as a fallback. With
    ``layer`` the weights are a scanned stack's and the kernel reads layer
    ``layer`` from it (only where :func:`_ff_runs_kernel`)."""
    act = act_fn(cfg.act)
    b, s, d = x.shape
    f = p["w1"].shape[-1]
    if tile is not None:
        blocks = _ff_blocks(tile, b * s, d, f)
        pallas = _ff_runs_kernel(tile, x, p)
        attn_mod.emit_tile_event(
            kernel="matmul", phase=phase,
            impl="pallas" if pallas else "reference", tile=tuple(tile),
            effective=blocks[0] if blocks else None,
            fallback=flags.pallas_enabled() and not pallas)
        if pallas:
            from repro.kernels.matmul.ops import mm

            up, down, mp = blocks
            xf = x.reshape(b * s, d)
            xf = jnp.pad(xf, ((0, mp - b * s), (0, 0)))
            interp = flags.pallas_interpret()
            h = act(mm(xf, p["w1"].astype(x.dtype), tile=up,
                       interpret=interp, layer=layer))
            h = h * mm(xf, p["w3"].astype(x.dtype), tile=up,
                       interpret=interp, layer=layer)
            y = mm(h, p["w2"].astype(x.dtype), tile=down, interpret=interp,
                   layer=layer)
            return y[:b * s].reshape(b, s, d)
    assert layer is None, "a stack of FF weights needs the Pallas kernel"
    h = act(jnp.einsum("bsd,df->bsf", x, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w3"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(x.dtype))


def layer_forward(
    p, cfg: ArchConfig, spec: LayerSpec, x, positions, cache,
    ctx: Optional[DistContext], decode: bool = False, tiles=None,
    chunk_start=None, pack_layout=None, ff_layer=None,
):
    """Returns (x_out, new_cache, aux). With ``pack_layout`` (a packed
    multi-request step) ``cache`` is a tuple of per-request caches and the
    returned new_cache matches. With ``ff_layer``, ``p["ff"]`` holds the
    scanned stack's FF weights and this layer is its ``ff_layer``-th."""
    aux = jnp.zeros((), jnp.float32)
    ff_tile = (tiles or {}).get("matmul")
    phase = "decode" if decode else "prefill"
    h = _apply_norm(p, cfg, x, "norm1")
    mix, new_cache = _mixer(p, cfg, spec, h, positions, cache, decode, ctx,
                            tiles, chunk_start=chunk_start,
                            pack_layout=pack_layout)
    if cfg.post_norms:
        mix = _apply_norm(p, cfg, mix, "post1")

    if cfg.parallel_block and spec.ff is not None:
        ff = _dense_ff(p["ff"], cfg, h, tile=ff_tile, phase=phase,
                       layer=ff_layer)
        x = x + mix + ff
    else:
        x = x + mix
        if spec.ff is not None:
            h2 = _apply_norm(p, cfg, x, "norm2")
            if spec.ff == "dense":
                ff = _dense_ff(p["ff"], cfg, h2, tile=ff_tile, phase=phase,
                               layer=ff_layer)
            else:
                ff, aux = moe_mod.moe_forward(p["moe"], cfg, h2, ctx)
            if cfg.post_norms:
                ff = _apply_norm(p, cfg, ff, "post2")
            x = x + ff
    if ctx is not None:
        x = ctx.constrain(x, "batch", None, None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stack forward
# ---------------------------------------------------------------------------

def _scan_unit(
    unit_params, cfg: ArchConfig, unit: Tuple[LayerSpec, ...], x, positions,
    unit_caches, ctx, decode: bool, remat: bool, tiles=None, chunk_start=None,
    pack_layout=None, unit_pool=None, bind=None, unbind=None,
):
    """Scan a repeat unit (tuple of per-position stacked params) ``reps``
    times. unit_caches: matching list of stacked caches (or None); in a
    packed step each element is a TUPLE of per-request stacked caches —
    scan slices every leaf's rep axis, tuples included.

    ``unit_pool`` (one stacked paged-pool leaf ``[reps, n_pages, ...]`` per
    unit position, None for non-attention positions) rides the scan CARRY,
    not ``xs``/``ys``: iteration ``i`` hands each layer the whole stacked
    leaf and its index (``bind(cache, leaf, i)`` merges them into the
    layer's cache, ``unbind(new_cache)`` splits ``(state, leaf)`` back out),
    so a layer reads only its request's pages and writes only its new rows,
    in place, instead of slicing out and restacking its whole layer of the
    pool.

    Where the FF runs the tiled matmul kernel, its stacked weights stay out
    of ``xs`` as well: the kernel reads iteration ``i``'s layer from the
    stack, so XLA never copies a layer's weights out of it before the
    kernel. Returns ``(x, new_caches, aux, new_unit_pool)``."""
    if unit_pool is None:
        unit_pool = [None] * len(unit)
    ff_tile = (tiles or {}).get("matmul")
    ff_stacks = [lp["ff"] if spec.ff == "dense"
                 and _ff_runs_kernel(ff_tile, x, lp["ff"]) else None
                 for spec, lp in zip(unit, unit_params)]
    unit_params = [lp if ff is None else
                   {k: v for k, v in lp.items() if k != "ff"}
                   for lp, ff in zip(unit_params, ff_stacks)]

    def body(carry, xs):
        xc, aux_sum, pls = carry
        lps, lcs, layer = xs
        ncs, npls = [], []
        for spec, lp, lc, pl, ff in zip(unit, lps, lcs, pls, ff_stacks):
            if pl is not None:
                lc = bind(lc, pl, layer)
            if ff is not None:
                lp = {**lp, "ff": ff}
            xc, nc, aux = layer_forward(lp, cfg, spec, xc, positions, lc,
                                        ctx, decode, tiles=tiles,
                                        chunk_start=chunk_start,
                                        pack_layout=pack_layout,
                                        ff_layer=None if ff is None else layer)
            if pl is not None:
                nc, pl = unbind(nc)
            aux_sum = aux_sum + aux
            ncs.append(nc)
            npls.append(pl)
        return (xc, aux_sum, tuple(npls)), ncs

    fn = body
    if remat:
        fn = jax.checkpoint(body, policy=flags.remat_policy())
    if unit_caches is None:
        unit_caches = [None] * len(unit)
    reps = jax.tree.leaves(unit_params)[0].shape[0]
    layers = jnp.arange(reps, dtype=jnp.int32)
    (x, aux, new_pool), new_caches = jax.lax.scan(
        fn, (x, jnp.zeros((), jnp.float32), tuple(unit_pool)),
        (tuple(unit_params), tuple(unit_caches), layers),
        unroll=flags.scan_unroll(),
    )
    return x, list(new_caches), aux, list(new_pool)


@dataclasses.dataclass(frozen=True)
class StackOutputs:
    logits: Optional[jnp.ndarray]
    aux_loss: jnp.ndarray
    caches: Optional[List[Any]] = None
    hidden: Optional[jnp.ndarray] = None
    # Updated paged-pool arrays (same structure as ``make_paged_pool``) when
    # the call ran pool-backed; None otherwise.
    pool: Optional[List[Any]] = None


def _cache_for(cfg, spec, batch, max_len, dtype, ring_local, paged=False):
    if spec.mixer in ("attn", "local_attn"):
        if paged:
            # Pool-backed request state: K/V live in the engine's shared
            # page arrays; the request itself carries only its write
            # position (its page table is engine-side bookkeeping, merged
            # in at call time). Windowed layers use the linear paged cache
            # too — the attention mask enforces the window, the ring's
            # memory bound is the pool's job now.
            return {"pos": jnp.zeros((), jnp.int32)}
        ring = ring_local and spec.mixer == "local_attn"
        length = min(max_len, cfg.attn_window) if ring else max_len
        return attn_mod.make_kv_cache(cfg, batch, length, dtype, ring=ring)
    if spec.mixer == "rglru":
        return rglru_mod.make_rglru_state(cfg, batch, dtype)
    if spec.mixer == "ssd":
        return ssm_mod.make_ssm_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def make_caches(
    cfg: ArchConfig, batch: int, max_len: int, dtype,
    ring_local: bool = False, paged: bool = False,
) -> List[Any]:
    """Caches mirroring the segment decomposition: seq segments get a list
    of per-layer caches; scan segments get per-position stacked caches.
    ``paged=True`` builds pool-backed request state: attention layers hold
    only their scalar write position (pages come from ``make_paged_pool``),
    recurrent/SSD layers keep their usual carried state."""
    caches = []
    for seg in decompose(cfg):
        if seg[0] == "seq":
            caches.append([
                _cache_for(cfg, spec, batch, max_len, dtype, ring_local,
                           paged=paged)
                for spec in seg[1]
            ])
        else:
            _, unit, reps = seg
            caches.append([
                jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape),
                    _cache_for(cfg, spec, batch, max_len, dtype, ring_local,
                               paged=paged))
                for spec in unit
            ])
    return caches


def make_paged_pool(
    cfg: ArchConfig, n_pages: int, page: int, dtype,
) -> List[Any]:
    """The engine-wide paged KV pool: per attention layer, physical page
    arrays ``[n_pages, Hkv, page, hd]`` (scan segments stack them on the
    rep axis like :func:`make_caches` stacks caches). Non-attention layers
    get ``None`` — their state stays per-request. Structure mirrors the
    segment decomposition so :func:`forward` can zip pool leaves with
    caches layer by layer."""

    def leaf(spec):
        if spec.mixer in ("attn", "local_attn"):
            return attn_mod.make_paged_kv_pages(cfg, n_pages, page, dtype)
        return None

    pool = []
    for seg in decompose(cfg):
        if seg[0] == "seq":
            pool.append([leaf(spec) for spec in seg[1]])
        else:
            _, unit, reps = seg
            pool.append([
                jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape),
                    leaf(spec))
                for spec in unit
            ])
    return pool


def _merge_pool_leaf(cache, pool_leaf, table, layer=None):
    """Hand a layer its pool pages + page table by merging them into its
    cache dict — the attention paths dispatch on ``k_pages``/``table`` keys,
    so scan/remat plumbing never changes shape. In a scanned segment the
    pages are the whole stacked leaf and ``layer`` says which layer is
    this one's."""
    if pool_leaf is None:
        return cache
    merged = {**cache, **pool_leaf, "table": table}
    if layer is not None:
        merged["layer"] = layer
    return merged


def _split_pool_leaf(new_cache):
    """Inverse of :func:`_merge_pool_leaf` on a layer's output: returns
    ``(request_state, pool_leaf_or_None)`` with the table dropped (it is
    engine bookkeeping, not model state)."""
    if isinstance(new_cache, dict) and "k_pages" in new_cache:
        pl = {"k_pages": new_cache["k_pages"],
              "v_pages": new_cache["v_pages"]}
        st = {k: v for k, v in new_cache.items()
              if k not in ("k_pages", "v_pages", "table")}
        return st, pl
    return new_cache, None


def forward(
    params, cfg: ArchConfig, tokens: jnp.ndarray,
    ctx: Optional[DistContext] = None,
    caches: Optional[List[Any]] = None,
    patch_embeds: Optional[jnp.ndarray] = None,
    decode: bool = False,
    start_pos: int = 0,
    remat: bool = True,
    logits_mode: str = "full",   # full | last | hidden
    tiles=None,
    chunked: bool = False,
    pool: Optional[List[Any]] = None,
    page_table: Optional[jnp.ndarray] = None,
) -> StackOutputs:
    """tokens [B, S] -> logits [B, S(+P), Vpad].

    ``decode=True``: S must be 1 and ``caches`` supplied (positions come from
    cache state). ``patch_embeds`` [B, P, 1024] (vlm stub) are projected and
    prepended to the token embeddings. ``logits_mode``: "last" applies the
    LM head to the final position only (prefill); "hidden" skips the head
    and returns normed hidden states (pair with fused_lm_loss to avoid
    materializing [B, S, V] logits). ``tiles`` (kernel name -> TileShape,
    from a resolved AOT plan) parameterizes the attention/FF/SSD kernel call
    sites — see ``launch.specs.resolve_model_tiles``.

    ``chunked=True`` runs the stack as one chunk of a multi-step prefill:
    tokens sit at absolute positions ``start_pos..start_pos+S-1`` (static
    ``start_pos``), attention layers attend over the cache written by the
    previous chunks plus the chunk itself (``attn_prefill_chunk``), and
    recurrent/SSD layers continue from their carried state — which they do
    natively, since ``caches`` is their initial state. Requires ``caches``.

    ``pool`` + ``page_table`` run the attention layers pool-backed: caches
    must come from ``make_caches(paged=True)``, the pool from
    ``make_paged_pool``, and ``page_table`` is the request's [n_pt] int32
    logical->physical page map (``serve.pool.PagedKVPool.device_table``).
    The updated page arrays come back in ``StackOutputs.pool``. Only the
    decode and chunked-prefill paths support it (a paged request prefills
    through chunk programs — a whole prompt is just one big chunk).
    """
    b, s = tokens.shape
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if patch_embeds is not None:
        pe = (
            patch_embeds @ params["vit_proj"]["w"].astype(patch_embeds.dtype)
            + params["vit_proj"]["b"].astype(patch_embeds.dtype)
        )
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        s = x.shape[1]
    if ctx is not None:
        x = ctx.constrain(x, "batch", None, None)

    positions = start_pos + jnp.arange(s)[None, :].astype(jnp.int32)
    positions = jnp.broadcast_to(positions, (b, s))

    if chunked and caches is None:
        raise ValueError("chunked prefill requires caches (serve state)")
    if pool is not None and not (decode or chunked):
        raise ValueError(
            "pool-backed forward supports decode and chunked prefill only")
    chunk_start = start_pos if chunked else None

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Optional[List[Any]] = [] if caches is not None else None
    new_pool: Optional[List[Any]] = [] if pool is not None else None
    for gi, seg in enumerate(decompose(cfg)):
        gp = params["segments"][gi]
        gc = caches[gi] if caches is not None else None
        pg = pool[gi] if pool is not None else None
        if seg[0] == "seq":
            ncs = []
            nps = []
            for li, spec in enumerate(seg[1]):
                lc = gc[li] if gc is not None else None
                if pg is not None:
                    lc = _merge_pool_leaf(lc, pg[li], page_table)
                x, nc, aux = layer_forward(gp[li], cfg, spec, x, positions,
                                           lc, ctx, decode, tiles=tiles,
                                           chunk_start=chunk_start)
                aux_total = aux_total + aux
                if pg is not None:
                    nc, pl = _split_pool_leaf(nc)
                    nps.append(pl)
                ncs.append(nc)
        else:
            _, unit, _ = seg
            x, ncs, aux, nps = _scan_unit(
                gp, cfg, unit, x, positions, gc, ctx, decode,
                remat=remat and not decode, tiles=tiles,
                chunk_start=chunk_start, unit_pool=pg,
                bind=lambda c, pl, i: _merge_pool_leaf(c, pl, page_table, i),
                unbind=_split_pool_leaf,
            )
            aux_total = aux_total + aux
        if new_caches is not None:
            new_caches.append(ncs)
        if new_pool is not None:
            new_pool.append(nps)

    x = _apply_norm(params, cfg, x, "final_norm")
    if logits_mode == "hidden":
        return StackOutputs(logits=None, aux_loss=aux_total,
                            caches=new_caches, hidden=x, pool=new_pool)
    if logits_mode == "last":
        x = x[:, -1:]
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    if ctx is not None:
        logits = ctx.constrain(logits, "batch", None, "vocab")
    return StackOutputs(logits=logits, aux_loss=aux_total, caches=new_caches,
                        hidden=x, pool=new_pool)


def forward_packed(
    params, cfg: ArchConfig, tokens: jnp.ndarray, states, layout,
    ctx: Optional[DistContext] = None, tiles=None,
    pool: Optional[List[Any]] = None, page_tables=None,
):
    """One packed multi-request prefill step over the whole stack.

    ``tokens`` [1, S_packed] segment-concatenates N requests' chunks;
    ``layout`` is the static tuple of per-segment ``(start, len)`` pairs
    and ``states`` the matching tuple of per-request serve states (from
    :func:`make_caches` / the previous chunk). Embedding, norms, and FF
    GEMMs run once over the pack (the step-packing occupancy win);
    attention runs one segment-masked launch per layer
    (``attn_prefill_packed``); recurrent/SSD mixers continue each
    request's carried state on per-segment slices. Per request the math is
    exactly the chunked prefill of ``forward(chunked=True)``.

    Returns ``(logits [N, Vpad], new_states)``: each segment's final-
    position logits (a request's first sampled token when this was its
    last chunk) and the tuple of per-request updated states.

    ``pool`` + ``page_tables`` (one table per segment) run the pack
    pool-backed: states come from ``make_caches(paged=True)`` and the
    SHARED page arrays ride segment 0's merged cache through the stack
    (``attn_prefill_packed``'s convention). The return grows a third
    element — the updated pool — so non-paged callers are untouched.
    """
    b, s = tokens.shape
    if b != 1:
        raise ValueError("packed prefill packs segments, not batch rows")
    if not layout or len(states) != len(layout):
        raise ValueError(f"layout/state mismatch: {len(layout)} segments, "
                         f"{len(states)} states")
    if sum(ln for _, ln in layout) != s:
        raise ValueError(f"layout {layout} does not cover {s} tokens")
    n_req = len(states)
    if pool is not None and (page_tables is None
                             or len(page_tables) != n_req):
        raise ValueError("pool-backed pack needs one page table per segment")

    def _merge_packed(cs, pool_leaf, layer=None):
        # Per-request merged caches: every segment gets its own table,
        # segment 0 additionally carries the shared page arrays (and, in a
        # scanned segment, the layer index).
        if pool is None or pool_leaf is None:
            return cs
        return tuple(
            _merge_pool_leaf(c, pool_leaf, page_tables[r], layer) if r == 0
            else _merge_pool_leaf(c, {}, page_tables[r])
            for r, c in enumerate(cs))

    def _split_packed(ncs):
        st0, pl = _split_pool_leaf(ncs[0])
        if pl is None:
            return ncs, None
        rest = tuple({k: v for k, v in c.items() if k != "table"}
                     for c in ncs[1:])
        return (st0,) + rest, pl

    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    positions = jnp.concatenate([
        start + jnp.arange(ln, dtype=jnp.int32) for start, ln in layout
    ])[None]
    if ctx is not None:
        x = ctx.constrain(x, "batch", None, None)

    # Per-request new states, mirroring each input state's segment layout.
    new_states: List[List[Any]] = [[] for _ in range(n_req)]
    new_pool: Optional[List[Any]] = [] if pool is not None else None
    for gi, seg in enumerate(decompose(cfg)):
        gp = params["segments"][gi]
        pg = pool[gi] if pool is not None else None
        if seg[0] == "seq":
            ncs = []
            nps = []
            for li, spec in enumerate(seg[1]):
                lc = tuple(st[gi][li] for st in states)
                if pg is not None:
                    lc = _merge_packed(lc, pg[li])
                x, nc, _ = layer_forward(gp[li], cfg, spec, x, positions,
                                         lc, ctx, False, tiles=tiles,
                                         pack_layout=layout)
                if pg is not None:
                    nc, pl = _split_packed(nc)
                    nps.append(pl)
                ncs.append(nc)                    # tuple over requests
            for r in range(n_req):
                new_states[r].append([nc[r] for nc in ncs])
        else:
            _, unit, _ = seg
            gc = [tuple(st[gi][ui] for st in states)
                  for ui in range(len(unit))]
            x, ncs, _, nps = _scan_unit(
                gp, cfg, unit, x, positions, gc, ctx, False, remat=False,
                tiles=tiles, pack_layout=layout, unit_pool=pg,
                bind=_merge_packed, unbind=_split_packed,
            )
            for r in range(n_req):
                new_states[r].append([nc[r] for nc in ncs])
        if new_pool is not None:
            new_pool.append(nps)

    x = _apply_norm(params, cfg, x, "final_norm")
    ends = []
    off = 0
    for _, ln in layout:
        off += ln
        ends.append(off - 1)
    x_last = x[0, jnp.asarray(ends)]              # [N, D]
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    logits = jnp.einsum("nd,dv->nv", x_last, head.astype(x_last.dtype))
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    if pool is None:
        return logits, tuple(new_states)
    return logits, tuple(new_states), new_pool


def lm_loss(logits: jnp.ndarray, targets: jnp.ndarray, cfg: ArchConfig,
            mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Cross-entropy over the real (unpadded) vocab."""
    v = cfg.padded_vocab
    vocab_ok = jnp.arange(v) < cfg.vocab_size
    logits = jnp.where(vocab_ok[None, None], logits.astype(jnp.float32),
                       -1e30)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def fused_lm_loss(
    head: jnp.ndarray, hidden: jnp.ndarray, targets: jnp.ndarray,
    cfg: ArchConfig, chunk: int = 1024,
) -> jnp.ndarray:
    """Head-projection + cross-entropy scanned over sequence chunks.

    Never materializes [B, S, Vpad] logits: each chunk's logits live only
    inside a checkpointed scan body (recomputed in backward). This is what
    lets 150k-vocab models train at seq 4096 within HBM.
    """
    b, s, d = hidden.shape
    if flags.ANALYSIS_UNROLL:
        chunk = 4096
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s  # fall back to unchunked for odd lengths
    n = s // chunk
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    vocab_ok = jnp.arange(cfg.padded_vocab) < cfg.vocab_size

    def body(total, xs):
        h, t = xs
        logits = jnp.einsum(
            "bsd,dv->bsv", h.astype(jnp.float32),
            head.astype(jnp.float32),
        )
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        logits = jnp.where(vocab_ok[None, None], logits, -1e30)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
        return total + jnp.sum(nll), None

    total, _ = jax.lax.scan(
        jax.checkpoint(body, policy=flags.remat_policy()),
        jnp.zeros((), jnp.float32), (hc, tc),
        unroll=flags.scan_unroll(),
    )
    return total / (b * s)
