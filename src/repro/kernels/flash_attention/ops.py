"""Jit'd wrappers + registry declarations for flash attention kernels.

``flash_attention`` (full-sequence prefill/train):
    problem dims {"sq", "skv", "d", "hq", "hkv", "window"(0=none)};
    tile rank 2 = (bq, bkv). VMEM per step: q + k + v + out tiles + scratch.
``flash_decode`` (single query over a KV cache — its own plan cell, with
    its own sensitivity curve per hardware model):
    problem dims {"b", "skv", "d", "hq", "hkv", "window"(0=none)};
    tile rank 1 = (bkv,), the split-KV chunk. VMEM per step: the K/V block
    pair plus the resident grouped-query rows, stats, and logits — VMEM
    capacity is what bounds the split size per hardware model.
``chunked_prefill`` (one prompt chunk over the live KV cache — the serving
    scheduler's sub-launch unit; see kernels/flash_attention/chunked.py):
    problem dims {"sq", "skv", "d", "hq", "hkv", "window"(0=none)} where
    ``sq`` is the whole admitted prompt length;
    tile rank 2 = (chunk, bkv) — the chunk length is a first-class tile
    axis. One grid step is one whole chunk (queries resident, K/V streamed
    in ``bkv`` splits), so VMEM capacity bounds the chunk per hardware
    model and the per-chunk fixed dispatch cost penalizes tiny chunks:
    different hardware models compile different chunk lengths for the same
    prompt.
``packed_prefill`` (N requests' chunks segment-concatenated into ONE
    launch — the step-packing unit; see flash_prefill_packed_ref):
    problem dims {"sq", "skv", "d", "hq", "hkv", "window"(0=none)} where
    ``sq`` is the segment class (the bucket edge the packed short prompts
    belong to); tile rank 2 = (pack, bkv) — ``pack`` is the PACK WIDTH,
    the total packed chunk tokens resident in one step, which may exceed
    ``sq`` (that is the point: several sq-length segments ride one
    launch). The cell models serving a fixed round of PACK_ROUND_SEGS
    segments in ceil(round/pack) packed steps, each paying one fixed
    dispatch cost, so wider packs amortize dispatch while VMEM capacity
    bounds the resident pack per hardware model: different models compile
    different pack widths for the same bucket set.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax

from repro.core import registry
from repro.core.cost_model import DRAM_PAGE_BYTES, TileWorkload
from repro.core.tiling import TileConstraints, TileShape, cdiv, dtype_bytes
from repro.kernels.flash_attention.decode import MIN_GROUP_ROWS, flash_decode
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_dense_ref, flash_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "q_offset",
                     "tile", "interpret"),
)
def attend(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
           q_offset=0, tile=(512, 512), interpret=False):
    return flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        q_offset=q_offset, tile=tile, interpret=interpret,
    )


def _constraints(problem: Mapping[str, int]) -> TileConstraints:
    return TileConstraints(
        rank=2, max_dims=(problem["sq"], problem["skv"]),
        mxu_dims=(0, 1), lane_dim=1, sublane_dim=0,
    )


def _vmem_bytes(tile: TileShape, problem: Mapping[str, int], dtype: str) -> float:
    bq, bkv = tile
    d = problem["d"]
    b = dtype_bytes(dtype)
    io_tiles = bq * d * b + 2 * bkv * d * b + bq * d * b
    scratch = bq * 128 * 4 * 2 + bq * d * 4
    logits = bq * bkv * 4  # in-register/VMEM intermediate
    return io_tiles + scratch + logits


def _workload(tile: TileShape, problem: Mapping[str, int], dtype: str) -> TileWorkload:
    bq, bkv = tile
    d = problem["d"]
    b = dtype_bytes(dtype)
    window = problem.get("window", 0)
    # Causal/window skipping halves (or more) the average visited kv blocks;
    # approximate the visited fraction analytically.
    if window:
        visit = min(1.0, (window + bkv) / problem["skv"])
    else:
        visit = 0.5 + 0.5 * bq / problem["sq"]  # causal triangle
    flops = 2.0 * bq * bkv * d * 2 * visit       # qk^T and pv
    # K/V stream dominates HBM traffic; q/out amortize over the kv loop.
    n_kv = cdiv(problem["skv"], bkv)
    hbm = (2 * bkv * d * b) * visit + (2 * bq * d * b) / n_kv
    return TileWorkload(
        flops=flops,
        hbm_bytes=hbm,
        row_segments=bkv // 8,                  # sublane segments of K stream
        row_stride_bytes=float(d * b),
        pad_waste=max(1.0, 128 / d),            # head_dim < lane pad waste
    )


def _n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    bq, bkv = tile
    return (
        problem["hq"] * cdiv(problem["sq"], bq) * cdiv(problem["skv"], bkv)
    )


def _default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    bq = min(512, problem["sq"])
    bkv = min(1024, problem["skv"])
    return TileShape((bq, bkv))


registry.register(registry.KernelSpec(
    name="flash_attention",
    constraints=_constraints,
    vmem_bytes=_vmem_bytes,
    workload=_workload,
    n_tiles=_n_tiles,
    default_tile=_default_tile,
))


# ---------------------------------------------------------------------------
# flash_decode: split-KV decode attention (one query over the cache).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "scale", "bkv", "interpret"),
)
def attend_decode(q, k, v, *, pos, kv_pos=None, window=None, softcap=None,
                  scale=None, bkv=512, interpret=False):
    return flash_decode(
        q, k, v, pos=pos, kv_pos=kv_pos, window=window, softcap=softcap,
        scale=scale, bkv=bkv, interpret=interpret,
    )


def _group_rows(problem: Mapping[str, int]) -> int:
    """Resident grouped-query rows per KV head, as the kernel pads them."""
    return max(problem["hq"] // max(problem["hkv"], 1), MIN_GROUP_ROWS)


def _decode_constraints(problem: Mapping[str, int]) -> TileConstraints:
    # bkv is the lane dim of the [rep, bkv] logits block and of the kernel's
    # [1, bkv] kv_pos block, and the N dim of the q @ k^T MXU op: it must
    # divide the cache and be a lane (128) multiple or the whole cache
    # (decode.split_legal) — the chip's compiler refuses anything else.
    return TileConstraints(
        rank=1, max_dims=(problem["skv"],), mxu_dims=(0,), lane_dim=0,
        exact_dims=(0,),
    )


def _decode_vmem_bytes(tile: TileShape, problem: Mapping[str, int],
                       dtype: str) -> float:
    bkv = tile[0]
    d = problem["d"]
    b = dtype_bytes(dtype)
    rep_p = _group_rows(problem)
    kv_tiles = 2 * bkv * d * b                  # the streamed K and V blocks
    resident = 2 * rep_p * d * b                # grouped q rows + out block
    scratch = rep_p * 128 * 4 * 2 + rep_p * d * 4
    logits = rep_p * bkv * 4
    return kv_tiles + resident + scratch + logits


def _decode_workload(tile: TileShape, problem: Mapping[str, int],
                     dtype: str) -> TileWorkload:
    bkv = tile[0]
    d = problem["d"]
    b = dtype_bytes(dtype)
    rep = max(problem["hq"] // max(problem["hkv"], 1), 1)
    window = problem.get("window", 0)
    # Decode visits every key up to ``pos`` (~ the whole cache in steady
    # state); a sliding window bounds the visited fraction like prefill.
    if window:
        visit = min(1.0, (window + bkv) / problem["skv"])
    else:
        visit = 1.0
    n_kv = cdiv(problem["skv"], bkv)
    flops = 2.0 * rep * bkv * d * 2 * visit          # qk^T and pv
    # K/V stream dominates; the resident q/out block amortizes over the KV
    # loop; each grid step re-issues the two stream DMAs (descriptor setup
    # ~ one DRAM page each) — the fixed per-split cost that makes tiny bkv
    # lose even though the streamed bytes are identical.
    rep_p = _group_rows(problem)
    hbm = (
        2 * bkv * d * b * visit
        + (2 * rep_p * d * b) / n_kv
        + 2 * DRAM_PAGE_BYTES
    )
    return TileWorkload(
        flops=flops,
        hbm_bytes=hbm,
        row_segments=bkv // 8,
        row_stride_bytes=float(d * b),
        pad_waste=max(1.0, 8 / rep) * max(1.0, 128 / d),
    )


def _decode_n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    return problem["b"] * problem["hkv"] * cdiv(problem["skv"], tile[0])


def _decode_default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    return TileShape((min(512, problem["skv"]),))


registry.register(registry.KernelSpec(
    name="flash_decode",
    constraints=_decode_constraints,
    vmem_bytes=_decode_vmem_bytes,
    workload=_decode_workload,
    n_tiles=_decode_n_tiles,
    default_tile=_decode_default_tile,
))


# ---------------------------------------------------------------------------
# chunked_prefill: one prompt chunk attending over the live KV cache.
# ---------------------------------------------------------------------------

# Fixed per-chunk dispatch cost, in DRAM pages: every chunk is a separate
# engine step (scheduler bookkeeping, program re-entry, cache-pointer DMA
# descriptors), so halving the chunk doubles this term while the streamed
# KV bytes stay constant. It is what makes degenerate tiny chunks lose the
# sweep even on overhead-free TPU descriptors.
CHUNK_STEP_PAGES = 256


def _chunked_constraints(problem: Mapping[str, int]) -> TileConstraints:
    # dim 0 = chunk length (the resident query block; sublane-tiled rows of
    # the logits block, MXU M dim), dim 1 = bkv (lane dim / MXU N dim).
    return TileConstraints(
        rank=2, max_dims=(problem["sq"], problem["skv"]),
        mxu_dims=(0, 1), lane_dim=1, sublane_dim=0,
    )


def _chunked_vmem_bytes(tile: TileShape, problem: Mapping[str, int],
                        dtype: str) -> float:
    chunk, bkv = tile
    d = problem["d"]
    b = dtype_bytes(dtype)
    resident = chunk * d * b + chunk * d * 4      # q block + f32 accumulator
    kv_tiles = 2 * bkv * d * b                    # streamed K and V blocks
    scratch = chunk * 128 * 4 * 2                 # running max / denominator
    logits = chunk * bkv * 4
    return resident + kv_tiles + scratch + logits


def _chunked_workload(tile: TileShape, problem: Mapping[str, int],
                      dtype: str) -> TileWorkload:
    chunk, bkv = tile
    sq, d = problem["sq"], problem["d"]
    b = dtype_bytes(dtype)
    window = problem.get("window", 0)
    # One grid step = one whole chunk: its queries stay resident while the
    # visible KV prefix streams once (shared across all chunk rows). The
    # average visible prefix over the chunks of one prompt:
    if window:
        visit = float(min(window + chunk, sq))
    else:
        visit = (sq + chunk) / 2.0
    # Causal masking halves the MAC work per query irrespective of the
    # chunk decomposition (inner tiles skip fully-masked blocks), so FLOPs
    # are chunk-independent per token: 4*d per (query, visible key) pair.
    flops = 4.0 * chunk * (sq / 2.0 if not window else visit) * d
    hbm = (
        2 * visit * d * b                    # K/V stream, shared by the chunk
        + 2 * chunk * d * b                  # q in / out write
        + CHUNK_STEP_PAGES * DRAM_PAGE_BYTES  # per-chunk dispatch (see above)
    )
    return TileWorkload(
        flops=flops,
        hbm_bytes=hbm,
        row_segments=bkv // 8,
        row_stride_bytes=float(d * b),
        pad_waste=max(1.0, 128 / d),
    )


def _chunked_n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    return problem["hq"] * cdiv(problem["sq"], tile[0])


def _chunked_default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    return TileShape((min(512, problem["sq"]), min(512, problem["skv"])))


registry.register(registry.KernelSpec(
    name="chunked_prefill",
    constraints=_chunked_constraints,
    vmem_bytes=_chunked_vmem_bytes,
    workload=_chunked_workload,
    n_tiles=_chunked_n_tiles,
    default_tile=_chunked_default_tile,
))


# ---------------------------------------------------------------------------
# packed_prefill: N requests' chunks segment-concatenated into one launch.
# ---------------------------------------------------------------------------

# The fixed workload one packed cell is scored against: a round of this many
# sq-length segments (short prompts of the bucket class), served in
# ceil(round/pack) packed steps. A fixed round makes scores comparable
# across pack widths — the tile changes how the round is decomposed, not
# how much work it is (mirroring chunked_prefill's whole-prompt scoring).
PACK_ROUND_SEGS = 8

# Fixed per-packed-step dispatch cost, in DRAM pages: one scheduler pick +
# program re-entry + per-segment cache-pointer descriptors per step,
# regardless of how many segments ride it. Packing exists to amortize this
# over more chunk tokens per step.
PACK_STEP_PAGES = 256


def _packed_constraints(problem: Mapping[str, int]) -> TileConstraints:
    # dim 0 = pack width (resident packed query tokens; sublane-tiled rows,
    # MXU M dim) — bounded by the whole round, NOT by sq: pack > sq is the
    # multi-segment case. dim 1 = bkv (lane dim / MXU N dim).
    return TileConstraints(
        rank=2,
        max_dims=(PACK_ROUND_SEGS * problem["sq"], problem["skv"]),
        mxu_dims=(0, 1), lane_dim=1, sublane_dim=0,
    )


def _packed_vmem_bytes(tile: TileShape, problem: Mapping[str, int],
                       dtype: str) -> float:
    pack, bkv = tile
    d = problem["d"]
    b = dtype_bytes(dtype)
    resident = pack * d * b + pack * d * 4        # q block + f32 accumulator
    kv_tiles = 2 * bkv * d * b                    # streamed K and V blocks
    scratch = pack * 128 * 4 * 2                  # running max / denominator
    logits = pack * bkv * 4
    return resident + kv_tiles + scratch + logits


def _packed_workload(tile: TileShape, problem: Mapping[str, int],
                     dtype: str) -> TileWorkload:
    pack, bkv = tile
    sq, d = problem["sq"], problem["d"]
    b = dtype_bytes(dtype)
    window = problem.get("window", 0)
    # Each packed token belongs to an sq-length segment and attends its own
    # causal prefix (avg sq/2; window-bounded when set) — segment masking
    # means packing never adds cross-segment MACs.
    visible = float(min(window, sq)) if window else sq / 2.0
    flops = 4.0 * pack * visible * d
    # Per step: every resident segment streams its own visible KV prefix
    # ((pack/sq) segments x avg prefix), the packed q/out block moves once,
    # each KV split re-issues its stream descriptors, and ONE fixed
    # dispatch cost covers the whole step — the term wider packs amortize.
    n_segs = max(1.0, pack / sq)
    hbm = (
        n_segs * 2.0 * visible * d * b            # per-segment K/V streams
        + 2 * pack * d * b                        # packed q in / out write
        + 2 * DRAM_PAGE_BYTES * cdiv(problem["skv"], bkv)
        + PACK_STEP_PAGES * DRAM_PAGE_BYTES       # per-step dispatch
    )
    return TileWorkload(
        flops=flops,
        hbm_bytes=hbm,
        row_segments=bkv // 8,
        row_stride_bytes=float(d * b),
        pad_waste=max(1.0, 128 / d),
    )


def _packed_n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    # Steps to serve the fixed round of segments, per query head.
    return problem["hq"] * cdiv(PACK_ROUND_SEGS * problem["sq"], tile[0])


def _packed_default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    pack = min(1024, PACK_ROUND_SEGS * problem["sq"])
    return TileShape((pack, min(512, problem["skv"])))


registry.register(registry.KernelSpec(
    name="packed_prefill",
    constraints=_packed_constraints,
    vmem_bytes=_packed_vmem_bytes,
    workload=_packed_workload,
    n_tiles=_packed_n_tiles,
    default_tile=_packed_default_tile,
))


# ---------------------------------------------------------------------------
# kv_page: the KV-cache page size of the paged pool (serve/pool.py).
# ---------------------------------------------------------------------------
#
# Page geometry is a tile axis, not a constant: a decode/prefill step stages
# one K page + one V page (across all kv heads) in VMEM while streaming the
# cache, so VMEM capacity bounds the page per hardware model exactly the way
# it bounds ``bkv`` — and every page transfer pays a fixed descriptor cost
# that penalizes tiny pages, while the last page of a request wastes
# (page - len % page) slots of HBM, amortized over how often the page is
# re-read. Net: cost decreases with page size until the VMEM budget binds,
# so models with different VMEM (v5e 16 MiB vs v6e 32 MiB) resolve
# different page sizes for the same cache geometry (goldens in
# tests/test_plans.py).
#     problem dims {"skv", "d", "hkv"}: cache length, head dim, kv heads.
#     tile rank 1 = (page,), the pool's page length in tokens.


def _kv_page_constraints(problem: Mapping[str, int]) -> TileConstraints:
    # A page is DMA granularity (token rows of the K/V stream), not an MXU
    # operand: it wants lane (128) multiples, nothing else.
    return TileConstraints(
        rank=1, max_dims=(problem["skv"],), lane_dim=0,
    )


def _kv_page_vmem_bytes(tile: TileShape, problem: Mapping[str, int],
                        dtype: str) -> float:
    page = tile[0]
    d, hkv = problem["d"], problem["hkv"]
    b = dtype_bytes(dtype)
    # One K page + one V page staged across all kv heads, plus the page
    # table rows resolving this cache (int32 per page).
    return 2 * page * hkv * d * b + cdiv(problem["skv"], page) * 4


def _kv_page_workload(tile: TileShape, problem: Mapping[str, int],
                      dtype: str) -> TileWorkload:
    page = tile[0]
    d, hkv, skv = problem["d"], problem["hkv"], problem["skv"]
    b = dtype_bytes(dtype)
    # Copy/accumulate through the page, sub-dominant to the stream.
    flops = 2.0 * page * hkv * d
    hbm = (
        2 * page * hkv * d * b            # the K and V page bytes
        + 2 * DRAM_PAGE_BYTES             # per-page stream descriptors
        # Allocation waste: a request's tail page holds on average page/2
        # dead slots; their bytes re-cross HBM once per full cache read,
        # amortized over the skv tokens each read covers.
        + page * hkv * d * b / (2.0 * max(skv, 1))
    )
    return TileWorkload(
        flops=flops,
        hbm_bytes=hbm,
        row_segments=page // 8,
        row_stride_bytes=float(d * b),
        pad_waste=max(1.0, 128 / d),
    )


def _kv_page_n_tiles(tile: TileShape, problem: Mapping[str, int]) -> int:
    return cdiv(problem["skv"], tile[0])


def _kv_page_default_tile(problem: Mapping[str, int], dtype: str) -> TileShape:
    return TileShape((min(512, problem["skv"]),))


registry.register(registry.KernelSpec(
    name="kv_page",
    constraints=_kv_page_constraints,
    vmem_bytes=_kv_page_vmem_bytes,
    workload=_kv_page_workload,
    n_tiles=_kv_page_n_tiles,
    default_tile=_kv_page_default_tile,
))
