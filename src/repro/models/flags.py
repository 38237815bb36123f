"""Analysis-mode switch.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count, so cost_analysis on scanned models undercounts flops/bytes/
collectives. For roofline analysis the dry-run lowers small probe configs
with ANALYSIS_UNROLL set: every lax.scan in the model unrolls (and the
RG-LRU time recurrence switches to an associative scan, which has no while
loop), making the compiled HLO's cost analysis exact. Normal training and
the full-depth compile-proof keep scans (fast compiles, small HLO).
"""
ANALYSIS_UNROLL = False

# ---------------------------------------------------------------------------
# Performance flags (§Perf hillclimb). Baseline = all off (paper-faithful
# reference lowering); the optimized dry-runs toggle these and record
# tagged results so both variants stay visible in EXPERIMENTS.md.
# ---------------------------------------------------------------------------

# Attention internals in bf16 (f32 only for softmax stats + MXU accumulate).
ATTN_COMPUTE_BF16 = False
# Remat policy for scanned layer bodies: "nothing" (full recompute) or
# "dots" (save matmul outputs — less recompute, more resident memory).
REMAT_POLICY = "nothing"
# SSD chunk-length override (0 = kernel default); autotuner-driven.
SSD_CHUNK = 0
# SSD intra-chunk einsums in bf16 (decay stats stay f32).
SSD_COMPUTE_BF16 = False
# Flash-decoding: shard_map LSE-combined decode attention over the
# sequence-sharded KV cache (kills the GQA-repeat replication collectives).
DECODE_ATTN_SHARDED = False


def set_analysis_unroll(value: bool) -> None:
    global ANALYSIS_UNROLL
    ANALYSIS_UNROLL = bool(value)


def set_perf(attn_bf16=None, remat=None, ssd_chunk=None,
             decode_sharded=None, ssd_bf16=None) -> None:
    global ATTN_COMPUTE_BF16, REMAT_POLICY, SSD_CHUNK, DECODE_ATTN_SHARDED
    global SSD_COMPUTE_BF16
    if ssd_bf16 is not None:
        SSD_COMPUTE_BF16 = bool(ssd_bf16)
    if attn_bf16 is not None:
        ATTN_COMPUTE_BF16 = bool(attn_bf16)
    if remat is not None:
        assert remat in ("nothing", "dots")
        REMAT_POLICY = remat
    if ssd_chunk is not None:
        SSD_CHUNK = int(ssd_chunk)
    if decode_sharded is not None:
        DECODE_ATTN_SHARDED = bool(decode_sharded)


def pallas_interpret() -> bool:
    """Interpret-mode Pallas: REPRO_PALLAS_INTERPRET=1 runs the Pallas TPU
    kernels through the Pallas interpreter on host backends. Orders of
    magnitude slower than the reference lowerings — for conformance CI
    only, where it exercises the exact kernel bodies (grid/BlockSpec/
    masking logic) a TPU deployment would run, without TPU hardware."""
    import os
    return os.environ.get("REPRO_PALLAS_INTERPRET", "") not in ("", "0")


def pallas_enabled() -> bool:
    """Whether plan-resolved tiles may select Pallas TPU kernels in the
    model stack. True only on a real TPU backend — or under interpret-mode
    Pallas (see :func:`pallas_interpret`): the kernels cannot lower to host
    HLO, so CPU/GPU backends keep the reference lowerings (tiles still
    parameterize those — e.g. the flash reference's KV chunk). A backend
    that fails to initialise raises: it is never read as "no TPU"."""
    import jax
    if pallas_interpret():
        return True
    return jax.default_backend() == "tpu"


def remat_policy():
    import jax
    if REMAT_POLICY == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def scan_unroll():
    """Pass as lax.scan(..., unroll=scan_unroll())."""
    return True if ANALYSIS_UNROLL else 1
