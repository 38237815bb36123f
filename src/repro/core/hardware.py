"""Hardware model descriptors — the framework's analogue of the paper's Table I.

The paper's central observation is that tiling decisions must be made
relative to a *hardware descriptor* (their Table I: registers/SM, active
warps, active threads, SP count, SM count, memory). On TPU the relevant
descriptor fields are different (VMEM capacity, MXU geometry, lane/sublane
tiling, HBM and ICI bandwidth) but the role is identical: every tile-shape
decision in this framework is a function of ``(kernel, problem, HardwareModel)``.

We keep the paper's two GPUs as calibrated descriptors so the reproduction
benchmarks (Fig. 3, Fig. 4, the sensitivity principle) can be evaluated with
the paper's own hardware parameters.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """A single accelerator model's performance-relevant parameters.

    TPU-oriented fields; the GPU entries (used only by the paper-reproduction
    cost model) reinterpret them as documented per-field.
    """

    name: str
    family: str                    # "tpu" | "gpu"
    # Compute ----------------------------------------------------------------
    peak_flops_bf16: float         # FLOP/s per chip (bf16 MXU; GPUs: fp32 MAD)
    num_cores: int                 # TensorCores per chip (GPUs: total SPs)
    mxu_dim: int                   # MXU systolic array dim (128); GPUs: warp size
    # Memory hierarchy -------------------------------------------------------
    hbm_bytes: int                 # device memory capacity
    hbm_bw: float                  # bytes/s HBM <-> chip
    vmem_bytes: int                # per-core fast scratch (VMEM); GPUs: shared mem/SM
    vmem_bw: float                 # bytes/s VMEM (modelled, >> hbm_bw)
    # Layout geometry --------------------------------------------------------
    lane_count: int                # minor-dim register tiling (128 on TPU; GPUs: coalesce width)
    sublane_fp32: int              # second-minor tiling for fp32 (8)
    sublane_bf16: int              # second-minor tiling for bf16 (16)
    # Interconnect -----------------------------------------------------------
    ici_bw_per_link: float         # bytes/s per ICI link
    ici_links: int                 # links per chip (torus degree)
    # Scheduling (GPU-only legacy fields, used by the paper reproduction) ----
    max_active_threads: int = 0    # per SM (paper Table I); 0 on TPU
    max_threads_per_block: int = 0 # 512 for cc<=1.3; 0 on TPU
    num_sm: int = 0                # streaming multiprocessors; 0 on TPU
    # Little's-law knob: resident threads/SM needed to saturate DRAM BW.
    saturation_threads: int = 0
    # DRAM banks: concurrently-open rows before page thrash sets in.
    dram_banks: int = 8
    # Per-block scheduling cost (GigaThread dispatch), seconds.
    sched_overhead: float = 0.0
    # Fixed overheads (calibrated, seconds) ----------------------------------
    dma_row_latency: float = 0.0   # cost of crossing a row (strided step) per tile row
    launch_overhead: float = 0.0   # per-grid-step fixed cost

    @property
    def sublane(self) -> Dict[str, int]:
        return {"float32": self.sublane_fp32, "bfloat16": self.sublane_bf16}

    def arithmetic_intensity_knee(self) -> float:
        """FLOP/byte at which the chip transitions memory- to compute-bound."""
        return self.peak_flops_bf16 / self.hbm_bw


# ---------------------------------------------------------------------------
# TPU generations (public spec-sheet numbers).
# ---------------------------------------------------------------------------

TPU_V4 = HardwareModel(
    name="tpu_v4", family="tpu",
    peak_flops_bf16=275e12, num_cores=2, mxu_dim=128,
    hbm_bytes=32 * 2**30, hbm_bw=1228e9,
    vmem_bytes=16 * 2**20, vmem_bw=20e12,
    lane_count=128, sublane_fp32=8, sublane_bf16=16,
    ici_bw_per_link=50e9, ici_links=6,
)

TPU_V5E = HardwareModel(
    name="tpu_v5e", family="tpu",
    peak_flops_bf16=197e12, num_cores=1, mxu_dim=128,
    hbm_bytes=16 * 2**30, hbm_bw=819e9,
    vmem_bytes=16 * 2**20, vmem_bw=20e12,
    lane_count=128, sublane_fp32=8, sublane_bf16=16,
    ici_bw_per_link=50e9, ici_links=4,
)

TPU_V5P = HardwareModel(
    name="tpu_v5p", family="tpu",
    peak_flops_bf16=459e12, num_cores=2, mxu_dim=128,
    hbm_bytes=95 * 2**30, hbm_bw=2765e9,
    vmem_bytes=16 * 2**20, vmem_bw=40e12,
    lane_count=128, sublane_fp32=8, sublane_bf16=16,
    ici_bw_per_link=100e9, ici_links=6,
)

TPU_V6E = HardwareModel(
    name="tpu_v6e", family="tpu",
    peak_flops_bf16=918e12, num_cores=1, mxu_dim=256,
    hbm_bytes=32 * 2**30, hbm_bw=1640e9,
    vmem_bytes=32 * 2**20, vmem_bw=40e12,
    lane_count=128, sublane_fp32=8, sublane_bf16=16,
    ici_bw_per_link=90e9, ici_links=4,
)

# ---------------------------------------------------------------------------
# The paper's two GPUs (Table I), calibrated for the Fig. 3 reproduction.
#
# peak_flops: SPs x clock x 2 (MAD) — GTX260: 192 x 1.242GHz x 2 = 477 GFLOP/s
#             8800GTS(320MB, G80): 96 x 1.2GHz x 2 = 230 GFLOP/s
# hbm_bw:     GTX260 448-bit GDDR3 ~111.9 GB/s; 8800GTS 320-bit ~64 GB/s
# dma_row_latency / launch_overhead are calibrated so the cost model
# reproduces Fig. 3's qualitative ordering (see benchmarks/bench_bilinear_fig3).
# ---------------------------------------------------------------------------

GTX260 = HardwareModel(
    name="gtx260", family="gpu",
    peak_flops_bf16=477e9, num_cores=192, mxu_dim=32,
    hbm_bytes=1 * 2**30, hbm_bw=111.9e9,
    vmem_bytes=16 * 2**10, vmem_bw=1.4e12,
    lane_count=32, sublane_fp32=1, sublane_bf16=1,
    ici_bw_per_link=0.0, ici_links=0,
    max_active_threads=1024, max_threads_per_block=512, num_sm=24,
    saturation_threads=512, dram_banks=16, sched_overhead=4.0e-7,
    dma_row_latency=2.0e-8, launch_overhead=3.0e-6,
)

GEFORCE_8800GTS = HardwareModel(
    name="geforce_8800gts", family="gpu",
    peak_flops_bf16=230e9, num_cores=96, mxu_dim=32,
    hbm_bytes=320 * 2**20, hbm_bw=64e9,
    vmem_bytes=16 * 2**10, vmem_bw=0.7e12,
    lane_count=32, sublane_fp32=1, sublane_bf16=1,
    ici_bw_per_link=0.0, ici_links=0,
    max_active_threads=768, max_threads_per_block=512, num_sm=12,
    saturation_threads=640, dram_banks=8, sched_overhead=5.0e-7,
    dma_row_latency=3.5e-8, launch_overhead=5.0e-6,
)


REGISTRY: Dict[str, HardwareModel] = {
    m.name: m
    for m in (TPU_V4, TPU_V5E, TPU_V5P, TPU_V6E, GTX260, GEFORCE_8800GTS)
}

# The roofline target for the multi-pod dry-run (per the task spec).
PRODUCTION_TARGET = TPU_V5E

# ``jax.Device.device_kind`` (as the TPU runtime reports it) of each chip
# this repo has a descriptor for.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v6 lite": "tpu_v6e",
}


def get(name: str) -> HardwareModel:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown hardware model {name!r}; known: {sorted(REGISTRY)}"
        ) from None


def for_device_kind(kind: str) -> HardwareModel:
    """The descriptor of a running chip, by its ``device_kind``. An unknown
    kind is an error: plans and timings for one chip must never be read as
    another's."""
    try:
        return REGISTRY[DEVICE_KINDS[kind]]
    except KeyError:
        raise KeyError(
            f"no hardware descriptor for device kind {kind!r}; known: "
            f"{sorted(DEVICE_KINDS)}"
        ) from None
