"""Compile the ahead-of-time tile-plan artifact for a hardware fleet.

Sweeps every registered kernel across the requested hardware models and the
problem families derived from the assigned shape set
(``repro.configs.shapes.SHAPES``) for each architecture (``--all-archs``
covers the full roofline table), plus the paper's bilinear scale family,
and writes one schema-versioned JSON artifact:

    PYTHONPATH=src python -m repro.launch.compile_plans --out plans.json

``--serve-buckets 64,128,512`` additionally compiles the serving
scheduler's shape family — one (batch=1, seq=edge) prefill cell per bucket
edge plus the slot-batch decode cell — so a
``ShapeBucketScheduler``-admitted request always lands on an exact plan
cell (see ``repro.serve.scheduler``).

``--measure wallclock`` times the analytically-best tile candidates on the
running backend (``launch.measure``) when real TPU hardware is present;
measured scores outrank analytic ones. Without usable hardware every cell
silently keeps the analytic cost model.

Serving (``ServeEngine(plans=...)``), training
(``TrainerConfig.tile_plans=...``) and ``TilingPolicy(plans=...)`` then
resolve tiles from the artifact — exact hit, nearest shape, or
cross-hardware transfer — without ever sweeping on a hot path.
"""
from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Sequence, Tuple

from repro import configs, kernels
from repro.configs import shapes as shape_families
from repro.core import HARDWARE_REGISTRY, Autotuner
from repro.core.plans import PLAN_SCHEMA_VERSION, PlanJob, compile_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import cell_problems, kernel_problems

# Kernels modelled only for one hardware family: everything defaults to the
# TPU estimator; the paper's CUDA gather kernel only makes sense on the
# paper's GPU descriptors.
KERNEL_FAMILIES: Dict[str, Tuple[str, ...]] = {
    "bilinear_cuda": ("gpu",),
}
DEFAULT_FAMILIES: Tuple[str, ...] = ("tpu",)

# Representative arch coverage: dense attention, hybrid attention+RG-LRU,
# and pure SSD — together they exercise every registered model kernel.
DEFAULT_ARCHS = ("qwen2-1.5b", "recurrentgemma-9b", "mamba2-2.7b")

# The paper's Fig. 3 sweep family (image kernels are shape-family-independent).
BILINEAR_PROBLEMS = [dict(src_h=800, src_w=800, scale=s) for s in (2, 4, 6, 8, 10)]


def kernel_dtypes(kernel: str, dtypes: Sequence[str]) -> Tuple[str, ...]:
    """The dtypes to compile one kernel's cells for.

    Image kernels run float32 only; model kernels sweep the requested list.
    dtype is part of the plan key — every artifact producer must use this
    policy or its entries are unreachable at lookup time.
    """
    return ("float32",) if kernel.startswith("bilinear") else tuple(dtypes)


def serve_bucket_cells(arch_names: Sequence[str], edges: Sequence[int],
                       slots: int, max_len: int, smoke: bool = False,
                       ) -> List[Tuple[str, Dict[str, int]]]:
    """The serving scheduler's shape family as deduped (kernel, problem)
    cells: a (batch=1, seq=edge) prefill cell, a chunked-prefill cell
    (chunk length swept as a first-class tile axis) AND a packed-prefill
    cell (pack width swept — how many chunk tokens ride one step) per
    bucket edge, plus the engine's (slots, max_len) decode cell, per
    architecture."""
    cells: Dict[Tuple[str, Tuple[Tuple[str, int], ...]], Dict[str, int]] = {}
    get_cfg = configs.get_smoke if smoke else configs.get_arch
    for arch in arch_names:
        cfg = get_cfg(arch)
        for edge in edges:
            for kind in ("prefill", "chunked_prefill", "packed_prefill"):
                for kernel, problem in kernel_problems(
                        cfg, 1, edge, kind).items():
                    cells[(kernel, tuple(sorted(problem.items())))] = problem
        for kernel, problem in kernel_problems(
                cfg, slots, max_len, "decode").items():
            cells[(kernel, tuple(sorted(problem.items())))] = problem
    return [(k, p) for (k, _), p in cells.items()]


def load_or_compile_cells(plans_path, cells, hw_names: Sequence[str],
                          dtype: str = "float32", meta=None, print_fn=print):
    """Reuse a compiled artifact when it covers ``cells`` on every listed
    hardware model; compile exactly those cells otherwise.

    The benches' artifact-reuse path: CI passes the compile-plans job's
    upload so bench jobs stop recompiling the serving shape family, and a
    missing/stale/non-covering artifact degrades to a local compile.
    """
    from repro import kernels as kernel_pkg
    from repro.core import HARDWARE_REGISTRY, Autotuner
    from repro.core.plans import TilePlan, compile_plan

    kernel_pkg.register_all()
    plan = TilePlan.load_or_none(plans_path)
    if plan is not None:
        covered = all(
            plan.lookup(kernel, problem, dtype, hw) is not None
            for kernel, problem in cells for hw in hw_names)
        if covered:
            print_fn(f"# reusing plan artifact {plans_path} "
                     f"({len(plan)} cells)")
            return plan
        print_fn(f"# plan artifact {plans_path} does not cover the "
                 f"requested cells; recompiling")
    jobs = [(kernel, problem, dtype, HARDWARE_REGISTRY[hw])
            for kernel, problem in cells for hw in hw_names]
    return compile_plan(jobs, autotuner=Autotuner(), meta=meta)


def build_jobs(arch_names: Sequence[str], hw_names: Sequence[str],
               dtypes: Sequence[str],
               serve_buckets: Sequence[int] = (),
               serve_slots: int = 4,
               serve_max_len: int = 0,
               serve_smoke: bool = False) -> List[PlanJob]:
    """Problem families (archs x shapes + paper bilinear + serve buckets)
    x hardware fleet."""
    kernels.register_all()
    hardware = [HARDWARE_REGISTRY[h] for h in hw_names]

    # Gather deduped (kernel, problem) cells from the shape families.
    cells: Dict[Tuple[str, Tuple[Tuple[str, int], ...]], Dict[str, int]] = {}
    for arch in arch_names:
        cfg = configs.get_arch(arch)
        for shape in shape_families.SHAPES:
            ok, _ = shape_families.applicable(cfg, shape)
            if not ok:
                continue
            for kernel, problem in cell_problems(cfg, shape).items():
                cells[(kernel, tuple(sorted(problem.items())))] = problem
    model_cells = [(k, p) for (k, _), p in cells.items()]
    if serve_buckets:
        model_cells += serve_bucket_cells(
            arch_names, serve_buckets, serve_slots,
            serve_max_len or max(serve_buckets), smoke=serve_smoke)
    image_cells = ([("bilinear", p) for p in BILINEAR_PROBLEMS]
                   + [("bilinear_cuda", p) for p in BILINEAR_PROBLEMS])

    jobs: List[PlanJob] = []
    seen = set()
    for kernel, problem in model_cells + image_cells:
        families = KERNEL_FAMILIES.get(kernel, DEFAULT_FAMILIES)
        for hw in hardware:
            if hw.family not in families:
                continue
            for dtype in kernel_dtypes(kernel, dtypes):
                job = (kernel, tuple(sorted(problem.items())), dtype, hw.name)
                if job in seen:
                    continue
                seen.add(job)
                jobs.append((kernel, problem, dtype, hw))
    return jobs


def main(argv: Optional[Sequence[str]] = None) -> str:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="plans.json",
                    help="artifact path (JSON)")
    ap.add_argument("--hardware", nargs="*",
                    default=sorted(HARDWARE_REGISTRY),
                    choices=sorted(HARDWARE_REGISTRY))
    ap.add_argument("--archs", nargs="*", default=list(DEFAULT_ARCHS),
                    choices=configs.list_archs())
    ap.add_argument("--all-archs", action="store_true",
                    help="cover every architecture (the full roofline "
                         "table's cells), not just the representative set")
    # Both serving dtypes by default: dtype is part of the plan key (it
    # changes sublane alignment and VMEM budgets), so a fleet artifact must
    # cover what engines actually run.
    ap.add_argument("--dtypes", nargs="*", default=["bfloat16", "float32"])
    ap.add_argument("--max-candidates", type=int, default=256,
                    help="sweep candidates per cell (bounds the curve size)")
    ap.add_argument("--curve-cap", type=int, default=0,
                    help="keep only the top-N curve points (0 = full curve)")
    ap.add_argument("--serve-buckets", default="",
                    help="comma list of scheduler bucket edges to compile "
                         "prefill/decode serving cells for (e.g. 64,128,512)")
    ap.add_argument("--serve-slots", type=int, default=4,
                    help="decode slot batch for --serve-buckets cells")
    ap.add_argument("--serve-max-len", type=int, default=0,
                    help="decode cache length for --serve-buckets cells "
                         "(default: largest bucket edge)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="compile serve cells for the reduced smoke configs "
                         "(what `python -m repro.launch.serve` runs) instead "
                         "of the full architectures")
    ap.add_argument("--measure", choices=("analytic", "wallclock"),
                    default="analytic",
                    help="wallclock: time top candidates on the running "
                         "backend when real hardware is present; falls back "
                         "to the analytic model per cell otherwise")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.all_archs:
        args.archs = configs.list_archs()
    buckets = sorted({int(x) for x in args.serve_buckets.split(",") if x})
    measure_factory = None
    if args.measure == "wallclock":
        from repro.launch.measure import make_measure_fn
        measure_factory = make_measure_fn

    jobs = build_jobs(args.archs, args.hardware, args.dtypes,
                      serve_buckets=buckets, serve_slots=args.serve_slots,
                      serve_max_len=args.serve_max_len,
                      serve_smoke=args.serve_smoke)
    plan = compile_plan(
        jobs,
        autotuner=Autotuner(),
        max_candidates=args.max_candidates,
        curve_cap=args.curve_cap or None,
        measure_fn_factory=measure_factory,
        meta={
            "generated_by": "repro.launch.compile_plans",
            "archs": list(args.archs),
            "dtypes": list(args.dtypes),
            "serve_buckets": buckets,
            "measure": args.measure,
        },
    )
    plan.save(args.out)
    print(f"schema v{PLAN_SCHEMA_VERSION}: {len(plan)} entries "
          f"({len(jobs)} jobs, {plan.meta['skipped_jobs']} infeasible) "
          f"-> {args.out}")
    print(f"kernels:  {', '.join(plan.kernels())}")
    print(f"hardware: {', '.join(plan.hardware_names())}")
    return args.out


if __name__ == "__main__":
    main()
