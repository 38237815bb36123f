"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds abstract params/optimizer/batch specs
(ShapeDtypeStruct only — nothing is allocated), jits the train or serve
step with explicit in/out shardings on the production mesh, compiles, and
records memory_analysis + cost_analysis + parsed collective bytes to
``dryrun_results/<cell>.json``. Incremental: existing results are skipped
unless --force.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]

The dry-run needs 512 host devices: run it as its own process (``main``
sets ``XLA_FLAGS`` before JAX initialises its backend).
"""
import argparse
import os
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import EncoderConfig
from repro.configs.shapes import SHAPES, applicable, get_shape
from repro.core.hardware import PRODUCTION_TARGET
from repro.distributed import sharding_rules as rules
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import api, flags
from repro.optim import adamw
from repro.roofline import analysis as RA
from repro.train.step import make_serve_steps, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "dryrun_results")

OPT_CFG = adamw.AdamWConfig(moment_dtype="bfloat16")  # 235B @256 chips needs it


def _batch_shardings(batch_abs, mesh):
    return jax.tree.map(
        lambda x: rules.batch_sharding(mesh, x.ndim)
        if x.shape[0] % mesh.shape[rules.batch_axes_for(mesh)[0]] == 0
        or x.shape[0] > 1 else rules.replicated(mesh),
        batch_abs,
    )


CARRY_BUDGET = 2 * 2**30  # target bytes for scan-carry activations/device


def choose_microbatches(cfg, shape, mesh) -> int:
    """Split the per-device batch so layer-boundary carries fit the budget."""
    if shape.kind != "train":
        return 1
    dp = 1
    for ax in rules.batch_axes_for(mesh):
        dp *= mesh.shape[ax]
    per_dev = max(1, shape.global_batch // dp)
    per_seq = shape.seq_len * cfg.d_model * 2 * max(cfg.n_layers, 1)
    if cfg.encoder is not None and cfg.encoder.kind == "audio":
        per_seq += cfg.encoder.seq_len * cfg.d_model * 2 * cfg.encoder.n_layers
    need = (per_dev * per_seq + CARRY_BUDGET - 1) // CARRY_BUDGET
    mb = 1
    while mb < need and mb < per_dev:
        mb *= 2
    return mb


def _compile_step(cfg, shape, mesh, microbatches: int = 1) -> Tuple[Any, Any]:
    """Build + lower + compile the cell's step. Returns (lowered, compiled)."""
    ctx = rules.make_context(mesh)
    params_abs = S.abstract_params(cfg, jnp.bfloat16)
    axes = api.param_logical_axes(cfg)
    p_shard = rules.param_shardings(axes, params_abs, mesh, fsdp=True)

    if shape.kind == "train":
        opt_abs = S.abstract_opt_state(params_abs, OPT_CFG)
        opt_shard = {"m": p_shard, "v": p_shard,
                     "step": rules.replicated(mesh)}
        batch_abs = S.input_specs(cfg, shape)
        b_shard = _batch_shardings(batch_abs, mesh)
        # Huge models (235B-class) accumulate microbatch grads in bf16 to
        # keep the f32 accumulation buffer off the HBM budget.
        import numpy as _np
        params_bytes = sum(_np.prod(l.shape) for l in jax.tree.leaves(params_abs)) * 2
        accum = jnp.bfloat16 if params_bytes / 256 > 2**30 else jnp.float32
        step = make_train_step(cfg, ctx, OPT_CFG, microbatches=microbatches,
                               accum_dtype=accum)
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, opt_shard, b_shard),
            out_shardings=(p_shard, opt_shard, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        batch_abs = S.input_specs(cfg, shape)
        batch_abs.pop("targets", None)
        b_shard = _batch_shardings(batch_abs, mesh)
        prefill_step, _ = make_serve_steps(cfg, ctx, max_len=shape.seq_len,
                                           dtype=jnp.bfloat16)
        state_abs = jax.eval_shape(prefill_step, params_abs, batch_abs)[1]
        st_shard = rules.serve_state_shardings(state_abs, mesh)
        jitted = jax.jit(
            prefill_step,
            in_shardings=(p_shard, b_shard),
            out_shardings=(None, st_shard),
        )
        lowered = jitted.lower(params_abs, batch_abs)
    else:  # decode
        state_abs = S.abstract_serve_state(cfg, shape, jnp.bfloat16,
                                           params=params_abs)
        st_shard = rules.serve_state_shardings(state_abs, mesh)
        tok_abs = S.decode_token_spec(cfg, shape)
        tok_shard = _batch_shardings({"t": tok_abs}, mesh)["t"]
        _, decode_step = make_serve_steps(cfg, ctx, max_len=shape.seq_len,
                                          dtype=jnp.bfloat16)
        jitted = jax.jit(
            decode_step,
            in_shardings=(p_shard, tok_shard, st_shard),
            out_shardings=(None, st_shard),
            donate_argnums=(2,),
        )
        lowered = jitted.lower(params_abs, tok_abs, state_abs)
    return lowered, lowered.compile()


# ---------------------------------------------------------------------------
# Exact cost terms via per-layer differencing of unrolled probe configs.
# XLA cost analysis counts while bodies once, so the full scanned compile
# undercounts; probes with 1-2 layers per distinct LayerSpec and
# ANALYSIS_UNROLL give exact per-layer costs to extrapolate from.
# ---------------------------------------------------------------------------

def _distinct_specs(cfg) -> List[Tuple[Any, int]]:
    counts: Dict[Any, int] = {}
    order = []
    for spec in cfg.layers():
        if spec not in counts:
            order.append(spec)
        counts[spec] = counts.get(spec, 0) + 1
    return [(s, counts[s]) for s in order]


def _probe_cfg(cfg, pattern, enc_layers: Optional[int] = None):
    kw = dict(n_layers=len(pattern), layer_pattern=tuple(pattern))
    if enc_layers is not None and cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=enc_layers)
    return dataclasses.replace(cfg, **kw)


def _terms_of(cfg, shape, mesh) -> Tuple[float, float, float]:
    flags.set_analysis_unroll(True)
    try:
        _, compiled = _compile_step(cfg, shape, mesh)
        t = RA.analyze(compiled, PRODUCTION_TARGET)
        return (t.flops, t.hbm_bytes, t.collective_bytes)
    finally:
        flags.set_analysis_unroll(False)


def exact_cost_terms(cfg, shape, mesh) -> Dict[str, float]:
    distinct = _distinct_specs(cfg)
    base_pattern = [s for s, _ in distinct]
    enc_probe = (cfg.encoder is not None and cfg.encoder.kind == "audio"
                 and shape.kind != "decode")
    base_enc = 1 if enc_probe else None

    base = _terms_of(_probe_cfg(cfg, base_pattern, base_enc), shape, mesh)
    total = list(base)
    for i, (spec, count) in enumerate(distinct):
        if count == 1:
            continue
        plus = _terms_of(
            _probe_cfg(cfg, base_pattern + [spec], base_enc), shape, mesh)
        for j in range(3):
            total[j] += (count - 1) * (plus[j] - base[j])
    if enc_probe and cfg.encoder.n_layers > 1:
        plus = _terms_of(_probe_cfg(cfg, base_pattern, 2), shape, mesh)
        for j in range(3):
            total[j] += (cfg.encoder.n_layers - 1) * (plus[j] - base[j])
    return {"flops": total[0], "hbm_bytes": total[1],
            "collective_bytes": total[2]}


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               fsdp: bool = True, remat: bool = True,
               extra_tag: str = "") -> Dict[str, Any]:
    cfg = configs.get_arch(arch)
    shape = get_shape(shape_name)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    mb = choose_microbatches(cfg, shape, mesh)

    # Phase A: full-depth scanned compile — proves sharding coherence and
    # gives the real memory picture.
    t0 = time.time()
    lowered, compiled = _compile_step(cfg, shape, mesh, microbatches=mb)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()

    # Phase B: exact cost terms from unrolled probe differencing. The
    # roofline table is single-pod only (per the task spec); the multi-pod
    # pass proves the pod axis shards and records memory/compile only.
    hw = PRODUCTION_TARGET
    if multi_pod:
        t_probe = 0.0
        quick = RA.analyze(compiled, hw)
        terms = quick  # scanned-HLO lower bound, recorded for reference
    else:
        t0 = time.time()
        exact = exact_cost_terms(cfg, shape, mesh)
        t_probe = time.time() - t0
        terms = RA.RooflineTerms(
            flops=exact["flops"],
            hbm_bytes=exact["hbm_bytes"],
            collective_bytes=exact["collective_bytes"],
            compute_s=exact["flops"] / hw.peak_flops_bf16,
            memory_s=exact["hbm_bytes"] / hw.hbm_bw,
            collective_s=exact["collective_bytes"]
            / (hw.ici_links * hw.ici_bw_per_link),
        )
    mf = RA.model_flops(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "n_chips": int(n_chips),
        "microbatches": mb,
        "compile_s": round(t_compile, 1),
        "probe_s": round(t_probe, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes),
            "hbm_per_chip": PRODUCTION_TARGET.hbm_bytes,
            "fits": bool(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         < PRODUCTION_TARGET.hbm_bytes),
        },
        "roofline": {
            "flops_per_chip": terms.flops,
            "hbm_bytes_per_chip": terms.hbm_bytes,
            "collective_bytes_per_chip": terms.collective_bytes,
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "roofline_fraction": terms.roofline_fraction(),
            "model_flops_global": mf,
            "useful_flops_ratio": (
                mf / (terms.flops * n_chips) if terms.flops else 0.0
            ),
        },
    }
    if extra_tag:
        result["tag"] = extra_tag
    return result


def cell_path(arch, shape_name, multi_pod, tag="") -> str:
    mesh = "multi" if multi_pod else "single"
    suffix = f".{tag}" if tag else ""
    return os.path.join(
        os.path.abspath(RESULTS_DIR),
        f"{arch}__{shape_name}__{mesh}{suffix}.json",
    )


OPT_PRESETS = {
    "attn_bf16": dict(attn_bf16=True),
    "remat_dots": dict(remat="dots"),
    "decode_sharded": dict(decode_sharded=True),
    "ssd256": dict(ssd_chunk=256),
    "ssd512": dict(ssd_chunk=512),
    "ssd_bf16": dict(ssd_bf16=True),
    "all": dict(attn_bf16=True, remat="dots", decode_sharded=True),
}


def apply_opts(opts: str) -> None:
    from repro.models import flags as _f
    _f.set_perf(attn_bf16=False, remat="nothing", ssd_chunk=0,
                decode_sharded=False)
    for name in [o for o in opts.split(",") if o]:
        _f.set_perf(**OPT_PRESETS[name])


def run_cell(arch, shape_name, multi_pod, force=False, fsdp=True,
             remat=True, tag="", opts="") -> Dict[str, Any]:
    path = cell_path(arch, shape_name, multi_pod, tag)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    apply_opts(opts)
    try:
        res = lower_cell(arch, shape_name, multi_pod, fsdp=fsdp,
                         remat=remat, extra_tag=tag)
    except Exception as e:  # record failures — they are bugs to fix
        res = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def plan_hit_report(plans, arch: str, shape_name: str,
                    dtype: str = "bfloat16") -> Dict[str, str]:
    """kernel -> resolution source for one roofline cell against a plan.

    Pure plan lookups (no lowering): the dry-run's (arch x shape) cell maps
    to kernel problems via ``specs.cell_problems`` — the same mapping
    ``compile_plans`` sweeps — so this reports how well the artifact covers
    the roofline table. Sources: exact | nearest_shape | cross_hardware |
    fallback (plan had nothing usable).
    """
    import warnings

    from repro import kernels as kernel_pkg
    from repro.core.plans import PlanTransferWarning

    kernel_pkg.register_all()
    cfg = configs.get_arch(arch)
    shape = get_shape(shape_name)
    ok, _ = applicable(cfg, shape)
    if not ok:
        return {}
    sources = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PlanTransferWarning)
        for kernel, problem in S.cell_problems(cfg, shape).items():
            res = plans.resolve(kernel, problem, dtype, PRODUCTION_TARGET)
            sources[kernel] = res.source if res is not None else "fallback"
    return sources


def main():
    # Must precede backend initialisation (the first device query or
    # compile), which nothing at import time triggers.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", default="",
                    help="comma list of OPT_PRESETS (perf hillclimb runs)")
    ap.add_argument("--tile-plans", default=None,
                    help="compiled TilePlan artifact; reports per-cell plan "
                         "hit-rate alongside the roofline results")
    ap.add_argument("--plan-dtype", default="bfloat16",
                    help="dtype key for the --tile-plans hit-rate lookups "
                         "(the dry-run itself lowers bfloat16)")
    args = ap.parse_args()
    if args.opt and not args.tag:
        args.tag = args.opt.replace(",", "+")

    from repro.core.plans import TilePlan
    plans = TilePlan.load_or_none(args.tile_plans)

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    if args.multi_pod or not args.single_pod:
        meshes.append(True)

    archs = configs.list_archs() if args.all or not args.arch else [args.arch]
    shapes = [s.name for s in SHAPES] if args.all or not args.shape \
        else [args.shape]

    plan_sources: List[Tuple[str, str]] = []   # (shape kind, source)
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                res = run_cell(arch, shape_name, mp, force=args.force,
                               fsdp=not args.no_fsdp, tag=args.tag,
                               opts=args.opt)
                status = res["status"]
                line = f"{arch:24s} {shape_name:12s} {res['mesh']:6s} {status}"
                if status == "ok":
                    r = res["roofline"]
                    line += (
                        f"  compile={res['compile_s']}s"
                        f"  peak={res['memory']['peak_bytes']/2**30:.2f}GiB"
                        f"  dom={r['dominant']}"
                        f"  frac={r['roofline_fraction']:.2f}"
                    )
                elif status == "error":
                    line += f"  {res['error'][:120]}"
                if plans is not None and not mp:
                    sources = plan_hit_report(plans, arch, shape_name,
                                              args.plan_dtype)
                    if sources:
                        kind = get_shape(shape_name).kind
                        plan_sources.extend(
                            (kind, s) for s in sources.values())
                        line += "  plan=" + ",".join(
                            f"{k}:{s}" for k, s in sorted(sources.items()))
                print(line, flush=True)
    if plans is not None and plan_sources:
        # Decode cells sweep their own kernel (flash_decode) with its own
        # sensitivity curve; report its coverage separately from the
        # full-sequence (train/prefill) cells.
        def _rate(label: str, pool: List[Tuple[str, str]]) -> None:
            if not pool:
                return
            srcs = [s for _, s in pool]
            hits = sum(s == "exact" for s in srcs)
            print(f"tile-plan hit-rate [{label}] ({args.plan_dtype}, "
                  f"{PRODUCTION_TARGET.name}): "
                  f"{hits}/{len(srcs)} exact ({hits / len(srcs):.2f}); "
                  f"sources: { {s: srcs.count(s) for s in sorted(set(srcs))} }",
                  flush=True)

        _rate("all", plan_sources)
        _rate("decode", [p for p in plan_sources if p[0] == "decode"])
        _rate("prefill+train", [p for p in plan_sources if p[0] != "decode"])


if __name__ == "__main__":
    main()
