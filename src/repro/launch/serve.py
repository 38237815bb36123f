"""Serving launcher: random-weight requests through the serving engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --requests 6

The reduced (smoke) config by default; ``--full`` serves the architecture at
its published widths, ``--dtype bfloat16`` in bf16 (the weights are random,
drawn from a fixed seed in one jitted call). On one TPU v5e:

    PYTHONPATH=src python -m repro.launch.serve --full --dtype bfloat16 \
        --paged --pack-prefill --scheduler bucket --bucket-policy 128,512 \
        --max-len 1024 --prompt-len 100,500 --new-tokens 32 \
        --tile-plans plans.json

``--prompt-len lo,hi`` draws prompt lengths from [lo, hi). ``--hardware``
defaults to the running chip's descriptor (by ``device_kind``) on a TPU and
to the modelled production target on host backends. ``main(argv)`` can be
called in-process; it returns the finished requests and the metrics, and
exits nonzero when requests are left unfinished.

``--tile-plans plans.json`` resolves decode-path kernel tiles from a
compiled AOT artifact (see ``repro.launch.compile_plans``) instead of
tuning lazily; an artifact that cannot be read is an error.

``--scheduler bucket`` switches admission to the shape-bucketed scheduler
(``--bucket-policy`` sets the shape family: "64,128,512", "pow2:16:512", or
"plan" to derive the edges from the loaded artifact). ``--fleet
tpu_v4,tpu_v5e`` serves through the hardware-aware router instead of a
single engine — one engine per hardware model, each request placed on the
cost-model-cheapest instance. Runtime telemetry (per-bucket TTFT/TPOT,
queue depth, plan hit/transfer/fallback counters) prints at exit.

``--chunk-prefill`` splits every admitted prompt into plan-sized chunks and
co-schedules one prefill chunk with the decode batch each step (mixed
steps), bounded by ``--step-token-budget`` tokens per step. The chunk
length comes from the artifact's ``chunked_prefill`` cell for the target
hardware, so different models prefill the same prompt in different chunk
sizes. Prompts longer than the largest bucket edge are then admitted too
(padded to a multiple of the top edge) instead of rejected.

``--pack-prefill`` goes one step further (true batch mixing): each step
packs SEVERAL in-flight prefills' chunks — segment-concatenated into one
kernel launch — plus the decode batch, under the step budget and the
artifact's ``packed_prefill`` pack width (VMEM-bounded per hardware model,
so different models pack different widths). Token outputs are identical to
one-chunk-per-step and unchunked service; only the schedule densifies.

``--paged`` swaps the per-request contiguous KV caches for the fleet-wide
paged pool (``repro.serve.pool``): page size comes from the artifact's
``kv_page`` cell for the target hardware, page-table indirection runs
through decode and (packed) chunked prefill, identical prompt prefixes are
served from shared refcounted pages (copy-on-write on divergence; disable
with ``--no-prefix-sharing``), and prefill admission is gated by pool
headroom instead of ``--prefill-slots``. Served tokens are identical to
the contiguous path; pool counters print under ``pool`` in the metrics.

``--autoscale`` (with ``--fleet``) starts from a minimal fleet and lets
the telemetry-driven :class:`~repro.serve.autoscale.AutoscalePolicy`
join/drain instances between ``--min-instances`` and ``--max-instances``:
every listed hardware model is a scale candidate, priced by the live
traffic mix, so compute-heavy and memory-heavy workloads grow DIFFERENT
hardware. Decisions land on the fleet trace lane and under ``autoscale``
in the exit metrics.

``--refine`` closes the loop from telemetry back to the plan: engines divert
``--shadow-fraction`` of their steps to shadow-measuring candidate tiles
from the artifact's sensitivity curves (served tokens are untouched), the
shared :class:`~repro.serve.refine.PlanRefiner` re-ranks confidently-better
cells at exit, the refined artifact is written to ``--refine-out``, and the
deployment rolls onto it (one instance at a time through the fleet router's
rollback guard).

``--trace-out trace.json`` records the full request lifecycle (submit ->
admit/reject -> prefill chunks -> first token -> decode -> finish), every
plan-resolution audit record, and shadow/rollout decisions through
``repro.obs``; the file loads in Perfetto (ui.perfetto.dev) and feeds
``python -m repro.launch.trace_report`` for waterfalls and regression diffs.
"""
from __future__ import annotations

import argparse
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import HARDWARE_REGISTRY, PRODUCTION_TARGET
from repro.core.hardware import for_device_kind
from repro.core.plans import PlanError, TilePlan
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve import (BucketPolicy, FleetExhausted, FleetRouter,
                         ServeEngine, make_scheduler)


def build_policy(spec: str, plans, hardware_name, max_queue: int,
                 allow_overflow: bool = False) -> BucketPolicy:
    """One policy for the whole deployment. ``hardware_name=None`` derives
    "plan" edges from every hardware's cells (the union) — a fleet must
    share a single edge set or the router's bucketing and each engine's
    would diverge."""
    if spec == "plan":
        if plans is None:
            raise SystemExit("--bucket-policy plan requires --tile-plans")
        return BucketPolicy.from_plan(plans, hardware=hardware_name,
                                      max_queue=max_queue,
                                      allow_overflow=allow_overflow)
    return BucketPolicy.parse(spec, max_queue=max_queue,
                              allow_overflow=allow_overflow)


def init_params(cfg, dtype, seed: int = 0):
    """Random weights drawn from ``seed`` in ``dtype`` by one jitted call."""
    return jax.jit(lambda key: api.init_params(cfg, key, dtype))(
        jax.random.PRNGKey(seed))


def running_hardware():
    """The running chip's descriptor on a TPU backend; the modelled
    production target on host backends (which model, not run, a chip)."""
    if jax.default_backend() == "tpu":
        return for_device_kind(jax.devices()[0].device_kind)
    return PRODUCTION_TARGET


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=configs.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="serve the architecture at its published widths "
                         "(default: the reduced smoke config)")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="parameter, activation and KV-cache dtype")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--prompt-len", default="4,12",
                    help="prompt lengths are drawn from [lo, hi): 'lo,hi'")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--tile-plans", default=None,
                    help="compiled TilePlan artifact (JSON)")
    ap.add_argument("--hardware", default=None,
                    choices=sorted(HARDWARE_REGISTRY),
                    help="descriptor to resolve tiles for (default: the "
                         "running chip on a TPU, else the production "
                         "target)")
    ap.add_argument("--scheduler", default="fifo", choices=("fifo", "bucket"),
                    help="admission policy: naive FIFO or shape-bucketed")
    ap.add_argument("--bucket-policy", default="pow2:16:128",
                    help='bucket edges: "64,128", "pow2:lo:hi", or "plan" '
                         "(derive from the --tile-plans artifact)")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission bound for the bucketed scheduler")
    ap.add_argument("--chunk-prefill", action="store_true",
                    help="split prompts into plan-sized chunks and build "
                         "mixed prefill/decode steps (admits over-length "
                         "prompts via chunking)")
    ap.add_argument("--step-token-budget", type=int, default=0,
                    help="max tokens one mixed step may process (prefill "
                         "chunk + decode batch); 0 = plan chunk unclamped")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="concurrent partially-prefilled requests (chunked "
                         "mode; lets short prompts overtake long ones)")
    ap.add_argument("--pack-prefill", action="store_true",
                    help="pack MULTIPLE prefill chunks (plus the decode "
                         "batch) into each step under --step-token-budget "
                         "and the plan's per-hardware pack width, instead "
                         "of one chunk per step (implies --chunk-prefill)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV pool (page size from the "
                         "plan's kv_page cell; shared-prefix copy-on-write "
                         "reuse; admission by pool headroom — implies "
                         "--chunk-prefill)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable shared-prefix page reuse in --paged mode")
    ap.add_argument("--fleet", default="",
                    help="comma list of hardware models; serve through the "
                         "fleet router with one engine per model "
                         "(overrides --hardware)")
    ap.add_argument("--watchdog-threshold", type=int, default=8,
                    help="fleet: consecutive no-progress steps before an "
                         "instance is declared stalled and its work "
                         "recovered onto survivors")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="fleet: recovery attempts per request before it "
                         "is declared lost")
    ap.add_argument("--autoscale", action="store_true",
                    help="fleet: start with ONE instance (the first --fleet "
                         "model) and let the telemetry-driven policy join/"
                         "drain instances — every --fleet model is a scale "
                         "candidate, priced by the live traffic mix")
    ap.add_argument("--min-instances", type=int, default=1,
                    help="autoscale: never drain below this many instances")
    ap.add_argument("--max-instances", type=int, default=4,
                    help="autoscale: never join above this many instances")
    ap.add_argument("--refine", action="store_true",
                    help="shadow-measure candidate tiles during service and "
                         "emit a refined (re-ranked) plan artifact at exit; "
                         "requires --tile-plans")
    ap.add_argument("--shadow-fraction", type=float, default=1 / 32,
                    help="fraction of steps diverted to shadow measurement "
                         "when --refine is on (deterministic counter-based "
                         "sampling; default 1/32)")
    ap.add_argument("--refine-out", default=None,
                    help="write the refined plan artifact here (with "
                         "--refine; default: print the drift summary only)")
    ap.add_argument("--metrics-json", action="store_true",
                    help="dump full metrics as JSON instead of the summary")
    ap.add_argument("--trace-out", default=None,
                    help="write a request-lifecycle / plan-audit trace here "
                         "(.jsonl for JSONL, else Chrome/Perfetto JSON; "
                         "inspect with python -m repro.launch.trace_report)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    enable_compile_cache()
    # The fleet router's cost model (and autoscale candidate pricing)
    # scores default tiles straight from the kernel registry; engines only
    # register lazily on their first plan resolution, which is too late
    # for the first route() call.
    from repro import kernels

    kernels.register_all()
    cfg = (configs.get_arch(args.arch) if args.full
           else configs.get_smoke(args.arch))
    dtype = jnp.dtype(args.dtype)
    hardware = args.hardware or running_hardware().name
    lo, hi = (int(x) for x in args.prompt_len.split(","))
    params = init_params(cfg, dtype)
    plans = None
    if args.tile_plans:
        try:
            plans = TilePlan.load(args.tile_plans)
        except PlanError as exc:
            raise SystemExit(f"unusable --tile-plans artifact: {exc}")

    refiner = None
    if args.refine:
        if plans is None:
            raise SystemExit("--refine requires a loadable --tile-plans "
                             "artifact (shadow candidates come from its "
                             "sensitivity curves)")
        from repro.serve import PlanRefiner

        refiner = PlanRefiner()

    tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        tracer = Tracer()  # wall clock, same as the launcher's timing

    fleet_names = [h for h in args.fleet.split(",") if h]
    policy = None
    if args.scheduler == "bucket":
        # Fleet: derive "plan" edges across all hardware (union) so router
        # and engines share one bucketing; single engine: its own cells.
        policy = build_policy(
            args.bucket_policy, plans,
            None if fleet_names else hardware, args.max_queue,
            allow_overflow=(args.chunk_prefill or args.pack_prefill
                            or args.paged))

    def make_engine(hw_name: str, instance: str = None) -> ServeEngine:
        return ServeEngine(
            cfg, params, max_len=args.max_len, slots=args.slots,
            dtype=dtype, plans=plans, hardware=HARDWARE_REGISTRY[hw_name],
            scheduler=make_scheduler(args.scheduler, policy),
            chunk_prefill=args.chunk_prefill,
            step_token_budget=args.step_token_budget,
            prefill_slots=args.prefill_slots,
            pack_prefill=args.pack_prefill,
            paged=args.paged,
            prefix_sharing=not args.no_prefix_sharing,
            shadow_fraction=args.shadow_fraction if args.refine else 0.0,
            refiner=refiner, tracer=tracer,
            instance=instance or hw_name)

    router = None
    if fleet_names:
        if args.scheduler != "bucket":
            raise SystemExit("--fleet requires --scheduler bucket "
                             "(routing is per shape bucket)")
        autoscaler = None
        seed_names = fleet_names
        if args.autoscale:
            from repro.serve import AutoscalePolicy, ScaleCandidate

            # Start minimal; every --fleet model is a candidate the policy
            # may join (under its own name, suffixed on re-join) when the
            # mix-priced cost says so.
            candidates = tuple(
                ScaleCandidate(name=h, hardware=h,
                               make_engine=lambda name, hw=h:
                                   make_engine(hw, instance=name))
                for h in fleet_names)
            autoscaler = AutoscalePolicy(
                candidates, min_instances=args.min_instances,
                max_instances=args.max_instances)
            seed_names = fleet_names[:max(1, args.min_instances)]
        router = FleetRouter({h: make_engine(h) for h in seed_names}, policy,
                             tracer=tracer,
                             watchdog_threshold=args.watchdog_threshold,
                             retry_budget=args.retry_budget,
                             autoscaler=autoscaler)
    elif args.autoscale:
        raise SystemExit("--autoscale requires --fleet (the candidates come "
                         "from its hardware list)")
    else:
        engine = make_engine(hardware)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    rejected = 0
    for i in range(args.requests):
        prompt = rng.integers(2, cfg.vocab_size, size=rng.integers(lo, hi))
        if router is not None:
            ok = router.route(prompt, max_new_tokens=args.new_tokens)
        else:
            ok = engine.add_request(prompt, max_new_tokens=args.new_tokens)
        rejected += ok is None

    if router is not None:
        try:
            done_by = router.run_until_done()
        except FleetExhausted as exc:
            # A partial result set must never read as a complete run:
            # report what did finish, then fail.
            for name, eng in sorted(router.engines.items()):
                for r in eng._finished:
                    print(f"req {r.rid}@{name}: {r.out_tokens}")
            raise SystemExit(f"fleet exhausted: {exc}")
        done = [r for rs in done_by.values() for r in rs]
        for name, rs in sorted(done_by.items()):
            for r in rs:
                print(f"req {r.rid}@{name}: {r.out_tokens}")
        print("placements:", {str(b): p for b, p in
                              sorted(router.placements().items())})
        metrics = router.metrics()
        scale = metrics.get("autoscale")
        if scale is not None:
            print(f"autoscale: {scale['joins']} join(s), "
                  f"{scale['drains']} drain(s) over "
                  f"{scale['evaluations']} evaluation(s); final fleet: "
                  f"{router.live_instances()}")
            for entry in scale["log"]:
                print(f"  step {entry['step']}: {entry['action']} "
                      f"{entry['instance']} ({entry['reason']})")
    else:
        done = engine.run_until_done()
        for r in done:
            print(f"req {r.rid}: {r.out_tokens}")
        if engine.in_flight() or engine.scheduler.pending():
            raise SystemExit(
                f"engine stopped with {engine.in_flight()} request(s) in "
                f"flight and {engine.scheduler.pending()} queued")
        metrics = engine.metrics.as_dict()

    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"{len(done)} requests ({rejected} rejected), {toks} tokens in "
          f"{dt:.2f}s ({toks / dt:.1f} tok/s)")

    if refiner is not None:
        from repro.serve import drift_report

        refine_trace = (tracer.attach("refiner", kind="refiner")
                        if tracer is not None else None)
        refined = refiner.refine(plans, trace=refine_trace)
        report = drift_report(refined)
        print(f"refined {report['n_refined']} cell(s) from "
              f"{report['shadow_samples']} shadow sample(s)")
        for cell in report["cells"]:
            print(f"  {cell['cell']}: {cell['incumbent']} -> "
                  f"{cell['refined']} ({cell['speedup']:.2f}x, "
                  f"{cell['samples']} samples)")
        if args.refine_out:
            refined.save(args.refine_out)
            print(f"refined plan artifact -> {args.refine_out}")
        # Versioned rollout: the fleet rolls one instance at a time via the
        # p95-TTFT guard (unguarded here — the demo has no probe traffic);
        # a single engine just swaps.
        if router is not None:
            for decision in router.roll_plans(refined):
                print(f"rolled {decision.instance}: "
                      f"rolled_back={decision.rolled_back}")
        else:
            engine.set_plans(refined)
            print("engine rolled onto the refined artifact")

    if tracer is not None:
        from repro.obs import write_jsonl, write_trace

        if args.trace_out.endswith(".jsonl"):
            write_jsonl(tracer, args.trace_out)
        else:
            write_trace(tracer, args.trace_out)
        print(f"trace -> {args.trace_out} "
              f"({len(tracer.events)} events; open in ui.perfetto.dev or "
              f"run python -m repro.launch.trace_report {args.trace_out})")

    if args.metrics_json:
        print(json.dumps(metrics, indent=1, sort_keys=True, default=str))
    elif router is not None:
        for name, eng in sorted(router.engines.items()):
            print(f"--- {name}")
            print(eng.metrics.render())
    else:
        print(engine.metrics.render())
    return {"requests": done, "rejected": rejected, "metrics": metrics,
            "wall_s": dt}


if __name__ == "__main__":
    main()
