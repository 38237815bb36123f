"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. In order: name the device and refuse anything but a TPU
with enough chips; look up its peaks in ``peaks.json``; draw the
configuration's weights from the seed on the device; compile the analytic
tile plan; build the serving engine as the launcher does; warm up every
program the cell's traffic reaches; serve the cell's traffic for
``--seconds``; check what was served against the plain reference; print
the compared numbers with their limits on standard error, then one JSON
line on standard output. ``--trace 1`` profiles some seconds from the
middle of the window, until prefill and decode have both run in them, and
reports the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# The profiled stretch: at least this long (at most a quarter of the
# window), from the middle of the window, and on until it has held a
# prefill step and a pure decode step.
TRACE_SECONDS = 8.0


class Refused(SystemExit):
    """The run cannot measure here: a message and a nonzero exit."""

    def __init__(self, message: str, code: int = 3):
        print(f"chipbench: {message}", file=sys.stderr, flush=True)
        super().__init__(code)


def require_devices(chips: int):
    """The devices, when JAX finds a TPU with at least ``chips`` chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds "
                      f"{len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    with open(ROOT / "chipbench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in chipbench/peaks.json "
                      f"(known: {sorted(table)})", code=4)
    return table[kind]


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read (``metrics/<name>.py``)."""

    conf: dict
    peaks: dict
    host_steps: list             # the window's steps outside the profiled
    #                              stretch
    compiles_in_window: int
    profile: object = None       # trace_reduce.Profile of the traced run
    traced: list = ()            # [(harness Step, its traced span)]


def enable_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed path
    (or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program however
    fast it compiled, so that only a cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def trace_dir() -> Path:
    """Where the traced run writes its profile (inside the checkout)."""
    from chipbench import spec

    return spec.DATA_ROOT / ".chipbench" / "trace"


def _annotate():
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def prepare(cell: dict, seed: int, devices) -> dict:
    """Weights from ``seed``, the plan, the engine, warmed up."""
    from chipbench import harness, model, spec
    from repro.core import hardware

    enable_cache()
    counter = harness.CompileCounter()
    conf, mix = cell["config"], cell["traffic"]
    cfg = model.arch_config(conf)
    params = model.make_params(cfg, conf, seed)
    engine, recording = harness.build(
        cfg, conf, params, hardware.for_device_kind(devices[0].device_kind))
    gen = spec.load_module("generators", mix["generator"])
    rate = float(cell["cell"]["rate_per_s"])
    vocab = int(conf["vocab_size"])
    warm = gen.schedule(mix, rate, 60.0, seed ^ 0x5A5A5A5A, vocab)
    harness.warm_up(engine, mix, warm, vocab, seed)
    return dict(cell=cell, cfg=cfg, params=params, engine=engine,
                recording=recording, gen=gen, counter=counter)


def both_phases(steps) -> bool:
    """Whether ``steps`` hold a step that ran prefill and one that only
    decoded: the profiled stretch runs on until it has both, so that every
    per-layer reader of the trace finds its steps whatever the seed's order
    of arrivals."""
    return (any(s.segments for s in steps)
            and any(s.decode_ctx and not s.segments for s in steps))


def settle() -> None:
    """Wait until the device has run everything dispatched so far: the
    profiled stretch then holds all the device work of the steps it timed
    (a prefill step returns before its programs have run) and none of the
    steps before it."""
    import jax

    jax.block_until_ready(jax.live_arrays())


def serve_window(prep: dict, seed: int, seconds: float, trace: bool,
                 devices) -> dict:
    """Serve one window of the cell's traffic on a prepared engine."""
    import jax

    from chipbench import harness

    cell = prep["cell"]
    mix, vocab = cell["traffic"], int(cell["config"]["vocab_size"])
    arrivals = prep["gen"].schedule(mix, float(cell["cell"]["rate_per_s"]),
                                    seconds, seed, vocab)
    setup_s = time.perf_counter() - T_START
    on_tick = None
    if trace:
        shutil.rmtree(trace_dir(), ignore_errors=True)
        trace_dir().mkdir(parents=True)
        length = min(TRACE_SECONDS, seconds / 4)
        lo = seconds / 2 - length / 2
        state = {"on": False, "done": False, "first": 0, "t0": 0.0,
                 "t1": seconds}

        def on_tick(now, steps):
            if state["done"]:
                return
            if not state["on"]:
                if now >= lo:
                    # Host spans only: tracing every Python call fills the
                    # host buffer within seconds and drops the harness's
                    # spans.
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    settle()
                    jax.profiler.start_trace(str(trace_dir()),
                                             profiler_options=options)
                    state.update(on=True, first=len(steps), t0=now)
            elif now >= lo + length and both_phases(steps[state["first"]:]):
                settle()
                jax.profiler.stop_trace()
                state.update(on=False, done=True, t1=now)

    counter = prep["counter"]
    before = counter.programs
    served, steps, lateness, rejected, due = harness.open_loop(
        prep["engine"], prep["recording"], arrivals, seconds, _annotate(),
        on_tick)
    in_window = counter.programs - before
    if trace and state["on"]:
        settle()
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return dict(served=served, steps=steps, lateness=lateness,
                rejected=rejected, due=due, setup_s=setup_s,
                in_window=in_window, peak=peak,
                traced_s=state["t1"] - state["t0"] if trace else 0.0)


def per_layer(cell: dict, run: RunData) -> dict:
    from chipbench import spec

    out = {}
    for metric in cell["per_layer"]:
        value = spec.load_module("metrics", metric["name"]).read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import check, spec, stats, trace_reduce

    cell = spec.load_cell(args.workload)
    chips = int(cell["entry"]["chips"])
    devices = require_devices(chips)
    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    print(f"chipbench: {args.workload} seed {args.seed} on "
          f"{devices[0].platform} {kind!r} x{len(devices)}",
          file=sys.stderr, flush=True)

    prep = prepare(cell, args.seed, devices)
    r = serve_window(prep, args.seed, args.seconds, bool(args.trace),
                     devices)
    counter = prep["counter"]
    late = sorted(r["lateness"]) or [0.0]
    print(f"chipbench: generator lateness over {len(late)} submissions: "
          f"p50 {1e3 * stats.percentile(late, 50):.3f} ms, p99 "
          f"{1e3 * stats.percentile(late, 99):.3f} ms, max "
          f"{1e3 * late[-1]:.3f} ms", file=sys.stderr, flush=True)
    print(f"chipbench: setup {r['setup_s']:.3f} s ({counter.programs} "
          f"programs built, {counter.fresh} of them compiled, the rest "
          f"loaded from the cache); {len(r['steps'])} steps, "
          f"{r['in_window']} programs built in the window",
          file=sys.stderr, flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(r["peak"])}
    result = {"correct": False, "attempted": r["due"],
              "failed": r["rejected"], "metrics": {}, "device": device}
    if args.trace:
        profile = trace_reduce.load(str(trace_dir()))
        traced = []
        if profile is not None and profile.spans:
            spans = trace_reduce.steps(profile)
            traced = [(r["steps"][i], s) for i, s in sorted(spans.items())
                      if i < len(r["steps"])]
            lo, hi = trace_reduce.window(profile)
            device.update(busy_s=trace_reduce.busy_seconds(profile),
                          window_s=hi - lo)
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(profile),
                "idle_gaps": trace_reduce.idle_gaps(profile)}
            if hi - lo < r["traced_s"] / 2:
                # The trace lost most of the harness's spans: nothing in it
                # can be matched to the steps it timed.
                print(f"chipbench: the trace holds {hi - lo:.3f} s of the "
                      f"{r['traced_s']:.3f} s profiled; per-layer readings "
                      f"from it are left out", file=sys.stderr)
                profile, traced = None, []
        inside = {id(step) for step, _ in traced}
        run = RunData(cell["config"], peaks,
                      [s for s in r["steps"] if id(s) not in inside],
                      r["in_window"], profile, traced)
        result["metrics"] = per_layer(cell, run)
    else:
        e2e = stats.end_to_end(r["served"], args.seconds, r["setup_s"])
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if m["name"] in e2e}

    # The check runs once the window has closed, the peak memory has been
    # read and the engine's state is freed.
    served, params = r["served"], prep["params"]
    prep.clear()
    gc.collect()
    limits = cell["cell"]["check"]
    picked = check.sample(served, args.seed, int(limits["min_tokens"]))
    gap_arrays = check.gaps(params, cell["config"], picked)["served"]
    ok, checks = check.verdict(picked, gap_arrays,
                               float(limits["max_logit_gap"]))
    result["correct"] = bool(ok)
    result["checks"] = checks
    print(f"chipbench: checked {len(picked)} requests, "
          f"{sum(len(g) for g in gap_arrays)} served tokens",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
