"""Knee sweep of an open-loop cell, on the chip, in one process.

    python3 chipbench/sweep.py --workload <name> --rates 1,2,3 --seconds 30

Builds the cell's engine once, warms it up, then offers the cell's traffic
mix at each rate in turn for ``--seconds``, dropping what is still in flight between
rates. For
each rate it prints one JSON line: the requests due, the end-to-end
metrics of that stretch, and how many requests were waiting for their
first token at each quarter of it. The knee is the highest rate at which
that waiting count does not grow across the stretch. Not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def waiting_at(served, t: float) -> int:
    """Requests due by ``t`` without a first token by ``t``."""
    return sum(s.due <= t and not (s.token_times and s.token_times[0] <= t)
               for s in served)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from chipbench import harness, model, run, spec, stats
    from repro.core import hardware

    cell = spec.load_cell(args.workload)
    devices = run.require_devices(int(cell["entry"]["chips"]))
    run.enable_cache()
    conf, mix = cell["config"], cell["traffic"]
    cfg = model.arch_config(conf)
    params = model.make_params(cfg, conf, args.seed)
    engine, recording = harness.build(
        cfg, conf, params, hardware.for_device_kind(devices[0].device_kind))
    gen = spec.load_module("generators", mix["generator"])
    vocab = int(conf["vocab_size"])
    rates = [float(r) for r in args.rates.split(",")]
    harness.warm_up(engine, mix, gen.schedule(mix, rates[0], args.seconds,
                                              args.seed + 1, vocab),
                    vocab, args.seed)
    counter = harness.CompileCounter()
    for i, rate in enumerate(rates):
        arrivals = gen.schedule(mix, rate, args.seconds, args.seed + 10 + i,
                                vocab)
        before = counter.programs
        served, steps, lateness, rejected, due = harness.open_loop(
            engine, recording, arrivals, args.seconds, run._annotate())
        line = {"rate_per_s": rate, "due": due, "rejected": rejected,
                "finished": sum(s.req.done for s in served),
                "programs_in_window": counter.programs - before,
                "waiting_by_quarter": [
                    waiting_at(served, args.seconds * q / 4)
                    for q in (1, 2, 3, 4)],
                "steps": len(steps)}
        line.update(stats.end_to_end(served, args.seconds, 0.0))
        line.pop("setup_s")
        print(json.dumps(line), flush=True)
        engine.evict_all()
    return 0


if __name__ == "__main__":
    sys.exit(main())
