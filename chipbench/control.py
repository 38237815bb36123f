"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 20 \
        [--modes int8,fp8] [--program-heads]

The engine is built and warmed up once; for each seed it gets that seed's
weights (the programs take the weights as an argument, so nothing
recompiles), serves the seed's traffic for ``--seconds`` exactly as a
benchmark run's window does, drops what is still in flight, and the same
sample is drawn. One JSON line per seed gives the
widest logit gap of the served tokens (the program's reading) and, for
each ``--modes`` entry, the widest gap of the token the reference computed
in that lower precision puts first (the control's reading).
``--program-heads`` adds the served tokens' gap against a reference whose
query heads are grouped by ``padded_heads // padded_kv_heads``, as a
program that pads its query heads and groups them naively would read
them (``program_kv_map``): a witness of where a disagreement lies.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def program_kv_map(cfg) -> tuple:
    """Which key/value head each real query head reads when query heads
    are grouped by ``cfg.padded_heads // cfg.padded_kv_heads``. With no
    padding that is the published head; with 12 heads padded to 16 over 2
    key/value heads it is ``h // 8`` where the published model reads
    ``h // 6``, so heads 6 and 7 read the wrong one. Whether the program
    groups its heads so is for the comparison to show."""
    group = cfg.padded_heads // cfg.padded_kv_heads
    return tuple(h // group for h in range(cfg.n_heads))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="int8,fp8")
    ap.add_argument("--program-heads", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import check, model, reference, run, spec

    cell = spec.load_cell(args.workload)
    devices = run.require_devices(int(cell["entry"]["chips"]))
    conf = cell["config"]
    modes = [m for m in args.modes.split(",") if m]
    min_tokens = int(cell["cell"]["check"]["min_tokens"])
    seeds = [int(s) for s in args.seeds.split(",")]
    prep = run.prepare(cell, seeds[0], devices)
    prep.pop("params")
    engine = prep["engine"]
    for seed in seeds:
        engine.params = params = model.make_params(prep["cfg"], conf, seed)
        served = run.serve_window(prep, seed, args.seconds, False,
                                  devices)["served"]
        engine.evict_all()
        gc.collect()
        picked = check.sample(served, seed, min_tokens)
        got = check.gaps(params, conf, picked, modes)
        line = {"workload": args.workload, "seed": seed,
                "requests": len(picked),
                "tokens": int(sum(len(g) for g in got["served"])),
                "served_max_gap": float(max(g.max() for g in got["served"]))}
        for m in modes:
            line[f"{m}_max_gap"] = float(max(g.max() for g in got[m]))
        if args.program_heads:
            kv_map = program_kv_map(model.arch_config(conf))
            widest = 0.0
            for s in picked:
                tokens = list(s.req.out_tokens)
                seq, want = check.sequence(s.req.prompt, tokens,
                                            conf["serve"])
                ref = reference.logits(params, conf, seq, want,
                                       kv_map=kv_map)
                gap = ref.max(-1) - ref[range(len(tokens)), tokens]
                widest = max(widest, float(gap.max()))
            line["served_max_gap_program_heads"] = widest
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
