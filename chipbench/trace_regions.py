"""The engine's own regions and named programs in a ``jax.profiler`` trace.

The engine writes ``serve.*`` regions into the profiler's trace, on the
device's clock: ``serve.step`` (its ``step`` index) around each step and,
inside it, ``serve.admit``, ``serve.prefill`` (``program``, ``segments``
and the unpadded prompt ``tokens`` of one launch) and ``serve.decode``
(``tokens`` of one launch). Its jitted programs are named
``serve_decode_paged``, ``serve_chunk_paged``, ``serve_pack_paged``, ...,
which the device's XLA Modules line shows as
``jit_serve_decode_paged(<fingerprint>)``. The functions below reduce both
to what the per-layer readers need; times are seconds on the trace's clock.

A program without the regions or the names (an older commit) gives a
trace with neither: every function then returns nothing to read (None, 0
or an empty list) and raises nothing.

    python3 -m chipbench.trace_regions [trace directory]

prints a JSON summary of the newest trace (by default the traced run's):
region counts, how much of each step the regions cover, device time by
program, the top operations of each program and the device's idle time in
``engine.step`` by region.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from chipbench import spec
from chipbench import trace_reduce as tr

PREFIX = "serve."
NAMED = "jit_serve_"
DECODE_PROGRAMS = ("jit_serve_decode",)
PREFILL_PROGRAMS = ("jit_serve_pack", "jit_serve_chunk", "jit_serve_prefill")
# The regions that divide a ``serve.step`` between them.
STEP_PARTS = ("serve.admit", "serve.prefill", "serve.decode")


def trace_dir() -> str:
    """Where the traced run writes its profile (``run.trace_dir``)."""
    return str(spec.DATA_ROOT / ".chipbench" / "trace")


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime: float) -> Tuple[tr.Event, ...]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [e for e in tr._events(line)
                        if e.name.startswith(PREFIX)]
    return tuple(sorted(out, key=lambda e: e.start))


def regions(directory: Optional[str] = None) -> List[tr.Event]:
    """The ``serve.*`` host events of the newest trace under
    ``directory`` (the traced run's by default), by start."""
    paths = glob.glob(os.path.join(directory or trace_dir(), "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return []
    path = max(paths, key=os.path.getmtime)
    return list(_read(path, os.path.getmtime(path)))


def named(events, name: str) -> List[tr.Event]:
    return [e for e in events if e.name == name]


def tokens(events) -> int:
    """Sum of the ``tokens`` stat of ``events``."""
    return sum(int(e.stat("tokens", 0)) for e in events)


def program(module: tr.Event) -> str:
    """A module execution's program name, without its fingerprint."""
    return module.name.split("(", 1)[0]


def module_seconds(profile: tr.Profile, prefixes) -> float:
    """Device seconds of the program executions whose names start with one
    of ``prefixes``, summed over devices."""
    return sum(m.seconds for m in profile.modules
               if m.name.startswith(tuple(prefixes)))


def intersect(a, b) -> List[Tuple[float, float]]:
    """Where two sorted lists of disjoint intervals overlap."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> List[Tuple[float, float]]:
    """The parts of ``a`` outside ``b`` (sorted lists of disjoint
    intervals)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        t, k = lo, j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < hi:
            out.append((t, hi))
    return out


def busy(profile: tr.Profile) -> List[Tuple[float, float]]:
    """When an operation ran on any device."""
    return tr.union((e.start, e.end) for e in profile.ops)


def idle_within(profile: tr.Profile, spans) -> float:
    """Seconds inside ``spans`` in which no operation ran on the device."""
    within = tr.union((s.start, s.end) for s in spans)
    return tr.length(within) - tr.length(intersect(busy(profile), within))


def innermost(events) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time ``events``
    cover, each named after the innermost event that holds it (the
    regions of one thread nest)."""
    pieces, stack = [], []
    t = float("-inf")

    def upto(until):
        nonlocal t
        if stack and until > t:
            pieces.append((t, until, stack[-1].name))
        t = max(t, until)

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= e.start:
            upto(stack[-1].end)
            stack.pop()
        upto(e.start)
        stack.append(e)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    return pieces


def idle_by_region(profile: tr.Profile, events) -> Dict[str, float]:
    """The device's idle seconds inside the harness's ``engine.step``
    spans, by the innermost ``serve.*`` region the host was in
    (``none`` where it was in none)."""
    steps = tr.union((s.start, s.end) for s in profile.spans
                     if s.name == "engine.step")
    gaps = subtract(steps, busy(profile))
    pieces = innermost(events)
    out = {}
    for name in sorted({n for _, _, n in pieces}):
        out[name] = tr.length(intersect(
            gaps, [(a, b) for a, b, n in pieces if n == name]))
    out["none"] = tr.length(gaps) - sum(out.values())
    return out


def launches_per_step(events) -> Optional[float]:
    """``serve.decode`` regions over the ``serve.step`` regions holding at
    least one."""
    steps = named(events, "serve.step")
    starts = [s.start for s in steps]
    decodes = named(events, "serve.decode")
    holding = set()
    for d in decodes:
        i = bisect.bisect_right(starts, d.start) - 1
        if i >= 0 and d.end <= steps[i].end:
            holding.add(i)
    return len(decodes) / len(holding) if holding else None


def step_coverage(events) -> List[float]:
    """For each ``serve.step``, the share of it that its admission,
    prefill and decode regions cover."""
    parts = tr.union((e.start, e.end) for e in events
                     if e.name in STEP_PARTS)
    return [tr.length(tr.clip(parts, s.start, s.end)) / s.seconds
            for s in named(events, "serve.step") if s.seconds > 0]


def busy_share(profile: tr.Profile) -> Optional[float]:
    """The share of the device's busy time spent in the engine's named
    programs (``jit_serve_*``)."""
    mine = []
    for op in profile.ops:
        home = profile.module_at(op)
        if home is not None and home.name.startswith(NAMED):
            mine.append((op.start, op.end))
    total = tr.length(busy(profile))
    return tr.length(mine) / total if total else None


def top_ops_by_program(profile: tr.Profile, n: int = 8):
    """``{program: [[operation, seconds]]}``: each program's ``n``
    operations that took most device time (control flow left out)."""
    totals: Dict[str, Dict[str, float]] = {}
    for op in profile.ops:
        if tr.op_base(op) in tr.CONTROL_FLOW:
            continue
        home = profile.module_at(op)
        per = totals.setdefault(program(home) if home else "none", {})
        per[tr.op_name(op)] = per.get(tr.op_name(op), 0.0) + op.seconds
    return {p: [[k, v] for k, v in sorted(per.items(),
                                          key=lambda kv: -kv[1])[:n]]
            for p, per in sorted(totals.items())}


def summary(directory: str) -> dict:
    profile = tr.load(directory)
    events = regions(directory)
    counts: Dict[str, int] = {}
    for e in events:
        counts[e.name] = counts.get(e.name, 0) + 1
    out = {"regions": counts,
           "tokens": {n: tokens(named(events, n))
                      for n in ("serve.prefill", "serve.decode")},
           "decode_launches_per_step": launches_per_step(events)}
    cover = step_coverage(events)
    if cover:
        out["step_coverage"] = {"median": statistics.median(cover),
                                "min": min(cover), "steps": len(cover)}
    if profile is not None:
        seconds: Dict[str, float] = {}
        for m in profile.modules:
            seconds[program(m)] = seconds.get(program(m), 0.0) + m.seconds
        out["device_s_by_program"] = dict(sorted(
            seconds.items(), key=lambda kv: -kv[1]))
        out["busy_s"] = tr.length(busy(profile))
        out["named_busy_share"] = busy_share(profile)
        out["decode_idle_s"] = idle_within(
            profile, named(events, "serve.decode"))
        if profile.spans:
            out["idle_by_region"] = idle_by_region(profile, events)
        out["top_ops_by_program"] = top_ops_by_program(profile)
    return out


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1] if len(sys.argv) > 1
                             else trace_dir()), indent=1))
