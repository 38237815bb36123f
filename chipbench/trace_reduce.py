"""From a ``jax.profiler`` trace to the numbers the per-layer readers need.

The traced run writes the harness's own spans into the profiler's trace
(``jax.profiler.TraceAnnotation``: ``engine.step`` with its step index,
``generator.wait``, ``tokens.bookkeeping``), on the same clock as the
device's operations. ``load`` reads the newest ``.xplane.pb`` under a
directory into plain intervals; the functions below reduce them. Times
are seconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from typing import Dict, List, Optional, Tuple

HARNESS_SPANS = ("engine.step", "generator.wait", "tokens.bookkeeping")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# Base names of the program's Pallas kernels as the TPU v5e trace shows
# them: a kernel called from a jitted wrapper takes the wrapper's name
# (``mm`` for the FF matmul); ``flash_decode``, called directly, shows as
# ``closed_call`` (one per layer of each decode launch, the only other
# custom call in the decode program).
KERNEL_OPS = {
    "matmul": ("mm",),
    "flash_decode": ("closed_call",),
}
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    device: int = -1             # device index; -1 for host spans
    stats: tuple = ()            # sorted (key, value) pairs

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def stat(self, key, default=None):
        return dict(self.stats).get(key, default)


@dataclasses.dataclass
class Profile:
    ops: List[Event]             # device operations, every device, by start
    modules: List[Event]         # device program executions, by start
    spans: List[Event]           # the harness's host spans, by start
    devices: int

    def __post_init__(self):
        self._op_starts = [e.start for e in self.ops]
        self._module_starts = [m.start for m in self.modules]

    def ops_in(self, span: Event) -> List[Event]:
        """Device operations that started inside ``span``."""
        lo = bisect.bisect_left(self._op_starts, span.start)
        hi = bisect.bisect_left(self._op_starts, span.end)
        return self.ops[lo:hi]

    def module_at(self, op: Event) -> Optional[Event]:
        """The program execution an operation ran in."""
        i = bisect.bisect_right(self._module_starts, op.start)
        while i > 0:
            i -= 1
            m = self.modules[i]
            if m.device == op.device:
                return m if op.start < m.end else None
        return None


def _events(line, device=-1) -> List[Event]:
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        stats = tuple(sorted((str(k), v) for k, v in e.stats
                             if isinstance(v, (int, float, str))))
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9,
                         device, stats))
    return out


def load(trace_dir: str) -> Optional[Profile]:
    """The newest trace under ``trace_dir``; None when there is none."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            index = int(plane.name[len(DEVICE_PLANE):].split()[0])
            devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line, index)
                elif line.name == MODULES_LINE:
                    modules += _events(line, index)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name in HARNESS_SPANS]
    return Profile(sorted(ops, key=lambda e: e.start),
                   sorted(modules, key=lambda e: e.start),
                   sorted(spans, key=lambda e: e.start), devices)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def overlap(intervals, within) -> float:
    """Length of the union of ``intervals`` inside the union of
    ``within``."""
    total = 0.0
    for lo, hi in union(within):
        total += length(clip(intervals, lo, hi))
    return total


def window(profile: Profile) -> Tuple[float, float]:
    """The traced stretch of the harness's loop: first to last span."""
    return profile.spans[0].start, max(s.end for s in profile.spans)


def busy_seconds(profile: Profile) -> float:
    """Seconds in which an operation ran on the device, in the traced
    window, averaged over the devices that ran any."""
    lo, hi = window(profile)
    per_device: Dict[int, list] = {}
    for e in profile.ops:
        per_device.setdefault(e.device, []).append((e.start, e.end))
    if not per_device:
        return 0.0
    return sum(length(clip(iv, lo, hi))
               for iv in per_device.values()) / len(per_device)


def steps(profile: Profile) -> Dict[int, Event]:
    """The traced ``engine.step`` spans by step index."""
    return {int(s.stat("step")): s for s in profile.spans
            if s.name == "engine.step" and s.stat("step") is not None}


def idle_share(profile: Profile) -> Optional[float]:
    """1 - device busy time / time inside ``engine.step`` spans."""
    spans = [(s.start, s.end) for s in steps(profile).values()]
    inside = length(spans)
    if not inside or not profile.ops:
        return None
    busy = overlap([(e.start, e.end) for e in profile.ops], spans)
    return 1.0 - busy / inside


def top_ops(profile: Profile, n: int = 10):
    """[[instruction name, total seconds]] of the device operations that
    took most time (control flow, which contains other operations, left
    out)."""
    lo, hi = window(profile)
    totals: Dict[str, float] = {}
    for e in profile.ops:
        if lo <= e.start < hi and op_base(e) not in CONTROL_FLOW:
            totals[op_name(e)] = totals.get(op_name(e), 0.0) + e.seconds
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(profile: Profile, n: int = 10):
    """[[host span, idle seconds]]: the device's idle time in the traced
    window, by the harness span the host was in (``outside`` where it was
    in none), most first."""
    lo, hi = window(profile)
    busy = union(clip([(e.start, e.end) for e in profile.ops], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    totals: Dict[str, float] = {}
    for name in HARNESS_SPANS:
        spans = [(s.start, s.end) for s in profile.spans if s.name == name]
        totals[name] = overlap(gaps, spans)
    totals["outside"] = length(gaps) - sum(totals.values())
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n] if v > 0]


def op_name(op: Event) -> str:
    """An operation's HLO instruction name (``mm.30`` of the trace's
    ``%mm.30 = bf16[...] custom-call(...)``)."""
    return op.name.split(" = ")[0].lstrip("%")


def op_base(op: Event) -> str:
    """The instruction name without its ``.N`` suffix."""
    return op_name(op).rsplit(".", 1)[0]


def is_kernel(op: Event, kernel: str) -> bool:
    return "custom-call(" in op.name and op_base(op) in KERNEL_OPS[kernel]


def out_rows(op: Event) -> int:
    """Rows of an operation's result: every dimension of its result shape
    but the last, multiplied (``1`` for ``%mm.30 = bf16[1,6912]{...}
    custom-call(...)``); 0 where the name shows no shape."""
    m = re.match(r"\(?\w+\[([\d,]+)\]", op.name.split(" = ", 1)[-1])
    if m is None:
        return 0
    return math.prod(int(d) for d in m.group(1).split(",")[:-1])


def decode_programs(profile: Profile, rows: int) -> set:
    """Names (with fingerprints) of the device programs that decode: those
    whose FF matmul takes at most ``rows`` rows (the engine's decode
    slots), where a prefill program's takes a whole chunk of prompt
    tokens."""
    names = set()
    for op in profile.ops:
        if is_kernel(op, "matmul") and out_rows(op) <= rows:
            home = profile.module_at(op)
            if home is not None:
                names.add(home.name)
    return names


def kernel_seconds(profile: Profile, kernel: str, decode_programs: set):
    """Device seconds of ``kernel`` over the whole trace, split by the
    program that ran it: ``"decode"`` for the programs in
    ``decode_programs``, ``"prefill"`` for the others. The traced run
    waits for the device before it starts and before it stops the
    profiler, so the trace holds all the device work of the steps it
    timed and none of any other."""
    out = {"decode": 0.0, "prefill": 0.0}
    for op in profile.ops:
        if is_kernel(op, kernel):
            home = profile.module_at(op)
            phase = ("decode" if home is not None
                     and home.name in decode_programs else "prefill")
            out[phase] += op.seconds
    return out
