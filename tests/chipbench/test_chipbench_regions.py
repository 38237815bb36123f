"""The reduction of the engine's ``serve.*`` regions and named programs,
and the four per-layer readers built on it, checked on a small trace
(``trace_excerpt_regions.json``: two engine steps, with program,
operation and region names as a TPU v5e trace of the benchmark gives
them, and the expected numbers worked by hand)."""
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import spec, trace_regions as rg  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402

HERE = Path(__file__).resolve().parent
READERS = ("decode_ms_per_token", "prefill_ms_per_ktok",
           "decode_launches_per_step", "decode_idle_ms_per_launch")


def _event(e, device=-1):
    return tr.Event(e["name"], e["start"], e["end"], device,
                    tuple(sorted(e.get("stats", {}).items())))


def _load(name):
    data = json.loads((HERE / name).read_text())
    profile = tr.Profile(
        [_event(e, 0) for e in data["ops"]],
        [_event(e, 0) for e in data["modules"]],
        [_event(e) for e in data["spans"]], 1)
    return profile, [_event(e) for e in data.get("regions", ())], data


@pytest.fixture(scope="module")
def excerpt():
    return _load("trace_excerpt_regions.json")


def _read(metric, profile, regions, monkeypatch):
    monkeypatch.setattr(rg, "regions", lambda directory=None: regions)
    reader = spec.load_module("metrics", metric)
    return reader.read(types.SimpleNamespace(profile=profile))


@pytest.mark.parametrize("metric", READERS)
def test_reader_on_the_excerpt(excerpt, metric, monkeypatch):
    profile, regions, data = excerpt
    got = _read(metric, profile, regions, monkeypatch)
    assert got == pytest.approx(data["expected"][metric])


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_in_an_older_program(metric, monkeypatch):
    """A trace of the program before it had regions and names (anonymous
    ``jit__lambda`` programs, the harness's spans only) gives no reading,
    and no error; nor does a lost trace."""
    profile, regions, _ = _load("trace_excerpt.json")
    assert regions == []
    assert _read(metric, profile, regions, monkeypatch) is None
    assert _read(metric, None, regions, monkeypatch) is None


def test_programs_and_coverage(excerpt):
    profile, regions, data = excerpt
    want = data["expected"]
    assert rg.module_seconds(profile, rg.DECODE_PROGRAMS) == pytest.approx(
        want["module_seconds"]["decode"])
    assert rg.module_seconds(profile, rg.PREFILL_PROGRAMS) == pytest.approx(
        want["module_seconds"]["prefill"])
    assert rg.busy_share(profile) == pytest.approx(want["named_busy_share"])
    assert rg.step_coverage(regions) == pytest.approx(want["step_coverage"])
    assert rg.program(profile.modules[0]) == "jit_serve_chunk_paged"


def test_idle_by_region(excerpt):
    profile, regions, data = excerpt
    got = rg.idle_by_region(profile, regions)
    want = data["expected"]["idle_by_region"]
    assert sorted(got) == sorted(want)
    for name, seconds in want.items():
        assert got[name] == pytest.approx(seconds, abs=1e-12), name
    # Every idle second inside the harness's steps is attributed once.
    assert sum(got.values()) == pytest.approx(
        dict(tr.idle_gaps(profile))["engine.step"])


def test_innermost_pieces_nest():
    def ev(name, a, b):
        return tr.Event(name, a, b)

    pieces = rg.innermost([ev("step", 0, 10), ev("admit", 0, 1),
                           ev("decode", 2, 5), ev("decode", 5, 9),
                           ev("step", 12, 13)])
    assert pieces == [(0, 1, "admit"), (1, 2, "step"), (2, 5, "decode"),
                      (5, 9, "decode"), (9, 10, "step"), (12, 13, "step")]


def test_interval_helpers():
    a = [(0, 2), (3, 6), (8, 9)]
    b = [(1, 4), (5, 10)]
    assert rg.intersect(a, b) == [(1, 2), (3, 4), (5, 6), (8, 9)]
    assert rg.subtract(a, b) == [(0, 1), (4, 5)]
    assert rg.subtract([(0, 5)], []) == [(0, 5)]


def test_regions_leave_the_harness_reduction_alone():
    """The old excerpt's numbers hold with the engine's regions (which lie
    inside the harness's steps) mixed into its host spans."""
    old, _, data = _load("trace_excerpt.json")
    _, regions, _ = _load("trace_excerpt_regions.json")
    mixed = tr.Profile(old.ops, old.modules,
                       sorted(old.spans + regions, key=lambda e: e.start),
                       old.devices)
    want = data["expected"]
    assert tr.window(mixed) == tr.window(old)
    assert tr.busy_seconds(mixed) == pytest.approx(want["busy_s"])
    assert tr.idle_share(mixed) == pytest.approx(want["idle_share"])
    assert sorted(tr.steps(mixed)) == want["steps"]
    decode = tr.decode_programs(mixed, rows=4)
    assert sorted(decode) == want["decode_programs"]
    for kernel in ("matmul", "flash_decode"):
        got = tr.kernel_seconds(mixed, kernel, decode)
        assert got == pytest.approx(want[kernel])
    assert [n for n, _ in tr.top_ops(mixed, n=3)] == want["top_ops"]
    gaps = dict(tr.idle_gaps(mixed))
    for name, seconds in want["idle_gaps"].items():
        assert gaps[name] == pytest.approx(seconds)


def test_a_profiler_trace_round_trip(tmp_path):
    """In a real ``jax.profiler`` trace, ``trace_reduce.load`` keeps only
    the harness's spans, and ``regions`` reads the ``serve.*`` ones with
    their stats."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.obs.trace import region

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("engine.step", step=0):
            with region("step", step=7):
                with region("prefill", program="pack", segments=2,
                            tokens=900):
                    pass
                with region("decode", tokens=1):
                    pass
        with TraceAnnotation("tokens.bookkeeping"):
            pass
    profile = tr.load(str(tmp_path))
    assert [s.name for s in profile.spans] == ["engine.step",
                                               "tokens.bookkeeping"]
    assert sorted(tr.steps(profile)) == [0]
    events = rg.regions(str(tmp_path))
    assert [e.name for e in events] == ["serve.step", "serve.prefill",
                                        "serve.decode"]
    assert events[0].stat("step") == 7
    assert dict(events[1].stats) == {"program": "pack", "segments": 2,
                                     "tokens": 900}
    assert rg.launches_per_step(events) == 1.0
    assert rg.tokens(rg.named(events, "serve.prefill")) == 900
