"""Conformance suite for the serving trace layer (``repro.obs``).

The contracts under test:

* **determinism** — two virtual-clock runs of the same seed-pinned trace
  export byte-identical files (the trace is a function of the schedule,
  not of wall time);
* **non-interference** — tracing on vs off leaves served tokens and every
  ``ServeMetrics`` aggregate bit-identical, and with tracing disabled the
  step hot path performs zero tracer calls (guard via the
  ``Tracer.record``/``Tracer.defer`` chokepoints);
* **export fidelity** — Chrome-trace and JSONL exports round-trip through
  ``load_trace`` (process names, hardware, timestamps), and the Chrome
  form carries the Perfetto metadata (process/thread names, instant
  scopes, async-span ids) the UI needs;
* **windowed TTFT clipping** — ``ServeMetrics.ttft_window`` flags windows
  wider than the retained circular buffer, and ``FleetRouter.roll_plans``
  treats a clipped window as inconclusive (no confident keep/revert);
* **the diff CLI** — ``repro.launch.trace_report --diff`` exits 0 on an
  identical pair and nonzero when the candidate's p95 TTFT regresses;
* **device-trace regions** — under ``jax.profiler`` the engine's
  ``serve.admit``/``serve.prefill``/``serve.decode`` regions nest in
  ``serve.step`` with their args, and its programs are ``jit_serve_*``.

Engine-driving tests are marked ``slow`` (the CI packing-conformance lane
runs them next to the packing suite); everything else is fast-lane.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "benchmarks"))

from repro.launch.trace_report import diff, main as report_main  # noqa: E402
from repro.obs import Tracer, load_trace, write_jsonl, write_trace  # noqa: E402
from repro.obs.trace import LANE_STEPS  # noqa: E402
from repro.serve.metrics import (  # noqa: E402
    ServeMetrics, _LatencyStat, nearest_rank,
)

EDGES = (8, 64)
NEW_TOKENS = 3


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# Tracer core
# --------------------------------------------------------------------------

def test_deferred_step_spans_close_at_next_begin():
    clock = _Clock()
    tr = Tracer(clock=clock)
    p = tr.attach("eng")
    p.step_mark(0.0, {"prefill_tokens": 4}, 1)
    clock.t = 0.5
    p.step_mark(0.5, {"prefill_tokens": 0}, 2)
    # Step 1's span closed when step 2 began, with the inter-step duration.
    spans = [e for e in tr.events if e["name"] == "step"]
    assert len(spans) == 1
    assert spans[0]["ts"] == 0.0 and spans[0]["dur"] == 0.5
    assert spans[0]["args"]["step"] == 1
    clock.t = 0.7
    tr.flush()
    spans = [e for e in tr.events if e["name"] == "step"]
    assert len(spans) == 2
    assert spans[1]["ts"] == 0.5 and abs(spans[1]["dur"] - 0.2) < 1e-12
    # Idempotent: a second flush adds nothing.
    n = len(tr.events)
    tr.flush()
    assert len(tr.events) == n


def test_ttft_span_reproduces_metrics_sample():
    clock = _Clock()
    tr = Tracer(clock=clock)
    p = tr.attach("eng")
    clock.t = 1.25
    p.first_token(7, 64, 1.0)
    span = [e for e in tr.events if e["name"] == "ttft"][0]
    assert span["ts"] == 1.0 and span["dur"] == 0.25
    assert span["args"] == {"rid": 7, "bucket": 64}
    # No submit time -> no span (metrics recorded nothing), and no
    # first_token instant either way: the ttft span ends at that moment.
    p.first_token(8, 64, None)
    assert [e["name"] for e in tr.events] == ["ttft"]


def _tiny_trace(tmp_path, name="t.json"):
    clock = _Clock()
    tr = Tracer(clock=clock)
    p = tr.attach("engine-a", hardware="tpu_v5e")
    p.submit(1, 10, 8)
    clock.t = 0.5
    p.admit(1, 10, 0.5)
    p.step_mark(0.5, {"prefill_tokens": 10, "packed_chunks": 2}, 1)
    clock.t = 1.0
    p.first_token(1, 8, 0.0)
    p.finish(1, 3)
    path = str(tmp_path / name)
    write_trace(tr, path)
    return tr, path


def test_chrome_round_trip(tmp_path):
    tr, path = _tiny_trace(tmp_path)
    loaded = load_trace(path)
    assert loaded["procs"] == [{"pid": 1, "name": "engine-a",
                                "hardware": "tpu_v5e"}]
    names = [e["name"] for e in loaded["events"]]
    for expected in ("submit", "admit", "step", "ttft", "finish", "req"):
        assert expected in names, f"{expected} lost in round-trip"
    ttft = [e for e in loaded["events"] if e["name"] == "ttft"][0]
    assert abs(ttft["ts"] - 0.0) < 1e-9 and abs(ttft["dur"] - 1.0) < 1e-9


def test_chrome_export_is_perfetto_shaped(tmp_path):
    _, path = _tiny_trace(tmp_path)
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    meta = {(e["name"], e["pid"], e["tid"]) for e in evs if e["ph"] == "M"}
    assert ("process_name", 1, 0) in meta
    assert ("thread_name", 1, LANE_STEPS) in meta
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert all(e.get("s") == "t" for e in by_name["submit"])
    # Async req span pair carries a shared id (Perfetto groups by it).
    assert {e["ph"] for e in by_name["req"]} == {"b", "e"}
    assert {e["id"] for e in by_name["req"]} == {1}
    assert doc["otherData"]["trace_schema"] == 1


def test_jsonl_round_trip(tmp_path):
    clock = _Clock()
    tr = Tracer(clock=clock)
    p = tr.attach("eng", kind="engine", hardware="tpu_v4")
    p.submit(3, 5, 8)
    clock.t = 0.25
    p.first_token(3, 8, 0.0)
    path = str(tmp_path / "t.jsonl")
    write_jsonl(tr, path)
    loaded = load_trace(path)
    assert loaded["procs"][0]["name"] == "eng"
    assert loaded["procs"][0]["hardware"] == "tpu_v4"
    ttft = [e for e in loaded["events"] if e["name"] == "ttft"][0]
    assert ttft["dur"] == 0.25  # JSONL stores raw seconds, no unit cooking


# --------------------------------------------------------------------------
# ttft_window clipping + the roll_plans guard
# --------------------------------------------------------------------------

def test_ttft_window_flags_clipped_buffer():
    m = ServeMetrics(clock=lambda: 0.0)
    m.ttft[64] = _LatencyStat(sample_cap=4)
    for i in range(6):
        m.ttft[64].record(0.01 * (i + 1))
    samples, clipped = m.ttft_window()          # whole run: 6 > 4 retained
    assert clipped and len(samples) == 4
    samples, clipped = m.ttft_window({64: 3})   # window of 3 <= 4 retained
    assert not clipped and len(samples) == 3
    # The newest three, oldest first — circular buffer decoded correctly.
    assert samples == [0.04, 0.05, 0.06]
    assert m.ttft_p95({64: 3}) == nearest_rank(samples, 0.95)


class _StubEngine:
    """Duck-typed engine for roll_plans: metrics + plans + set_plans."""

    def __init__(self, sample_cap):
        self.metrics = ServeMetrics(clock=lambda: 0.0)
        self.metrics.ttft[64] = _LatencyStat(sample_cap=sample_cap)
        self.plans = object()
        self.swaps = []

    def set_plans(self, plans):
        self.swaps.append(plans)
        self.plans = plans


def _roll(sample_cap, n_probe):
    """One roll_plans pass where the post window regresses 100x."""
    from repro.serve.fleet import FleetRouter

    eng = _StubEngine(sample_cap)
    phase = {"n": 0}

    def probe(name):
        phase["n"] += 1
        dt = 0.01 if phase["n"] == 1 else 1.0
        for _ in range(n_probe):
            eng.metrics.ttft[64].record(dt)

    router = FleetRouter({"a": eng}, policy=None)
    new = object()
    (decision,) = router.roll_plans(new, drive_fn=probe, tolerance=1.10)
    return eng, new, decision


@pytest.mark.parametrize("sample_cap,n_probe,want_clipped,want_rollback", [
    (8192, 6, False, True),   # healthy window: 100x regression reverts
    (4, 6, True, False),      # window outgrew the buffer: inconclusive
])
def test_roll_plans_treats_clipped_windows_as_thin(
        sample_cap, n_probe, want_clipped, want_rollback):
    eng, new, decision = _roll(sample_cap, n_probe)
    assert decision.clipped is want_clipped
    assert decision.rolled_back is want_rollback
    if want_rollback:
        assert eng.swaps[-1] is not new and eng.plans is not new
    else:
        # Clipped: the swap stands unguarded, no revert happened.
        assert eng.swaps == [new] and eng.plans is new


# --------------------------------------------------------------------------
# trace_report + diff CLI
# --------------------------------------------------------------------------

def _trace_with_ttfts(tmp_path, name, durs, packed_steps=()):
    clock = _Clock()
    tr = Tracer(clock=clock)
    p = tr.attach("eng")
    for i, d in enumerate(durs):
        clock.t = float(i) + d
        p.first_token(i, 64, float(i))
    for i, n in enumerate(packed_steps):
        p.step_mark(clock.t + i, {"packed_chunks": n}, i + 1)
    clock.t += len(packed_steps) + 1.0
    path = str(tmp_path / name)
    write_trace(tr, path)
    return path


def test_diff_flags_ttft_and_occupancy_regressions(tmp_path):
    base = load_trace(_trace_with_ttfts(
        tmp_path, "base.json", [0.01] * 10, packed_steps=[3, 3, 3]))
    slow = load_trace(_trace_with_ttfts(
        tmp_path, "slow.json", [0.10] * 10, packed_steps=[3, 3, 3]))
    sparse = load_trace(_trace_with_ttfts(
        tmp_path, "sparse.json", [0.01] * 10, packed_steps=[1, 1, 1]))
    assert diff(base, base) == []
    breaches = diff(base, slow)
    assert len(breaches) == 1 and "ttft p95" in breaches[0]
    breaches = diff(base, sparse)
    assert len(breaches) == 1 and "occupancy" in breaches[0]
    # Tolerance is respected: a 5% drift under a 1.10 gate is clean.
    near = load_trace(_trace_with_ttfts(tmp_path, "near.json",
                                        [0.0105] * 10,
                                        packed_steps=[3, 3, 3]))
    assert diff(base, near) == []


def test_report_cli_exit_codes(tmp_path, capsys):
    base = _trace_with_ttfts(tmp_path, "base.json", [0.01] * 10)
    cand = _trace_with_ttfts(tmp_path, "cand.json", [0.10] * 10)
    assert report_main([base]) == 0                       # summary
    assert report_main([base, base, "--diff"]) == 0       # identical pair
    assert report_main([base, cand, "--diff"]) == 1       # regression
    assert report_main([cand, base, "--diff"]) == 0       # improvement
    assert report_main([base, "--diff"]) == 2             # usage
    assert report_main([str(tmp_path / "missing.json")]) == 2
    capsys.readouterr()
    assert report_main([base, cand, "--diff", "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["breaches"] and out["base"]["ttft"]["n"] == 10


# --------------------------------------------------------------------------
# Engine integration (slow: drives the real ServeEngine)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    import jax

    from repro import configs
    from repro.models import api

    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(n=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 64, size=int(s)).astype(np.int32)
            for s in rng.integers(4, 40, size=n)]


def _drive_traced(cfg, params, tracer, instance="eng"):
    """Packed-prefill engine on a virtual clock; fixed arrivals."""
    from repro.serve import BucketPolicy, ServeEngine, ShapeBucketScheduler

    clock = _Clock()
    if tracer is not None:
        tracer.clock = clock
    eng = ServeEngine(
        cfg, params, max_len=max(EDGES) + 16, slots=2,
        scheduler=ShapeBucketScheduler(BucketPolicy(EDGES, max_queue=99)),
        clock=clock, chunk_prefill=True, pack_prefill=True,
        prefill_slots=3, step_token_budget=32,
        tracer=tracer, instance=instance)
    prompts = _prompts()
    for i, prompt in enumerate(prompts):
        eng.add_request(prompt, max_new_tokens=NEW_TOKENS)
        if i % 3 == 2:
            eng.step()
            clock.t += 1e-3
    for _ in range(500):
        if not (eng.step() or eng.scheduler.pending()):
            break
        clock.t += 1e-3
    if tracer is not None:
        tracer.flush()
    return eng


@pytest.mark.slow
def test_two_virtual_clock_runs_export_byte_identical(smoke_model, tmp_path):
    cfg, params = smoke_model
    paths = []
    for run in ("a", "b"):
        tracer = Tracer()
        _drive_traced(cfg, params, tracer)
        path = str(tmp_path / f"run_{run}.json")
        write_trace(tracer, path)
        paths.append(path)
    a, b = (open(p, "rb").read() for p in paths)
    assert a == b, "same seed-pinned virtual-clock run, different bytes"
    assert len(load_trace(paths[0])["events"]) > 0


@pytest.mark.slow
def test_tracing_on_off_leaves_service_bit_identical(smoke_model):
    cfg, params = smoke_model
    eng_off = _drive_traced(cfg, params, None)
    eng_on = _drive_traced(cfg, params, Tracer())
    tokens_off = {r.rid: tuple(r.out_tokens) for r in eng_off._finished}
    tokens_on = {r.rid: tuple(r.out_tokens) for r in eng_on._finished}
    assert tokens_on == tokens_off and tokens_off
    assert eng_on.metrics.as_dict() == eng_off.metrics.as_dict()


@pytest.mark.slow
def test_disabled_tracing_makes_zero_tracer_calls(smoke_model, monkeypatch):
    cfg, params = smoke_model
    calls = {"n": 0}
    real_record, real_defer = Tracer.record, Tracer.defer

    def counting_record(self, *a, **k):
        calls["n"] += 1
        return real_record(self, *a, **k)

    def counting_defer(self, *a, **k):
        calls["n"] += 1
        return real_defer(self, *a, **k)

    monkeypatch.setattr(Tracer, "record", counting_record)
    monkeypatch.setattr(Tracer, "defer", counting_defer)
    eng = _drive_traced(cfg, params, None)
    assert eng._trace is None
    assert eng.metrics.completed > 0
    assert calls["n"] == 0, "hot path touched the tracer while disabled"


# --------------------------------------------------------------------------
# serve.* regions and named programs in a jax.profiler trace
# --------------------------------------------------------------------------

def _profiled_regions(cfg, params, tmp_path, packed):
    """Drive a paged, packed engine (or, with ``packed`` false, the
    per-request engine that prefills whole prompts at admission) under
    ``jax.profiler.trace``; returns the engine, its prompts and the
    trace's ``serve.*`` host events as ``(name, start_ns, end_ns,
    stats)``."""
    import glob

    import jax
    from jax.profiler import ProfileData, ProfileOptions

    from repro.serve import BucketPolicy, ServeEngine, ShapeBucketScheduler

    eng = ServeEngine(
        cfg, params, max_len=max(EDGES) + 16, slots=2,
        scheduler=ShapeBucketScheduler(BucketPolicy(EDGES, max_queue=99)),
        chunk_prefill=packed, pack_prefill=packed, prefill_slots=3,
        step_token_budget=32 if packed else 0, paged=packed,
        prefix_sharing=False)
    # Short prompts pack several to a launch; 20-40 tokens fill the
    # 64 bucket mostly with left pads.
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 64, size=n).astype(np.int32)
               for n in (5, 7, 30, 6, 21, 3, 40)]
    options = ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        for prompt in prompts:
            eng.add_request(prompt, max_new_tokens=NEW_TOKENS)
        for _ in range(200):
            if not (eng.step() or eng.scheduler.pending()):
                break
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("serve.")]
    return eng, prompts, sorted(events, key=lambda e: e[1])


@pytest.mark.parametrize("packed,programs", [
    (True, {"chunk", "pack"}), (False, {"prefill"})])
def test_engine_regions_nest_in_steps(smoke_model, tmp_path, packed,
                                      programs):
    cfg, params = smoke_model
    eng, prompts, events = _profiled_regions(cfg, params, tmp_path, packed)
    steps = [e for e in events if e[0] == "serve.step"]
    assert [e[3]["step"] for e in steps] == list(range(eng.steps_run))
    inner = [e for e in events if e[0] != "serve.step"]
    assert {e[0] for e in inner} == {"serve.admit", "serve.prefill",
                                     "serve.decode"}
    for name, start, end, _ in inner:
        assert any(s[1] <= start and end <= s[2] for s in steps), name
    prefills = [e[3] for e in events if e[0] == "serve.prefill"]
    assert {p["program"] for p in prefills} == programs
    for p in prefills:
        assert (p["segments"] >= 2) == (p["program"] == "pack"), p
    # Unpadded prompt tokens: the bucket's left pads are not counted.
    assert sum(p["tokens"] for p in prefills) == sum(map(len, prompts))
    decodes = [e[3] for e in events if e[0] == "serve.decode"]
    assert all(d == {"tokens": 1} for d in decodes)
    done = eng._finished
    assert len(done) == len(prompts)
    assert len(decodes) == sum(len(r.out_tokens) - 1 for r in done)


@pytest.mark.parametrize("paged", [True, False])
def test_engine_programs_are_named(smoke_model, paged):
    """Every program the engine jits lowers to a module named
    ``jit_serve_*`` (the name a device trace shows), also after a plan
    swap rebuilds the decode programs."""
    import re

    import jax.numpy as jnp

    from repro.models import api
    from repro.serve import BucketPolicy, ServeEngine, ShapeBucketScheduler

    cfg, params = smoke_model
    eng = ServeEngine(
        cfg, params, max_len=max(EDGES) + 16, slots=2,
        scheduler=ShapeBucketScheduler(BucketPolicy(EDGES, max_queue=99)),
        pack_prefill=True, prefill_slots=2, paged=paged)
    eng.set_plans(None)
    toks = jnp.zeros((1, 8), jnp.int32)
    if paged:
        state = api.make_paged_state(cfg, eng.dtype)
        table = jnp.zeros((eng.pool.n_pt,), jnp.int32)
        pool = (eng.pool.arrays,)
        programs = {
            "jit_serve_decode_paged": (eng._decode_paged, toks[:, :1], state,
                                       *pool, table),
            "jit_serve_chunk_paged": (eng._chunk_fn(8, 0), toks, state,
                                      *pool, table),
            "jit_serve_pack_paged": (eng._pack_fn(((0, 4), (0, 4))), toks,
                                     (state, state), *pool, (table, table)),
        }
    else:
        state = api.make_serve_state(cfg, 1, eng.max_len, eng.dtype,
                                     ring_local=bool(cfg.attn_window))
        programs = {
            "jit_serve_decode": (eng._decode, toks[:, :1], state),
            "jit_serve_chunk": (eng._chunk_fn(8, 0), toks, state),
            "jit_serve_pack": (eng._pack_fn(((0, 4), (0, 4))), toks,
                               (state, state)),
            "jit_serve_prefill": (eng._prefill_fn(8), {"tokens": toks}),
        }
    for name, (fn, *args) in programs.items():
        text = fn.lower(params, *args).as_text()
        assert re.search(r"module @(\w+)", text).group(1) == name
