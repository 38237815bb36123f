"""The system under test, driven the way its users would drive it.

``build`` makes the configuration's weights from the seed, compiles the
analytic tile plan in process and builds ``ServeEngine`` exactly as
``repro.launch.serve`` does for ``--full --paged --pack-prefill
--scheduler bucket``, with the configuration's serving settings.
``warm_up`` runs every program the cell's traffic reaches before the
window; ``open_loop`` drives ``add_request`` and ``step`` on the
generator's schedule for the window and records, on the host clock, every
step and every token at the moment ``step()`` returned with it.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Step:
    start: float                 # seconds since the window opened
    end: float
    prefill_tokens: int
    decode_tokens: int
    # (admitted length, start offset, tokens, left pads of the prompt) per
    # prefill segment the step ran
    segments: tuple = ()
    # cache positions each token decoded in the step attended over
    decode_ctx: tuple = ()


@dataclasses.dataclass
class Served:
    """One request of the window, as its user saw it."""

    rid: int
    due: float                   # seconds since the window opened
    prompt_len: int
    max_new_tokens: int
    token_times: List[float] = dataclasses.field(default_factory=list)
    req: object = None           # the engine's Request (tokens, prompt)


class CompileCounter:
    """Programs built, as JAX reports them (``jax.monitoring``): every
    backend compile event, of which ``cache_hits`` were loaded from the
    persistent cache instead of compiled."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        return self.compiles

    @property
    def fresh(self) -> int:
        return self.compiles - self.cache_hits


class _Recording:
    """The program's scheduler, unchanged, remembering every request it
    accepted so the harness can read each request's tokens."""

    def __init__(self, inner):
        self._inner = inner
        self.accepted = []

    def submit(self, req) -> bool:
        ok = self._inner.submit(req)
        if ok:
            self.accepted.append(req)
        return ok

    def __getattr__(self, name):
        return getattr(self._inner, name)


def plan_cells(cfg, edges, slots: int, max_len: int):
    """The serving plan's (kernel, problem) cells for this configuration,
    as ``launch.compile_plans.serve_bucket_cells`` lists them for a
    registry name."""
    from repro.launch.specs import kernel_problems

    cells = {}
    for edge in edges:
        for kind in ("prefill", "chunked_prefill", "packed_prefill"):
            for kernel, problem in kernel_problems(cfg, 1, edge,
                                                   kind).items():
                cells[(kernel, tuple(sorted(problem.items())))] = problem
    for kernel, problem in kernel_problems(cfg, slots, max_len,
                                           "decode").items():
        cells[(kernel, tuple(sorted(problem.items())))] = problem
    return [(k, p) for (k, _), p in cells.items()]


def build(cfg, conf: dict, params, hardware):
    """(engine, recording scheduler) for one configuration."""
    import jax.numpy as jnp

    from repro import kernels
    from repro.core.plans import compile_plan
    from repro.serve import BucketPolicy, ServeEngine, make_scheduler

    kernels.register_all()
    serve = conf["serve"]
    dtype = conf["dtype"]
    edges = tuple(serve["bucket_edges"])
    cells = plan_cells(cfg, edges, serve["slots"], serve["max_len"])
    plan = compile_plan([(k, p, dtype, hardware) for k, p in cells],
                        meta={"generated_by": "chipbench",
                              "measure": "analytic"})
    if plan.meta["skipped_jobs"] or len(plan) != len(cells):
        raise RuntimeError(f"plan compiled {len(plan)} of {len(cells)} "
                           f"cells")
    policy = BucketPolicy(edges, max_queue=serve["max_queue"],
                          allow_overflow=True)
    scheduler = _Recording(make_scheduler("bucket", policy,
                                          pad_id=serve["pad_id"]))
    engine = ServeEngine(
        cfg, params, max_len=serve["max_len"], slots=serve["slots"],
        dtype=jnp.dtype(dtype), plans=plan, hardware=hardware,
        scheduler=scheduler, chunk_prefill=False,
        step_token_budget=serve["step_token_budget"],
        prefill_slots=serve["prefill_slots"], pack_prefill=True,
        paged=True, prefix_sharing=True, instance=hardware.name)
    return engine, scheduler


def busy(engine) -> bool:
    return bool(engine.in_flight() or engine.scheduler.pending())


def drain(engine, max_steps: int = 100_000) -> None:
    for _ in range(max_steps):
        if not busy(engine):
            return
        engine.step()
    raise RuntimeError("engine did not drain")


def admitted_lengths(engine, lo: int, hi: int) -> List[int]:
    """The padded prefill lengths prompts of ``lo..hi`` tokens admit at."""
    return sorted({engine.scheduler.admit_length(n)
                   for n in range(lo, hi + 1)})


def chunk_segments(engine, lengths) -> List[tuple]:
    """``(start, tokens)`` of every chunk that prompts of these admitted
    lengths prefill in (the engine's own chunk length for each)."""
    segments = set()
    for admit in lengths:
        chunk = engine._chunk_plan(admit)[0]
        segments |= {(start, min(chunk, admit - start))
                     for start in range(0, admit, chunk)}
    return sorted(segments)


def pack_layouts(engine, lengths) -> List[tuple]:
    """Every layout a packed step can give prompts of these admitted
    lengths: each ordered choice of 2 to ``prefill_slots`` of their chunks
    whose tokens fit the pack budget (``scheduler.pick_chunks`` adds a
    chunk only while it fits)."""
    segments = chunk_segments(engine, lengths)
    budget = engine._pack_budget()
    return [layout for k in range(2, engine.prefill_slots + 1)
            for layout in itertools.product(segments, repeat=k)
            if sum(take for _, take in layout) <= budget]


def warm_packs(engine, layouts) -> None:
    """Run the engine's packed program for each layout once, on pages it
    leaves as they were: the program returns a new pool, which is
    dropped. The arguments have the types and placement the engine gives
    them, so the window finds each program built."""
    import jax
    import jax.numpy as jnp

    from repro.models import api

    table = [0] * engine.pool.n_pt
    for layout in layouts:
        tokens = sum(take for _, take in layout)
        out = engine._pack_fn(layout)(
            engine.params,
            jnp.asarray(np.full((1, tokens), 2, np.int32)),
            tuple(api.make_paged_state(engine.cfg, engine.dtype)
                  for _ in layout),
            engine.pool.arrays,
            tuple(jnp.asarray(table, jnp.int32) for _ in layout))
        jax.block_until_ready(out)
        del out


def warm_up(engine, mix: dict, arrivals, vocab: int, seed: int) -> None:
    """Run the programs the cell's traffic reaches, then drain.

    1. One prompt per admitted length, alone: every chunk program of that
       length, and the decode program.
    2. Every packed layout the traffic's prompts can form
       (``pack_layouts``): which of them a window meets depends on the
       order of its arrivals, so all are built.
    3. A burst of the mix itself (2 x slots requests due at once, from a
       seed derived from the run's): the small programs a loaded engine
       runs around its steps.
    """
    from chipbench.generators.open_loop import length_range

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xAA]))
    lo, hi = length_range(mix["prompt_tokens"])
    lengths = admitted_lengths(engine, lo, hi)

    def prompt(admit: int) -> np.ndarray:
        return rng.integers(2, vocab, min(admit, hi), dtype=np.int32)

    for admit in lengths:
        engine.add_request(prompt(admit), max_new_tokens=2)
        drain(engine)
    warm_packs(engine, pack_layouts(engine, lengths))
    for a in arrivals[:2 * engine.slots]:
        engine.add_request(a.prompt, max_new_tokens=min(a.max_new_tokens, 4))
    drain(engine)


def _stamp(live: List[Served], now: float):
    """Record the tokens that appeared in the last step. Returns the
    requests still producing, and for each token decoded (every token but
    a request's first, which its prefill gives) the cache positions it
    attended over: the padded prompt plus the tokens before it."""
    still, ctx = [], []
    for s in live:
        old, total = len(s.token_times), len(s.req.out_tokens)
        s.token_times.extend([now] * (total - old))
        ctx.extend(s.req.bucket + k for k in range(max(old, 1), total))
        if not s.req.done:
            still.append(s)
    return still, tuple(ctx)


def open_loop(engine, recording, arrivals, seconds: float, annotate,
              on_tick=None):
    """Drive the window: submit each request when it falls due, step the
    engine while it has work, sleep until the next due time when it has
    none. Returns ``(served, steps, lateness, rejected, due)`` with times in
    seconds since the window opened. ``on_tick(now, steps)`` runs between
    steps, with the steps recorded so far (the traced run starts and stops
    the profiler there)."""
    served: List[Served] = []
    live: List[Served] = []
    steps: List[Step] = []
    lateness: List[float] = []
    rejected = 0
    progress: Dict[int, int] = {}       # rid -> prefill tokens done
    by_rid: Dict[int, Served] = {}
    i, n = 0, len(arrivals)
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if on_tick is not None:
            on_tick(now, steps)
        while i < n and arrivals[i].due_s <= now:
            a = arrivals[i]
            i += 1
            lateness.append(now - a.due_s)
            rid = engine.add_request(a.prompt,
                                     max_new_tokens=a.max_new_tokens)
            if rid is None:
                rejected += 1
                continue
            s = Served(rid, a.due_s, len(a.prompt), a.max_new_tokens,
                       req=recording.accepted[-1])
            served.append(s)
            live.append(s)
            by_rid[rid] = s
        if not busy(engine):
            wake = arrivals[i].due_s if i < n else seconds
            with annotate("generator.wait"):
                time.sleep(max(0.0, min(wake, seconds) - (clock() - t0)))
            continue
        start = clock() - t0
        with annotate("engine.step", step=len(steps)):
            engine.step()
        end = clock() - t0
        with annotate("tokens.bookkeeping"):
            stats = engine.last_step_stats
            segs = []
            for rid, (admit, take) in zip(stats["packed_rids"],
                                          stats["prefill_segments"]):
                done = progress.get(rid, 0)
                progress[rid] = done + take
                segs.append((admit, done, take,
                             admit - by_rid[rid].prompt_len))
            live, ctx = _stamp(live, end)
            steps.append(Step(start, end, stats["prefill_tokens"],
                              stats["decode_tokens"], tuple(segs), ctx))
    return served, steps, lateness, rejected, n

