"""Batched serving engine: slot-based continuous batching (lite).

Fixed decode batch of ``slots``; requests occupy free slots, prefill runs
per request (left-padded into the shared cache), decode advances all active
slots in one jitted step. Greedy sampling. This is the serving analogue of
the train loop — the decode step is the unit the decode_* dry-run shapes
lower.

Chunked prefill (``chunk_prefill=True``): instead of one monolithic prefill
per admitted request, the engine splits each prompt into plan-sized chunks
and builds **mixed steps** — one prefill chunk co-scheduled with the whole
pending decode batch under ``step_token_budget`` tokens per step. The chunk
length comes from the AOT plan's ``chunked_prefill`` cell for the admitted
bucket (VMEM bounds the resident chunk per hardware model, so different
models prefill the same prompt in different chunk sizes), clamped so chunk
+ decode batch always fits the budget. Up to ``prefill_slots`` requests
hold partially-built caches concurrently and the next chunk goes to the
most urgent one (priority, deadline, then fewest remaining tokens — so a
short prompt admitted behind a 32k prompt produces its first token after
one chunk-time, not after the whole 32k prefill). Chunk N's program closes
over its static start offset and replays the existing q_offset
continuation math in kernels/flash_attention, so chunked and whole-prompt
prefill match position by position (tests/test_serve_chunked.py).

Step packing (``pack_prefill=True``, implies chunked prefill): instead of
ONE chunk per mixed step, the engine packs MULTIPLE in-flight prefills'
chunks — segment-concatenated into a single kernel launch with per-segment
``q_offset``/``kv_pos`` masking (``api.prefill_packed``) — plus the decode
batch, under the same ``step_token_budget``. The pack is chosen by the
scheduler's knapsack (:func:`~repro.serve.scheduler.pick_chunks`): the
SRPT/aging head always runs (progress guarantee), then further whole
chunks greedily fill ``min(step budget - slots, pack width)``, where the
PACK WIDTH is the plan's ``packed_prefill`` tile — VMEM-bounded per
hardware model, so v5e and v6e pack different numbers of chunk tokens per
step for the same bucket set. Per request the math is unchanged (token
parity with one-chunk-per-step and unchunked service is pinned by
``tests/test_serve_packing.py``); only the schedule gets denser.

Paged KV pool (``paged=True``): per-request caches are replaced by ONE
engine-wide page pool (``repro.serve.pool.PagedKVPool``) — attention K/V
live in shared ``[n_pages, Hkv, page, D]`` arrays, each request holds a
page table, and pages are refcount-alloc'd as chunks are written / freed
at completion. The page size is the plan's ``kv_page`` cell (VMEM-bounded
per hardware model, like every other tile in this repo), admission is
pool-headroom reservation accounting instead of slot counting — so the
number of concurrently resident prefills is no longer capped at
``prefill_slots`` — and identical prompt prefixes prefill ONCE, with
copy-on-write splits at the first divergent write. Every prefill goes
through the chunk path (a whole prompt is one big chunk when chunking is
off), decode indirects reads/writes through the page table, and the token
stream is bit-identical with the per-request-cache engine
(tests/test_serve_paged.py pins this differentially per trace family).

Admission is delegated to a scheduler (``repro.serve.scheduler``): the
default :class:`~repro.serve.scheduler.FifoScheduler` preserves the naive
raw-shape behavior; a :class:`~repro.serve.scheduler.ShapeBucketScheduler`
pads prompts to the plan's shape family so every prefill lands on an
exactly-resolved plan cell (and a warm jit cache entry) instead of an
arbitrary shape that silently falls back to heuristics.

Tile selection: pass a compiled :class:`~repro.core.plans.TilePlan` (and the
target :class:`~repro.core.HardwareModel`) and the engine resolves every
decode-path kernel tile at construction time — exact hit, nearest shape, or
cross-hardware transfer — without ever invoking an autotuner sweep on the
request path. Prefill tiles are resolved per admitted shape (cached per
length) and threaded into the model's kernel call sites. Cells the plan
cannot resolve fall back to the zero-cost heuristic default tile, never to
a sweep. Every resolution is counted in ``self.metrics`` (plan hit /
transfer / fallback counters, TTFT/TPOT, queue depth).

Tracing: pass ``tracer=`` (a :class:`repro.obs.trace.Tracer`) and the
engine records the full causal timeline on its injected clock — request
lifecycle (submit → admit/reject → chunks with pack membership and queue
age → first token → decode → finish), per-step spans, and plan-resolution
audit instants. With no tracer (the default) every site short-circuits on
``self._trace is None``: zero allocations, zero calls. Independently of
the tracer, each step, admission, prefill launch and decode launch is a
``serve.*`` region of a ``jax.profiler`` trace (``repro.obs.trace.region``),
and every program the engine jits has a stable name (``serve_decode``,
``serve_chunk_paged``, ...), so a device trace shows which engine program
each device operation ran in.
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.hardware import PRODUCTION_TARGET, HardwareModel
from repro.core.plans import (PLAN_SCHEMA_VERSION, PlanResolution,
                              PlanTransferWarning, TilePlan, problem_key)
from repro.core.tiling import TileShape, cdiv
from repro.models import api
from repro.models import attention as attn_mod
from repro.obs.trace import region
from repro.serve.metrics import ServeMetrics
from repro.serve.pool import PagedKVPool
from repro.serve.scheduler import FifoScheduler


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 16
    priority: int = 0           # lower = more urgent
    deadline: float = math.inf  # absolute, scheduler-clock units
    bucket: Optional[int] = None  # padded length (set at submit)
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submit_t: Optional[float] = None  # original TTFT anchor (set on eviction)


@dataclasses.dataclass
class _ChunkJob:
    """One request's in-flight chunked prefill (chunk-resumable state)."""

    req: Request
    prompt: np.ndarray            # padded to the admitted length
    chunk_len: int
    state: Any = None             # serve caches, built chunk by chunk
    done: int = 0                 # prompt tokens prefilled so far
    chunks_run: int = 0
    packed_runs: int = 0          # chunks that rode a multi-segment pack
    last_t: float = 0.0           # last prefill progress (chunk queue age)
    # Trace-time tile events from every chunk program this request ran,
    # deduped once at prefill completion so an N-chunk prefill counts each
    # distinct fallback once — not N times (see _finish_prefill).
    events: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    @property
    def remaining(self) -> int:
        return len(self.prompt) - self.done

    def unpadded(self, take: int) -> int:
        """Prompt tokens among the next ``take`` this job prefills, the
        scheduler's left pads not counted."""
        pad = len(self.prompt) - len(self.req.prompt)
        return take - max(0, min(pad, self.done + take) - self.done)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, max_len: int = 512,
                 slots: int = 4, dtype=jnp.float32,
                 plans: Optional[TilePlan] = None,
                 hardware: Optional[HardwareModel] = None,
                 scheduler=None,
                 metrics: Optional[ServeMetrics] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 chunk_prefill: bool = False,
                 step_token_budget: int = 0,
                 prefill_slots: int = 2,
                 pack_prefill: bool = False,
                 shadow_fraction: float = 0.0,
                 shadow_measure=None,
                 refiner=None,
                 tracer=None,
                 instance: Optional[str] = None,
                 paged: bool = False,
                 pool_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 prefix_sharing: bool = True):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = slots
        self.dtype = dtype
        self.hardware = hardware or PRODUCTION_TARGET
        self.plans = plans
        self.scheduler = scheduler or FifoScheduler()
        self.metrics = metrics or ServeMetrics(clock=clock)
        self._clock = clock
        # Request-lifecycle / plan-audit tracing (repro.obs.trace). None by
        # default and every call site is guarded with
        # ``if self._trace is not None`` — disabled tracing adds zero
        # object construction and zero calls on the step hot path.
        self._trace = None
        self._plan_schema: Optional[int] = None
        if tracer is not None:
            self._trace = tracer.attach(instance or "engine", kind="engine",
                                        hardware=self.hardware.name)
            bind = getattr(self.scheduler, "bind_trace", None)
            if bind is not None:
                bind(self._trace)
        # Chunked-prefill configuration. ``step_token_budget`` bounds one
        # mixed step's tokens (decode batch + one prefill chunk); 0 = no
        # bound, the plan's chunk length runs unclamped. ``prefill_slots``
        # bounds how many partially-prefilled caches are held at once (the
        # concurrency that lets a short prompt overtake a long one).
        # ``pack_prefill`` packs several chunks per step (implies chunking).
        self.pack_prefill = pack_prefill
        # Paged mode reuses the chunk-program machinery for every prefill
        # (a whole prompt is one big chunk when chunking is off — see
        # _chunk_plan), so the paged engine has ONE prefill path to keep
        # token-identical with the per-request-cache engine.
        self.paged = paged
        self._paged_whole = paged and not (chunk_prefill or pack_prefill)
        self.chunk_prefill = chunk_prefill or pack_prefill or paged
        self.step_token_budget = step_token_budget
        self.prefill_slots = max(1, prefill_slots)
        self._chunking: List[_ChunkJob] = []
        # Paged admission: requests the pool cannot reserve pages for yet
        # (FIFO — the head gets first claim on freed pages).
        self._pool_wait: List[Any] = []
        # rid -> next cache write position for pool-backed decodes.
        self._pos: Dict[int, int] = {}
        self._ready: List[Any] = []   # (Request, state) done prefilling,
        #                               waiting for a free decode slot
        self._held: List[Request] = []  # multi-chunk requests deferred while
        #                                 another multi-chunk prefill runs
        #                                 (FIFO schedulers only; see
        #                                 _next_admission)
        self._single_chunk_edge: Optional[int] = None  # lazy, per engine
        self._chunk_ticks = 0  # aging counter for _next_chunk_job
        self._chunk_plans: Dict[int, Any] = {}      # admit_len -> plan tuple
        self._chunk_fns: Dict[Any, Any] = {}        # (admit_len, start) -> fn
        self._chunk_tile_events: Dict[Any, List[Dict[str, Any]]] = {}
        # Step packing: the plan-resolved pack width + tiles (lazy, per
        # engine), one jitted packed program per static segment layout.
        # Unlike _chunk_fns (whose (admit_len, start) key space is linear
        # in buckets x chunks), layouts are cross-products of per-segment
        # offsets — the cache is LRU-bounded so a long-running server
        # cannot accrete compiled programs without limit.
        self._pack_plan_cache: Optional[Any] = None
        self._pack_fns: Dict[Any, Any] = {}         # layout -> fn
        self._pack_tile_events: Dict[Any, List[Dict[str, Any]]] = {}
        # Per-step mixed-token accounting (virtual-clock drivers read this).
        # ``packed_chunks``/``packed_rids`` describe the step's prefill pack
        # (conformance tests and the bench histogram read them).
        self.last_step_stats: Dict[str, Any] = {"prefill_tokens": 0,
                                                "decode_tokens": 0,
                                                "packed_chunks": 0,
                                                "packed_rids": (),
                                                "prefill_segments": ()}
        # Shadow execution (repro.serve.refine): divert a deterministic
        # fraction of steps to measuring one candidate tile from the plan's
        # sensitivity curve next to the incumbent. Counter-based sampling
        # (fractional accumulator), so tests and CI see the exact same
        # shadow schedule every run — no wall-clock randomness. Shadowing
        # is measurement-only: it never touches the serving math.
        self.shadow_fraction = float(shadow_fraction)
        if not 0.0 <= self.shadow_fraction <= 1.0:
            raise ValueError(
                f"shadow_fraction must be in [0, 1]: {shadow_fraction}")
        self.refiner = refiner
        self._shadow_measure = shadow_measure
        self._shadow_acc = 0.0
        self._shadow_rr = 0                       # round-robin cell cursor
        self._shadow_idx: Dict[str, int] = {}     # cell -> candidate cursor
        # cell key -> (kernel, problem): every plan cell this engine has
        # resolved so far — the shadow candidates' universe.
        self._shadow_cell_map: Dict[str, Any] = {}
        self._shadow_order: List[str] = []
        # cell key -> (incumbent dims, candidate dims tuple) | None.
        self._shadow_views: Dict[str, Any] = {}
        self.steps_run = 0
        # kernel name -> resolved tile for the decode path; populated from
        # the AOT plan at init so serving never pays a sweep.
        self.tiles: Dict[str, TileShape] = {}
        self.tile_resolutions: Dict[str, PlanResolution] = {}
        if plans is not None:
            self._resolve_tiles(plans)
        self._active: List[Optional[Request]] = [None] * slots
        self._finished: List[Request] = []
        self._next_rid = 0
        # Why the most recent add_request returned None ("ok" = it didn't);
        # the fleet router's failover path reads this after a rejection.
        self.last_reject_reason = "ok"

        # Per-slot independent caches (batch=1) batched by stacking.
        self._states = [None] * slots

        # Paged KV pool: page geometry comes from the plan's ``kv_page``
        # cell (VMEM-bounded per hardware model — v5e and v6e resolve
        # different page sizes for the same cache length), overridable with
        # ``page_size``. Default capacity matches what the per-request
        # engine would reserve for every decode + prefill slot, plus the
        # pool's copy-on-write slack — so paged mode never fits FEWER
        # requests, and fits many more whenever prompts only partially
        # fill their reservations.
        self.pool: Optional[PagedKVPool] = None
        if paged:
            kv_tile = self.tiles.get("kv_page")
            page = int(page_size if page_size is not None
                       else kv_tile[0] if kv_tile is not None
                       else min(512, max_len))
            n_pages = pool_pages if pool_pages is not None else (
                (slots + self.prefill_slots)
                * (cdiv(max_len, page) + PagedKVPool.RESERVE_SLACK))
            self.pool = PagedKVPool(
                cfg, n_pages=n_pages, page=page, max_len=max_len,
                dtype=dtype, prefix_sharing=prefix_sharing,
                metrics=self.metrics, trace=self._trace)

        self._build_decode_programs()
        # Prefill programs are built per admitted length so each shape
        # family gets its own exactly-resolved tiles (see _prefill_fn).
        self._prefill_fns: Dict[int, Any] = {}
        self._prefill_sources: Dict[int, Dict[str, str]] = {}
        # Tile-dispatch events fire once per jit trace; cache them per
        # length and replay per admitted request so tile_fallback counts in
        # the same unit as the per-request plan-source counters above. The
        # decode program's (deduped) events record once per engine — the
        # same unit as its per-engine plan-source counts from
        # ``_resolve_tiles``. None = decode not yet traced.
        self._prefill_tile_events: Dict[int, List[Dict[str, Any]]] = {}
        self._decode_tile_events: Optional[List[Dict[str, Any]]] = None

    @staticmethod
    def _dedupe_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        """Drop retrace duplicates (eval_shape / checkpoint passes and
        identical per-layer call sites re-emit the same event)."""
        seen, out = set(), []
        for ev in events:
            key = tuple(sorted((k, str(v)) for k, v in ev.items()))
            if key not in seen:
                seen.add(key)
                out.append(ev)
        return out

    def _record_tile_event(self, event: Dict[str, Any]) -> None:
        """Trace-time tile-dispatch events -> plan counters.

        A ``fallback`` event means a resolved plan tile did NOT legally
        apply at the call site (clamped to a non-dividing block, or a
        Pallas-eligible tile degraded to the reference lowering); counting
        it as ``tile_fallback`` makes ``plan_hit_rate`` reflect the tiles
        the compiled programs actually consumed, not just the plan-store
        lookups.
        """
        if event.get("fallback"):
            self.metrics.record_plan(event["phase"], event["kernel"],
                                     "tile_fallback")

    def _resolve_tiles(self, plans: TilePlan) -> None:
        """Resolve decode-path kernel tiles from the plan store. No sweeps."""
        from repro.launch.specs import kernel_problems, resolve_model_tiles

        self._plan_schema = int(plans.meta.get(
            "schema_version", PLAN_SCHEMA_VERSION))
        self.tiles, self.tile_resolutions = resolve_model_tiles(
            plans, self.cfg, self.slots, self.max_len, "decode",
            jnp.dtype(self.dtype).name, self.hardware)
        problems = kernel_problems(self.cfg, self.slots, self.max_len,
                                   "decode")
        for kernel in self.tiles:
            res = self.tile_resolutions.get(kernel)
            source = res.source if res else "fallback"
            self.metrics.record_plan("decode", kernel, source)
            if self._trace is not None:
                self._trace.plan_resolve(
                    "decode", kernel, problem_key(problems.get(kernel, {})),
                    tuple(self.tiles[kernel].dims), source,
                    self._plan_schema)
        self._note_shadow_cells(problems)

    def _trace_plan_table(self, phase: str, tiles, sources, problems) -> None:
        """Emit one ``plan_resolve`` audit instant per kernel: which tile
        each launch resolved to, from which source, under which artifact
        schema. Call sites fire once per resolution (per length / geometry),
        mirroring when the plan store was actually consulted."""
        for kernel in sorted(sources):
            tile = tiles.get(kernel)
            self._trace.plan_resolve(
                phase, kernel, problem_key(problems.get(kernel) or {}),
                tuple(tile.dims) if tile is not None else (),
                sources[kernel], self._plan_schema)

    # -- live plan refinement ------------------------------------------------
    def _note_shadow_cells(self, problems: Dict[str, Dict[str, int]]) -> None:
        """Register plan cells this engine resolved as shadow targets."""
        from repro.core.plans import problem_key

        for kernel, problem in problems.items():
            key = f"{kernel}|{problem_key(problem)}"
            if key not in self._shadow_cell_map:
                self._shadow_cell_map[key] = (kernel, dict(problem))
                self._shadow_order.append(key)

    def _shadow_view(self, key: str):
        """(incumbent dims, candidate dims tuple) for one cell, or None.

        The incumbent is the plan-resolved serving tile; the candidates are
        every other tile on the resolved entry's stored sensitivity curve —
        the ranking the paper says cannot be trusted once hardware or
        conditions change, which is exactly why shadow steps re-measure it.
        """
        if key in self._shadow_views:
            return self._shadow_views[key]
        kernel, problem = self._shadow_cell_map[key]
        view = None
        if self.plans is not None:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", PlanTransferWarning)
                res = self.plans.resolve(kernel, problem,
                                         jnp.dtype(self.dtype).name,
                                         self.hardware)
            if res is not None:
                inc = tuple(int(x) for x in res.tile.dims)
                cands, seen = [], {inc}
                for dims, _score in res.entry.curve:
                    dims = tuple(int(x) for x in dims)
                    if dims not in seen:
                        seen.add(dims)
                        cands.append(dims)
                if cands:
                    view = (inc, tuple(cands))
        self._shadow_views[key] = view
        return view

    def _shadow_measure_fn(self):
        if self._shadow_measure is None:
            from repro.serve.refine import make_shadow_measure

            self._shadow_measure = make_shadow_measure(self.hardware)
        return self._shadow_measure

    def _maybe_shadow(self) -> None:
        """Divert this step to shadow measurement when the deterministic
        fractional accumulator crosses 1: measure ONE candidate tile (and
        the incumbent, for a like-for-like baseline) for the next cell in
        round-robin order, record both, and feed the refiner. Serving state
        is untouched — tokens are identical with shadowing on or off."""
        if not self.shadow_fraction or self.plans is None:
            return
        self._shadow_acc += self.shadow_fraction
        if self._shadow_acc < 1.0:
            return
        self._shadow_acc -= 1.0
        if not self._shadow_order:
            return
        measure = self._shadow_measure_fn()
        dtype = jnp.dtype(self.dtype).name
        for _ in range(len(self._shadow_order)):
            key = self._shadow_order[self._shadow_rr
                                     % len(self._shadow_order)]
            self._shadow_rr += 1
            view = self._shadow_view(key)
            if view is None:
                continue
            inc, cands = view
            kernel, problem = self._shadow_cell_map[key]
            idx = self._shadow_idx.get(key, 0)
            self._shadow_idx[key] = idx + 1
            cand = cands[idx % len(cands)]
            dt_inc = float(measure(kernel, problem, dtype, inc))
            dt_cand = float(measure(kernel, problem, dtype, cand))
            self.metrics.record_shadow(kernel, inc, dt_inc, incumbent=True)
            self.metrics.record_shadow(kernel, cand, dt_cand)
            if self._trace is not None:
                self._trace.shadow(kernel, problem_key(problem), inc, cand,
                                   dt_inc, dt_cand)
            if self.refiner is not None:
                self.refiner.observe(kernel, problem, dtype,
                                     self.hardware.name, inc, dt_inc,
                                     incumbent=True)
                self.refiner.observe(kernel, problem, dtype,
                                     self.hardware.name, cand, dt_cand)
            self.metrics.record_shadow_step()
            return

    def set_plans(self, plans: Optional[TilePlan]) -> None:
        """Swap this engine onto a (refined) plan artifact, live.

        Every plan-derived cache is dropped — prefill/chunk/pack programs,
        chunk plans, tile events, shadow views — and the decode program is
        REBUILT (jax.jit caches the traced graph, so a closure over the old
        tiles would keep serving them). In-flight requests keep their
        states and chunk progress: tiles never change the math (the repo's
        pinned invariant), so a mid-prefill swap is token-transparent.
        """
        self.plans = plans
        self._prefill_fns.clear()
        self._prefill_sources.clear()
        self._prefill_tile_events.clear()
        self._chunk_plans.clear()
        self._chunk_fns.clear()
        self._chunk_tile_events.clear()
        self._pack_plan_cache = None
        self._pack_fns.clear()
        self._pack_tile_events.clear()
        self._single_chunk_edge = None
        self._decode_tile_events = None
        self._shadow_views.clear()
        self.tiles, self.tile_resolutions = {}, {}
        self._plan_schema = None
        if plans is not None:
            self._resolve_tiles(plans)
        self._build_decode_programs()
        if self._trace is not None:
            refined_from = (plans.meta.get("refined_from")
                            if plans is not None else None)
            self._trace.plan_swap(self._plan_schema, refined_from)

    def _build_decode_programs(self) -> None:
        """(Re)build the decode programs, which read ``self.tiles`` when
        they trace."""
        cfg = self.cfg

        def serve_decode(p, tok, st):
            return api.decode_step(p, cfg, tok, st, tiles=self.tiles or None)

        def serve_decode_paged(p, tok, st, arrays, table):
            return api.decode_step_paged(p, cfg, tok, st, arrays, table,
                                         tiles=self.tiles or None)

        self._decode = jax.jit(serve_decode)
        # The pool (argument 3) is donated: the program updates it in place
        # and the caller stores the returned arrays back into the pool.
        self._decode_paged = jax.jit(serve_decode_paged, donate_argnums=3)

    def _prefill_fn(self, length: int):
        """The jitted prefill program for one admitted prompt length.

        Resolves the (batch=1, seq=length) prefill cell's kernel tiles from
        the plan (cached per length) and closes over them, so a bucketed
        shape family compiles once per bucket with the plan's exact tiles.
        """
        fn = self._prefill_fns.get(length)
        if fn is not None:
            return fn
        tiles: Dict[str, TileShape] = {}
        sources: Dict[str, str] = {}
        if self.plans is not None:
            from repro.launch.specs import resolve_model_tiles

            with warnings.catch_warnings():
                # Transfer warnings already fire once at plan resolution
                # inside resolve; accounting below records them as counters.
                warnings.simplefilter("ignore", PlanTransferWarning)
                tiles, resolutions = resolve_model_tiles(
                    self.plans, self.cfg, 1, length, "prefill",
                    jnp.dtype(self.dtype).name, self.hardware)
            sources = {
                kernel: (resolutions[kernel].source
                         if kernel in resolutions else "fallback")
                for kernel in tiles
            }
        else:
            from repro.launch.specs import kernel_problems

            sources = {
                kernel: "no_plan"
                for kernel in kernel_problems(self.cfg, 1, length, "prefill")
            }
        cfg, max_len, dtype = self.cfg, self.max_len, self.dtype

        def serve_prefill(p, batch):
            return api.prefill(p, cfg, batch, max_len=max_len, dtype=dtype,
                               ring_local=bool(cfg.attn_window),
                               tiles=tiles or None)

        fn = jax.jit(serve_prefill)
        self._prefill_fns[length] = fn
        self._prefill_sources[length] = sources
        if self._trace is not None:
            from repro.launch.specs import kernel_problems

            self._trace_plan_table(
                "prefill", tiles, sources,
                kernel_problems(self.cfg, 1, length, "prefill"))
        if self.plans is not None:
            from repro.launch.specs import kernel_problems

            self._note_shadow_cells(
                kernel_problems(self.cfg, 1, length, "prefill"))
        return fn

    # -- chunked prefill -----------------------------------------------------
    def _resolve_serve_cell(self, kind: str, seq_len: int):
        """Resolve one serving attention cell (``chunked_prefill`` or
        ``packed_prefill``) from the plan store at one geometry; falls back
        to the kernel's heuristic default tile, never a sweep. Returns
        ``(problem | None, tile | None, source)`` — problem is None for
        attention-free models (the cell never runs). ONE implementation for
        both cell kinds so chunked and packed plan accounting cannot
        drift."""
        from repro import kernels as kernel_pkg
        from repro.core import registry
        from repro.launch.specs import kernel_problems

        kernel_pkg.register_all()
        dtype = jnp.dtype(self.dtype).name
        problem = kernel_problems(self.cfg, 1, seq_len, kind).get(kind)
        tile, source = None, "no_plan"
        if problem is not None:
            if self.plans is not None:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", PlanTransferWarning)
                    res = self.plans.resolve(kind, problem, dtype,
                                             self.hardware)
                if res is not None:
                    tile, source = res.tile, res.source
                else:
                    source = "fallback"
            if tile is None:
                tile = registry.get(kind).default_tile(problem, dtype)
        return problem, tile, source

    def _model_tiles_for(self, seq_len: int):
        """The surrounding (FF/recurrent) prefill kernel tiles at one
        geometry, with their plan sources. The whole-sequence
        flash_attention cell is dropped: chunk/pack programs consume the
        chunked_prefill/packed_prefill cells instead, and plan counters
        must reflect the cells the programs actually run."""
        from repro.launch.specs import kernel_problems, resolve_model_tiles

        dtype = jnp.dtype(self.dtype).name
        tiles: Dict[str, TileShape] = {}
        sources: Dict[str, str] = {}
        if self.plans is not None:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", PlanTransferWarning)
                tiles, resolutions = resolve_model_tiles(
                    self.plans, self.cfg, 1, seq_len, "prefill", dtype,
                    self.hardware)
            tiles.pop("flash_attention", None)
            sources = {
                kernel: (resolutions[kernel].source
                         if kernel in resolutions else "fallback")
                for kernel in tiles
            }
        else:
            sources = {
                kernel: "no_plan"
                for kernel in kernel_problems(self.cfg, 1, seq_len,
                                              "prefill")
                if kernel != "flash_attention"
            }
        return tiles, sources

    def _chunk_plan(self, admit_len: int):
        """(chunk_len, tiles, sources) for prefilling one admitted length.

        The chunk length is the plan-resolved ``chunked_prefill`` tile's
        first dim — chosen per hardware model, so the same prompt prefills
        in different chunk sizes on different models — clamped so one chunk
        plus a full decode batch fits ``step_token_budget``. The remaining
        (FF/recurrent) kernel tiles are resolved at the chunk geometry,
        which is the shape the chunk programs actually run.
        """
        hit = self._chunk_plans.get(admit_len)
        if hit is not None:
            return hit
        problem, tile, source = self._resolve_serve_cell(
            "chunked_prefill", admit_len)
        chunk = int(tile[0]) if tile is not None else min(512, admit_len)
        if self.step_token_budget:
            # A mixed step must fit one chunk + the whole decode batch.
            chunk = min(chunk, max(1, self.step_token_budget - self.slots))
        chunk = max(1, min(chunk, admit_len))
        if self._paged_whole:
            # Paged without explicit chunking: the whole prompt is ONE
            # chunk, so the paged engine reproduces the monolithic-prefill
            # schedule exactly (single program per admitted length).
            chunk = admit_len

        tiles, sources = self._model_tiles_for(chunk)
        if tile is not None:
            tiles["chunked_prefill"] = tile
        if problem is not None:
            # Attention-free models have no chunked_prefill cell — don't
            # tick a phantom plan counter for a kernel that never runs.
            sources["chunked_prefill"] = source
        entry = (chunk, tiles, sources)
        self._chunk_plans[admit_len] = entry
        if self._trace is not None:
            from repro.launch.specs import kernel_problems

            probs = dict(kernel_problems(self.cfg, 1, chunk, "prefill"))
            if problem is not None:
                probs["chunked_prefill"] = problem
            self._trace_plan_table("prefill", tiles, sources, probs)
        if self.plans is not None:
            from repro.launch.specs import kernel_problems

            cells = {k: v for k, v in kernel_problems(
                self.cfg, 1, chunk, "prefill").items()
                if k != "flash_attention"}
            if problem is not None:
                cells["chunked_prefill"] = problem
            self._note_shadow_cells(cells)
        return entry

    def chunk_len_for(self, admit_len: int) -> int:
        """Chunk length one admitted prompt prefills in (= admit_len when
        chunking is off — the whole prefill is one quantum)."""
        if not self.chunk_prefill:
            return admit_len
        return self._chunk_plan(admit_len)[0]

    def _chunk_fn(self, admit_len: int, start: int):
        """The jitted program for one (admitted length, chunk offset) pair.

        ``start`` is closed over statically: the causal q_offset arithmetic
        and the cache-prefix slice stay compile-time constants, so a chunk
        reads only the KV actually written — at the cost of one program per
        chunk offset (bounded by admit_len / chunk_len per bucket).
        """
        key = (admit_len, start)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        _, tiles, _ = self._chunk_plan(admit_len)
        cfg = self.cfg

        def serve_chunk_paged(p, toks, st, arrays, table):
            return api.prefill_chunk_paged(p, cfg, toks, st, start, arrays,
                                           table, tiles=tiles or None)

        def serve_chunk(p, toks, st):
            return api.prefill_chunk(p, cfg, toks, st, start,
                                     tiles=tiles or None)

        # Like decode, the paged chunk program donates the pool.
        fn = (jax.jit(serve_chunk_paged, donate_argnums=3) if self.paged
              else jax.jit(serve_chunk))
        self._chunk_fns[key] = fn
        return fn

    # -- step packing --------------------------------------------------------
    def _pack_plan(self):
        """(pack width, tiles, source) for packed multi-chunk steps.

        The pack width — how many prefill-chunk tokens one packed step may
        carry — is the plan-resolved ``packed_prefill`` tile's first dim,
        chosen per hardware model (VMEM bounds the resident pack, so v5e
        and v6e pack different widths for the same bucket set). The cell is
        resolved at the single-chunk bucket bound: the segment class step
        packing exists for is the short prompts that fit one chunk. The
        remaining (FF/recurrent) tiles are resolved at the pack geometry —
        the token count the packed programs actually run.
        """
        if self._pack_plan_cache is not None:
            return self._pack_plan_cache
        policy = getattr(self.scheduler, "policy", None)
        edge = self._single_chunk_bound() or (
            min(policy.edges) if policy is not None else 512)
        problem, tile, source = self._resolve_serve_cell(
            "packed_prefill", edge)
        width = int(tile[0]) if tile is not None else max(512, edge)
        tiles, _ = self._model_tiles_for(min(width, self.max_len))
        if tile is not None:
            tiles["packed_prefill"] = tile
        self._pack_plan_cache = (width, tiles, source)
        if self._trace is not None and problem is not None:
            self._trace_plan_table(
                "prefill", tiles, {"packed_prefill": source},
                {"packed_prefill": problem})
        if self.plans is not None and problem is not None:
            self._note_shadow_cells({"packed_prefill": problem})
        return self._pack_plan_cache

    def _pack_budget(self) -> float:
        """Max prefill-chunk tokens one packed step may carry: the plan's
        pack width, clamped so pack + decode batch fits the step budget."""
        width, _, _ = self._pack_plan()
        if self.step_token_budget:
            return min(width, max(1, self.step_token_budget - self.slots))
        return width

    # Bound on cached packed programs (and their tile events): beyond it
    # the least-recently-USED layout is evicted and would retrace if seen
    # again. Eviction must be LRU, not FIFO: a hot layout (a steady-state
    # pack shape hit every few steps) is also one of the OLDEST insertions,
    # so insertion-order eviction retraces exactly the programs a
    # long-running server needs most (tests/test_serve_paged.py pins a hot
    # layout surviving cap-many cold ones).
    PACK_FN_CACHE_CAP = 256

    def _pack_fn(self, layout):
        """The jitted packed program for one static segment layout
        (tuple of per-segment (start, len) pairs — the packed analogue of
        the per-(admit_len, start) chunk programs)."""
        fn = self._pack_fns.pop(layout, None)
        if fn is not None:
            # Re-insert at the end: recency, not insertion order, decides
            # eviction.
            self._pack_fns[layout] = fn
            return fn
        while len(self._pack_fns) >= self.PACK_FN_CACHE_CAP:
            oldest = next(iter(self._pack_fns))
            del self._pack_fns[oldest]
            self._pack_tile_events.pop(oldest, None)
        _, tiles, _ = self._pack_plan()
        cfg = self.cfg

        def serve_pack_paged(p, toks, sts, arrays, tbls):
            return api.prefill_packed_paged(p, cfg, toks, sts, layout, arrays,
                                            tbls, tiles=tiles or None)

        def serve_pack(p, toks, sts):
            return api.prefill_packed(p, cfg, toks, sts, layout,
                                      tiles=tiles or None)

        # Unlike decode and chunk, the paged packed program does not donate
        # the pool: a warm-up may run it on the engine's live pool and drop
        # the result, which must leave that pool intact. So the program
        # returns a fresh copy of the pool.
        fn = jax.jit(serve_pack_paged if self.paged else serve_pack)
        self._pack_fns[layout] = fn
        return fn

    def _ensure_state(self, job: _ChunkJob) -> None:
        if job.state is None:
            if self.paged:
                # Attention K/V live in the shared pool; the per-request
                # state carries only scalar positions (+ recurrent/SSD
                # carried state for hybrids).
                job.state = api.make_paged_state(self.cfg, self.dtype)
            else:
                job.state = api.make_serve_state(
                    self.cfg, 1, self.max_len, self.dtype,
                    ring_local=bool(self.cfg.attn_window))

    def _advance_job(self, job: _ChunkJob, take: int, events, logits,
                     packed: bool = False, pack_n: int = 1, lane: int = 0,
                     t0: Optional[float] = None) -> None:
        """Per-chunk bookkeeping shared by the one-chunk and packed paths:
        tile events accrue, chunk telemetry ticks, progress advances, and a
        completed prefill leaves the chunking set. One implementation on
        purpose — packed and one-chunk accounting must never drift (the
        conformance suite pins their observable equality)."""
        job.events.extend(events)
        now = self._clock()
        age = now - job.last_t
        self.metrics.record_chunk(job.req.bucket, age)
        if self._trace is not None:
            self._trace.chunk(job.req.rid, lane, now if t0 is None else t0,
                              job.done, take, pack_n, age)
        job.last_t = now
        job.done += take
        job.chunks_run += 1
        job.packed_runs += packed
        if job.done >= len(job.prompt):
            self._chunking.remove(job)
            self._finish_prefill(job, logits)

    def _run_pack(self, picks) -> int:
        """Advance every picked job by one chunk in ONE packed launch;
        returns the pack's total token count."""
        jobs = [job for job, _ in picks]
        layout = tuple((job.done, take) for job, take in picks)
        with region("prefill", program="pack", segments=len(picks),
                    tokens=sum(job.unpadded(take) for job, take in picks)):
            t0 = self._clock() if self._trace is not None else None
            for job in jobs:
                self._ensure_state(job)
            toks = jnp.asarray(np.concatenate([
                job.prompt[start:start + take]
                for job, (start, take) in zip(jobs, layout)
            ])[None])
            fn = self._pack_fn(layout)
            states = tuple(job.state for job in jobs)
            events = self._pack_tile_events.get(layout)
            if self.paged:
                for job, (start, take) in zip(jobs, layout):
                    self.pool.prepare_span(job.req.rid, start, take)
                tables = tuple(self.pool.device_table(job.req.rid)
                               for job in jobs)
                # The packed program returns a new pool (it does not donate
                # the old one). Wait for the pool's last writer first, so
                # that one copy at most is in flight: back-to-back packs
                # otherwise hold three pools at once.
                jax.block_until_ready(self.pool.arrays)
                args = (self.params, toks, states, self.pool.arrays, tables)
                if events is None:
                    captured: List[Dict[str, Any]] = []
                    with attn_mod.capture_tile_events(captured.append):
                        logits, new_states, self.pool.arrays = fn(*args)
                    events = self._dedupe_events(captured)
                    self._pack_tile_events[layout] = events
                else:
                    logits, new_states, self.pool.arrays = fn(*args)
            elif events is None:
                captured = []
                with attn_mod.capture_tile_events(captured.append):
                    logits, new_states = fn(self.params, toks, states)
                events = self._dedupe_events(captured)
                self._pack_tile_events[layout] = events
            else:
                logits, new_states = fn(self.params, toks, states)
            for i, (job, (start, take)) in enumerate(zip(jobs, layout)):
                job.state = new_states[i]
                self._advance_job(job, take, events, logits[i][None],
                                  packed=True, pack_n=len(jobs), lane=i, t0=t0)
        return sum(take for _, take in layout)

    def _is_multi_chunk(self, req: Request) -> bool:
        """Will this request's prefill span more than one chunk?"""
        admit_len = req.bucket if req.bucket is not None else len(req.prompt)
        return admit_len > self._chunk_plan(admit_len)[0]

    def _single_chunk_bound(self) -> int:
        """Largest bucket edge whose prefill fits one chunk (0 if none)."""
        if self._single_chunk_edge is None:
            policy = getattr(self.scheduler, "policy", None)
            edges = policy.edges if policy is not None else ()
            self._single_chunk_edge = max(
                (e for e in edges if self._chunk_plan(e)[0] >= e), default=0)
        return self._single_chunk_edge

    def _next_admission(self, long_ok: bool) -> Optional[Request]:
        """Next request to start prefilling.

        With ``long_ok=False`` only single-chunk requests qualify. Bucketed
        schedulers support a filtered pop (``next_request_within``), so
        queued long prompts stay in the scheduler — visible to ``max_queue``
        admission control and the queue-depth metric — while small buckets
        behind them stay reachable no matter how many longs are queued.
        FIFO schedulers cannot pop selectively; deferred longs go to a
        holding pen capped at ``prefill_slots`` entries (beyond the cap the
        engine simply waits for the in-flight long, preserving FIFO order).
        """
        for i, req in enumerate(self._held):
            if long_ok or not self._is_multi_chunk(req):
                return self._held.pop(i)
        within = getattr(self.scheduler, "next_request_within", None)
        if not long_ok and within is not None:
            return within(self._single_chunk_bound())
        while len(self._held) < self.prefill_slots:
            req = self.scheduler.next_request()
            if req is None:
                return None
            if long_ok or not self._is_multi_chunk(req):
                return req
            self._held.append(req)
        return None

    def _admit_chunked(self) -> None:
        """Move ready prefills into decode slots and queued requests into
        free prefill slots (chunk concurrency).

        At most ONE multi-chunk prefill runs at a time: a stream of long
        prompts must not occupy every prefill slot and starve short ones —
        the head-of-line blocking chunking exists to cut. Deferred longs
        keep their order and start as soon as the running one finishes.
        Paged mode lifts the one-long rule: longs cannot starve shorts by
        occupying slots (the pool gate, not ``prefill_slots``, bounds the
        resident set, and the SRPT pack rule still serves shorts first),
        so many partial long prefills accumulate pages concurrently.
        """
        free = [i for i, r in enumerate(self._active) if r is None]
        while free and self._ready:
            req, state = self._ready.pop(0)
            i = free.pop(0)
            self._active[i] = req
            self._states[i] = state
        # Backpressure: a completed prefill holds a full KV cache until a
        # decode slot frees. Once _ready already covers every decode slot,
        # admitting more prefills would only stack further caches (the
        # unchunked engine never holds more than ``slots`` live states) —
        # stall admission until decode catches up. Live states stay
        # bounded: decode slots + in-flight chunking + ready <=
        # 2*slots + 2*prefill_slots.
        if len(self._ready) >= self.slots:
            return
        long_in_flight = any(len(j.prompt) > j.chunk_len
                             for j in self._chunking)
        # Paged mode admits PAST ``prefill_slots``: the pool's reservation
        # accounting (PagedKVPool.can_admit) is the real resident-set gate
        # — a request holds only the pages it has written, so many partial
        # prefills coexist where whole-cache slots fit few. The count cap
        # is only a retrace/bookkeeping safety bound.
        cap = (8 * (self.slots + self.prefill_slots) if self.paged
               else self.prefill_slots)
        while len(self._chunking) < cap:
            req = None
            if self.paged and self._pool_wait:
                # Pool-starved requests hold a FIFO claim on freed pages:
                # the head admits first or nobody does (no overtaking).
                if not self.pool.can_admit(
                        self._pool_estimate(self._pool_wait[0])):
                    break
                req = self._pool_wait.pop(0)
            if req is None:
                req = self._next_admission(
                    long_ok=self.paged or not long_in_flight)
            if req is None:
                break
            if self.paged and not self.pool.can_admit(
                    self._pool_estimate(req)):
                self._pool_wait.append(req)
                break
            prompt = np.asarray(self.scheduler.prepare(req), np.int32)
            chunk_len, _, _ = self._chunk_plan(len(prompt))
            long_in_flight = long_in_flight or len(prompt) > chunk_len
            submit_t = self.metrics.submit_time(req.rid)
            if self._trace is not None:
                now = self._clock()
                self._trace.admit(
                    req.rid, len(prompt),
                    now - submit_t if submit_t is not None else 0.0)
            hit = 0
            if self.paged:
                self.pool.register_request(
                    req.rid, len(prompt) + req.max_new_tokens - 1)
                # A shared-prefix hit maps already-prefilled pages and the
                # job starts its chunks at the divergence point.
                hit = self.pool.lookup_prefix(req.rid, prompt.tolist())
            self._chunking.append(_ChunkJob(
                req=req, prompt=prompt, chunk_len=chunk_len, done=hit,
                last_t=submit_t if submit_t is not None else self._clock()))

    def _pool_estimate(self, req: Request) -> int:
        """Worst-case cache positions a request will write (for the pool
        admission gate): padded prompt + generation minus the never-cached
        final sampled token."""
        admit_len = req.bucket if req.bucket is not None else len(req.prompt)
        return admit_len + req.max_new_tokens - 1

    # Every AGING_PERIOD-th chunk goes to the OLDEST in-flight prefill
    # instead of the shortest-remaining one: a sustained stream of short
    # prompts can otherwise starve a long prefill forever (its `remaining`
    # never shrinks because it never runs). 1/AGING_PERIOD of the chunk
    # bandwidth is a guaranteed progress floor for the long request.
    AGING_PERIOD = 4

    def _next_chunk_job(self) -> Optional[_ChunkJob]:
        """The most urgent in-flight prefill: priority, deadline, then
        fewest remaining tokens (shortest-remaining-prefill-first), so a
        short prompt admitted behind a long one reaches its first token
        after one chunk-time instead of after the long prompt's entire
        prefill — with periodic aging so the long one still progresses."""
        if not self._chunking:
            return None
        self._chunk_ticks += 1
        if self._chunk_ticks % self.AGING_PERIOD == 0:
            return min(self._chunking,
                       key=lambda j: (j.req.priority, j.req.deadline,
                                      j.req.rid))
        return min(self._chunking,
                   key=lambda j: (j.req.priority, j.req.deadline,
                                  j.remaining, j.req.rid))

    def _run_chunk(self, job: _ChunkJob) -> int:
        """Advance one job by one chunk; returns the chunk's token count."""
        start = job.done
        length = min(job.chunk_len, len(job.prompt) - start)
        with region("prefill", program="chunk", segments=1,
                    tokens=job.unpadded(length)):
            t0 = self._clock() if self._trace is not None else None
            self._ensure_state(job)
            fn = self._chunk_fn(len(job.prompt), start)
            toks = jnp.asarray(job.prompt[None, start:start + length])
            key = (len(job.prompt), start)
            events = self._chunk_tile_events.get(key)
            if self.paged:
                self.pool.prepare_span(job.req.rid, start, length)
                args = (self.params, toks, job.state, self.pool.arrays,
                        self.pool.device_table(job.req.rid))
                if events is None:
                    captured: List[Dict[str, Any]] = []
                    with attn_mod.capture_tile_events(captured.append):
                        logits, job.state, self.pool.arrays = fn(*args)
                    events = self._dedupe_events(captured)
                    self._chunk_tile_events[key] = events
                else:
                    logits, job.state, self.pool.arrays = fn(*args)
            elif events is None:
                captured = []
                with attn_mod.capture_tile_events(captured.append):
                    logits, job.state = fn(self.params, toks, job.state)
                events = self._dedupe_events(captured)
                self._chunk_tile_events[key] = events
            else:
                logits, job.state = fn(self.params, toks, job.state)
            self._advance_job(job, length, events, logits, t0=t0)
        return length

    def _finish_prefill(self, job: _ChunkJob, logits) -> None:
        """Last chunk done: sample the first token, account the prefill."""
        req = job.req
        _, _, sources = self._chunk_plan(len(job.prompt))
        # Plan + tile-event counters tick once per request prefill, not once
        # per chunk: a 16-chunk prefill must not inflate tile_fallback 16x.
        for kernel, source in sources.items():
            self.metrics.record_plan("prefill", kernel, source)
        if job.packed_runs:
            # The request's chunks (also) rode packed launches: count the
            # packed cell's resolution once per request, like every other
            # prefill cell.
            _, _, pack_source = self._pack_plan()
            self.metrics.record_plan("prefill", "packed_prefill",
                                     pack_source)
        for ev in self._dedupe_events(job.events):
            self._record_tile_event(ev)
        self.metrics.record_prefill_chunks(job.chunks_run)
        tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
        req.out_tokens.append(tok)
        # Submit time must be read BEFORE record_first_token pops it: the
        # ttft trace span is anchored at submit, exactly like the metric.
        sub_t = (self.metrics.submit_time(req.rid)
                 if self._trace is not None else None)
        self.metrics.record_first_token(req.rid, req.bucket)
        if self._trace is not None:
            self._trace.first_token(req.rid, req.bucket, sub_t)
        if self.paged:
            # The prefilled pages become shareable fleet-wide (weak
            # registry — holds no refs, never delays a free).
            self.pool.register_prefix(req.rid, job.prompt.tolist())
        if len(req.out_tokens) >= req.max_new_tokens:
            req.done = True
            if self.paged:
                self.pool.release(req.rid)
            self._finished.append(req)
            self.metrics.record_complete()
            if self._trace is not None:
                self._trace.finish(req.rid, len(req.out_tokens))
        else:
            if self.paged:
                # Next cache write (first decode) lands right after the
                # prompt.
                self._pos[req.rid] = len(job.prompt)
            self._ready.append((req, job.state))

    def add_request(self, prompt: np.ndarray, max_new_tokens: int = 16,
                    priority: int = 0,
                    deadline: float = math.inf,
                    submit_t: Optional[float] = None) -> Optional[int]:
        """Submit a request; returns its rid, or None when admission control
        rejects it (queue full, prompt longer than every bucket edge, or the
        padded prompt plus the generation would overflow the KV cache).

        ``submit_t`` backdates the TTFT anchor: fleet recovery re-queues a
        failed instance's request here with its ORIGINAL submit time, so
        the recovered first token's TTFT spans the whole outage instead of
        restarting the clock (submit-anchored across retries)."""
        prompt = np.asarray(prompt, np.int32)
        shaped = self.scheduler.admit_length(len(prompt))
        if shaped is None:
            return self._reject("over_length", len(prompt))
        # Decode writes KV at positions shaped..shaped+max_new-2 (the last
        # sampled token is never cached); past max_len the update would
        # silently clamp onto the final slot and corrupt attention.
        if shaped + max_new_tokens - 1 > self.max_len:
            return self._reject("cache_overflow", len(prompt))
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens,
                      priority=priority, deadline=deadline)
        if not self.scheduler.submit(req):
            return self._reject(
                getattr(self.scheduler, "last_reject_reason", "admission"),
                len(prompt))
        self.metrics.record_submit(rid, t=submit_t)
        self._record_backlog(self.scheduler.pending() + len(self._held)
                             + len(self._pool_wait))
        if self._trace is not None:
            self._trace.submit(rid, len(prompt), req.bucket)
        return rid

    def _reject(self, reason: str, prompt_len: int) -> None:
        """Account one admission rejection: reason counter, backlog sample
        (a rejected submit is exactly when backlog pressure peaked), and a
        trace instant carrying the reason. The reason also lands in
        ``self.last_reject_reason`` so a caller holding only the ``None``
        return (the fleet router's failover path) can read why."""
        self.last_reject_reason = reason
        self.metrics.record_reject(reason=reason)
        self._record_backlog(self.scheduler.pending() + len(self._held)
                             + len(self._pool_wait))
        if self._trace is not None:
            self._trace.reject(reason, prompt_len)
        return None

    def _record_backlog(self, depth: int) -> None:
        """Sample queue depth into metrics (and the trace counter track).
        Called at every step AND at every admit/reject: backlog accrued
        while the engine sits idle between steps was previously invisible
        to the step-only sampling."""
        self.metrics.record_queue_depth(depth)
        if self._trace is not None:
            self._trace.queue_depth(depth)

    def _admit(self):
        """Admit into free slots, running each whole prefill. Returns
        (total prompt tokens prefilled, per-prefill (admit_len, tokens)
        segments) — mixed-step accounting for virtual-clock drivers."""
        prefill_tokens = 0
        segments: List[Any] = []
        free = [i for i, r in enumerate(self._active) if r is None]
        while free:
            req = self.scheduler.next_request()
            if req is None:
                break
            prompt = self.scheduler.prepare(req)
            prefill_tokens += len(prompt)
            segments.append((len(prompt), len(prompt)))
            prefill = self._prefill_fn(len(prompt))
            for kernel, source in self._prefill_sources[len(prompt)].items():
                self.metrics.record_plan("prefill", kernel, source)
            sub_t = (self.metrics.submit_time(req.rid)
                     if self._trace is not None else None)
            with region("prefill", program="prefill", segments=1,
                        tokens=len(req.prompt)):
                t0 = self._clock() if self._trace is not None else None
                batch = {"tokens": jnp.asarray(prompt[None])}
                events = self._prefill_tile_events.get(len(prompt))
                if events is None:
                    captured: List[Dict[str, Any]] = []
                    with attn_mod.capture_tile_events(captured.append):
                        logits, state = prefill(self.params, batch)
                    events = self._dedupe_events(captured)
                    self._prefill_tile_events[len(prompt)] = events
                else:
                    logits, state = prefill(self.params, batch)
                for ev in events:
                    self._record_tile_event(ev)
                tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
            req.out_tokens.append(tok)
            self.metrics.record_first_token(req.rid, req.bucket)
            if self._trace is not None:
                self._trace.admit(
                    req.rid, len(prompt),
                    t0 - sub_t if sub_t is not None else 0.0)
                self._trace.prefill(req.rid, t0, len(prompt))
                self._trace.first_token(req.rid, req.bucket, sub_t)
            if len(req.out_tokens) >= req.max_new_tokens:
                # Satisfied by the prefill token alone — never occupy a
                # slot or run a decode step (which would also write KV one
                # position past the admission bound).
                req.done = True
                self._finished.append(req)
                self.metrics.record_complete()
                if self._trace is not None:
                    self._trace.finish(req.rid, len(req.out_tokens))
                continue
            i = free.pop(0)
            self._active[i] = req
            self._states[i] = state
        return prefill_tokens, tuple(segments)

    def _decode_all(self) -> int:
        """One decode step for every active slot. Returns #active."""
        n = 0
        active_buckets = []
        trace_rids = [] if self._trace is not None else None
        t0 = self._clock()
        for i, req in enumerate(self._active):
            if req is None:
                continue
            n += 1
            active_buckets.append(req.bucket)
            if trace_rids is not None:
                trace_rids.append(req.rid)
            with region("decode", tokens=1):
                last = jnp.asarray([[req.out_tokens[-1]]], jnp.int32)
                if self.paged:
                    # The decode program writes this token's K/V at the
                    # next cache position — make its page writable
                    # (CoW-splitting a shared one) before the launch.
                    pos = self._pos[req.rid]
                    self.pool.prepare_span(req.rid, pos, 1)
                    self._pos[req.rid] = pos + 1
                    args = (self.params, last, self._states[i],
                            self.pool.arrays, self.pool.device_table(req.rid))
                    if self._decode_tile_events is None:
                        captured: List[Dict[str, Any]] = []
                        with attn_mod.capture_tile_events(captured.append):
                            (logits, self._states[i],
                             self.pool.arrays) = self._decode_paged(*args)
                        self._decode_tile_events = self._dedupe_events(
                            captured)
                        for ev in self._decode_tile_events:
                            self._record_tile_event(ev)
                    else:
                        (logits, self._states[i],
                         self.pool.arrays) = self._decode_paged(*args)
                elif self._decode_tile_events is None:
                    captured = []
                    with attn_mod.capture_tile_events(captured.append):
                        logits, self._states[i] = self._decode(
                            self.params, last, self._states[i])
                    self._decode_tile_events = self._dedupe_events(captured)
                    for ev in self._decode_tile_events:
                        self._record_tile_event(ev)
                else:
                    logits, self._states[i] = self._decode(
                        self.params, last, self._states[i])
                tok = int(jnp.argmax(logits[0, :self.cfg.vocab_size]))
                req.out_tokens.append(tok)
                if len(req.out_tokens) >= req.max_new_tokens:
                    req.done = True
                    self._active[i] = None
                    self._states[i] = None
                    if self.paged:
                        self.pool.release(req.rid)
                        self._pos.pop(req.rid, None)
                    self._finished.append(req)
                    self.metrics.record_complete()
                    if self._trace is not None:
                        self._trace.finish(req.rid, len(req.out_tokens))
        self.metrics.record_decode_step(active_buckets, self._clock() - t0)
        if trace_rids is not None and n:
            self._trace.decode(t0, trace_rids)
        return n

    def step(self) -> int:
        """One engine step. Returns the number of requests in service.

        Unchunked: admit (each admission runs its whole prefill) + one
        decode step over the active slots — the pre-chunking behavior.
        Chunked: a **mixed step** — one prefill chunk for the most urgent
        in-flight prefill co-scheduled with the whole decode batch, the two
        together bounded by ``step_token_budget`` tokens.
        """
        with region("step", step=self.steps_run):
            if self.chunk_prefill:
                return self._step_chunked()
            return self._step_whole()

    def _step_whole(self) -> int:
        t0 = self._clock() if self._trace is not None else 0.0
        with region("admit"):
            prefill_tokens, segments = self._admit()
        self._record_backlog(self.scheduler.pending())
        n = self._decode_all()
        # Second admission pass: requests that FINISHED in this step's
        # decode released their slots (and caches) above — admitting again
        # lets a queued request claim the freed headroom in the same step
        # instead of idling one extra step per turnover. Admission-order
        # and token math are untouched; only the latency of reusing a
        # freed slot changes.
        with region("admit"):
            extra_tokens, extra_segments = self._admit()
        prefill_tokens += extra_tokens
        segments = segments + extra_segments
        self.last_step_stats = {"prefill_tokens": prefill_tokens,
                                "decode_tokens": n,
                                "packed_chunks": 0, "packed_rids": (),
                                "prefill_segments": segments}
        self._maybe_shadow()
        self.steps_run += 1
        if self._trace is not None:
            self._trace.step_mark(t0, self.last_step_stats, self.steps_run)
        return n

    def _step_chunked(self) -> int:
        t0 = self._clock() if self._trace is not None else 0.0
        with region("admit"):
            self._admit_chunked()
        # Held (deferred multi-chunk) requests are still backlog.
        self._record_backlog(self.scheduler.pending() + len(self._held)
                             + len(self._pool_wait))
        prefill_tokens = 0
        packed_rids: tuple = ()
        segments: tuple = ()
        if self.pack_prefill:
            picks = self._next_pack()
            if picks:
                packed_rids = tuple(job.req.rid for job, _ in picks)
                segments = tuple((len(job.prompt), take)
                                 for job, take in picks)
                self.metrics.record_packed_step(len(picks))
                if len(picks) == 1:
                    # Singleton pack: reuse the per-(admit_len, start)
                    # chunk program — same math, warmer jit cache.
                    prefill_tokens = self._run_chunk(picks[0][0])
                else:
                    prefill_tokens = self._run_pack(picks)
                with region("admit"):
                    self._admit_chunked()
        else:
            job = self._next_chunk_job()
            if job is not None:
                packed_rids = (job.req.rid,)
                segments = ((len(job.prompt),
                             min(job.chunk_len, job.remaining)),)
                prefill_tokens = self._run_chunk(job)
                # A prefill finished by that chunk may start decoding this
                # very step if a slot is free — its first decode token
                # rides the same mixed step.
                with region("admit"):
                    self._admit_chunked()
        n = self._decode_all()
        # Second admission pass (same rationale as _step_whole): decode just
        # released the slots/pool pages of every request it finished, so a
        # waiting request admits THIS step — in paged mode this is also
        # what lets a pool-starved request claim freed pages without a
        # one-step bubble.
        with region("admit"):
            self._admit_chunked()
        if self.paged:
            self.metrics.record_pool(self.pool.used_pages,
                                     self.pool.n_pages)
        self.last_step_stats = {"prefill_tokens": prefill_tokens,
                                "decode_tokens": n,
                                "packed_chunks": len(packed_rids),
                                "packed_rids": packed_rids,
                                "prefill_segments": segments}
        self._maybe_shadow()
        self.steps_run += 1
        if self._trace is not None:
            self._trace.step_mark(t0, self.last_step_stats, self.steps_run)
        return (n + len(self._chunking) + len(self._ready)
                + len(self._held) + len(self._pool_wait))

    def _next_pack(self):
        """The chunks this packed step runs: scheduler knapsack over the
        in-flight prefills under min(step budget - decode batch, plan pack
        width), at most ``prefill_slots`` segments, with the same
        SRPT-plus-aging head rule as one-chunk-per-step service."""
        from repro.serve.scheduler import pick_chunks

        if not self._chunking:
            return []
        self._chunk_ticks += 1
        aging = self._chunk_ticks % self.AGING_PERIOD == 0
        return pick_chunks(self._chunking, self._pack_budget(),
                           self.prefill_slots, aging=aging)

    def in_flight(self) -> int:
        """Requests holding engine state (decode slots + partial prefills +
        deferred multi-chunk admissions + pool-starved waiters)."""
        return (sum(r is not None for r in self._active)
                + len(self._chunking) + len(self._ready)
                + len(self._held) + len(self._pool_wait))

    # -- eviction / handoff (fleet fault tolerance) --------------------------
    def _evict_state(self, req: Request) -> None:
        """Tear down one request's engine-held state: pool pages released
        (refcount-balanced; ``missing_ok`` because _held/_pool_wait stages
        never registered), decode cursor dropped, pending TTFT anchor
        dropped (the recovering router re-anchors it on the next engine)."""
        if self.paged:
            self.pool.release(req.rid, missing_ok=True)
            self._pos.pop(req.rid, None)
        t = self.metrics.drop_submit(req.rid)
        if t is not None:
            req.submit_t = t

    def extract_queued(self) -> List[Request]:
        """Hand off every request that has not started prefilling: the
        scheduler queue (drained in urgency order), the multi-chunk holding
        pen, and the pool-wait line. None of these hold device state or
        pool pages — extraction is pure bookkeeping. Generated tokens are
        untouched (there are none). Used by graceful drain and work
        handoff; the caller re-queues them elsewhere."""
        out: List[Request] = []
        while True:
            req = self.scheduler.next_request()
            if req is None:
                break
            out.append(req)
        out.extend(self._held)
        self._held.clear()
        out.extend(self._pool_wait)
        self._pool_wait.clear()
        for req in out:
            self._evict_state(req)
        return out

    def evict_all(self) -> List[Request]:
        """Evict EVERY non-finished request — queued, mid-prefill, ready,
        and decoding — tearing down per-request state (pool pages released
        and refcount-balanced, partial caches dropped). Returns the evicted
        requests with their ``out_tokens`` so far, so fleet recovery can
        account discarded work; recovery re-prefills from the original
        prompt, never from the torn-down caches. Finished requests stay in
        ``self._finished``."""
        out = self.extract_queued()
        for job in list(self._chunking):
            self._evict_state(job.req)
            out.append(job.req)
        self._chunking.clear()
        for req, _state in self._ready:
            self._evict_state(req)
            out.append(req)
        self._ready.clear()
        for i, req in enumerate(self._active):
            if req is None:
                continue
            self._evict_state(req)
            out.append(req)
            self._active[i] = None
            self._states[i] = None
        return out

    def cancel(self, rid: int) -> Optional[Request]:
        """Remove one request wherever it sits in the pipeline (queued,
        held, pool-waiting, mid-chunk-prefill, ready, or decoding), tearing
        down its state exactly like :meth:`evict_all` does for the whole
        engine. Returns the request, or None when ``rid`` is not resident
        (already finished or never admitted)."""
        remove = getattr(self.scheduler, "remove", None)
        req = remove(rid) if remove is not None else None
        if req is None:
            for pen in (self._held, self._pool_wait):
                for i, r in enumerate(pen):
                    if r.rid == rid:
                        req = pen.pop(i)
                        break
                if req is not None:
                    break
        if req is None:
            for job in self._chunking:
                if job.req.rid == rid:
                    req = job.req
                    self._chunking.remove(job)
                    break
        if req is None:
            for i, (r, _state) in enumerate(self._ready):
                if r.rid == rid:
                    req = self._ready.pop(i)[0]
                    break
        if req is None:
            for i, r in enumerate(self._active):
                if r is not None and r.rid == rid:
                    req = r
                    self._active[i] = None
                    self._states[i] = None
                    break
        if req is not None:
            self._evict_state(req)
        return req

    def run_until_done(self, max_steps: int = 1000) -> List[Request]:
        self._finished = []
        for _ in range(max_steps):
            if not self.in_flight() and not self.scheduler.pending():
                break
            self.step()
        return self._finished
