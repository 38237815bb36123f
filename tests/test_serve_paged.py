"""Differential conformance + lifecycle property suite for the paged KV
pool (``repro.serve.pool`` + ``ServeEngine(paged=True)``).

The headline contract mirrors test_serve_packing's: a pool-backed engine —
page-table indirection on every cache read/write, refcounted pages,
shared-prefix copy-on-write — must be *observationally identical* to the
per-request-cache engine. The suite replays the SAME seed-pinned traces
(``benchmarks/traces.py``) through baseline and paged engines in all three
service modes and asserts:

* **token parity** — every request's greedy tokens are identical between
  per-request caches and the paged pool, per adversarial family, per mode
  (unchunked / chunked / packed);
* **lifecycle balance** (property test, hypothesis with a fixed-sample
  fallback) — after every replay drains, refcounts are zero, the free list
  covers the pool exactly once (``check_balanced``), and page allocs equal
  page frees — no leak, no double-free, for every family x mode x seed;
* **copy-on-write correctness** — a prefix-sharing run (donor resident and
  decoding while the recipient maps its pages) produces tokens identical
  to a sharing-disabled run, with at least one prefix hit and one CoW
  split actually exercised;
* **occupancy unlock** — the paged engine holds strictly more concurrent
  resident prefills than ``prefill_slots``, the per-request-cache ceiling
  (the tentpole's capacity claim, also measured by bench_chunked_prefill);
* **cache-lifecycle bugfix pins** — the ``_pack_fn`` layout cache is LRU
  (a hot layout survives cap-many cold layouts), freed capacity is re-used
  in the same step it frees (second admission pass), and ring-cache
  wraparound at exact ``cache_len`` boundaries matches whole-prompt
  prefill position by position;
* **in-place pool** — the compiled decode and chunk programs alias the
  donated pool and keep no pool-sized temporaries, the packed program
  aliases nothing, and donation never costs a later request its tokens
  or a warm-up its pool.

Run on the reference lowerings by default; the CI ``paged-conformance``
job adds an interpret-mode Pallas leg (REPRO_PALLAS_INTERPRET=1) so the
same assertions cover the Pallas kernel bodies without TPU hardware.
"""
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
import traces as trace_lib  # noqa: E402  (benchmarks/traces.py)

from repro import configs  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serve import (  # noqa: E402
    BucketPolicy, PagedKVPool, ServeEngine, ShapeBucketScheduler,
    supports_prefix_sharing,
)

try:  # keep the rest of this module runnable without the dev dependency
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

EDGES = (8, 64)
NEW_TOKENS = 3
PAGE = 16            # small pages so requests span multiple table entries
MODES = ("unchunked", "chunked", "packed")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, mode, paged=False, budget=32, edges=EDGES,
            slots=2, prefill_slots=3, allow_overflow=False, max_len=None,
            max_queue=99, **paged_kw):
    top = max(edges)
    if max_len is None:
        max_len = (2 * top + 16) if allow_overflow else top + 16
    return ServeEngine(
        cfg, params, max_len=max_len, slots=slots,
        scheduler=ShapeBucketScheduler(
            BucketPolicy(edges, max_queue=max_queue,
                         allow_overflow=allow_overflow)),
        chunk_prefill=(mode != "unchunked"),
        pack_prefill=(mode == "packed"),
        prefill_slots=prefill_slots,
        step_token_budget=(budget if mode != "unchunked" else 0),
        paged=paged, page_size=(PAGE if paged else None), **paged_kw)


def _serve(eng, trace, max_new_tokens=NEW_TOKENS, max_steps=2000):
    """Drive to drain; returns ({rid: tokens}, peak concurrent prefills)."""
    rids = [eng.add_request(p, max_new_tokens=max_new_tokens) for p in trace]
    assert all(r is not None for r in rids), "pinned trace request rejected"
    peak = 0
    for _ in range(max_steps):
        eng.step()
        peak = max(peak, len(eng._chunking))
        if not eng.in_flight() and not eng.scheduler.pending():
            break
    else:
        pytest.fail("engine did not drain (starvation?)")
    return {r.rid: tuple(r.out_tokens) for r in eng._finished}, peak


# ---------------------------------------------------------------------------
# The differential suite: per-request caches vs the paged pool, per family
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("family", trace_lib.FAMILIES)
def test_paged_differential_conformance(family, smoke_model):
    """Token parity baseline-vs-paged in every service mode, plus the
    drained-pool balance invariant, per adversarial family."""
    cfg, params = smoke_model
    overflow = family == "overflow_heavy"
    trace = trace_lib.make_trace(family, seed=0, vocab=cfg.vocab_size,
                                 edges=EDGES, n=8)
    for mode in MODES:
        base, _ = _serve(_engine(cfg, params, mode,
                                 allow_overflow=overflow), trace)
        assert len(base) == len(trace)          # no starvation, no drops
        eng = _engine(cfg, params, mode, paged=True,
                      allow_overflow=overflow)
        paged, _ = _serve(eng, trace)
        assert paged == base, \
            f"{family}/{mode}: paged tokens diverged from per-request caches"
        eng.pool.check_balanced()               # refcounts drained to zero
        pm = eng.metrics.as_dict()["pool"]
        assert pm["page_allocs"] == pm["page_frees"]


@pytest.mark.slow
def test_paged_occupancy_exceeds_prefill_slots(smoke_model):
    """The capacity unlock is vacuous if the paged engine never holds more
    partial prefills than the per-request ceiling: under a short-burst
    trace, concurrent resident prefills must exceed ``prefill_slots``."""
    cfg, params = smoke_model
    trace = trace_lib.make_trace("all_short", seed=0, vocab=cfg.vocab_size,
                                 edges=EDGES, n=10)
    base_eng = _engine(cfg, params, "chunked", prefill_slots=2)
    _, base_peak = _serve(base_eng, trace)
    assert base_peak <= 2                       # the ceiling being unlocked
    eng = _engine(cfg, params, "chunked", paged=True, prefill_slots=2)
    _, peak = _serve(eng, trace)
    assert peak > 2, \
        f"paged engine never exceeded prefill_slots residency (peak={peak})"
    eng.pool.check_balanced()


# ---------------------------------------------------------------------------
# Shared prefixes: reuse hits, CoW splits, and token identity
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_prefix_sharing_cow_token_parity(smoke_model):
    """A recipient mapping a resident donor's pages (including the donor's
    partial tail page -> CoW on both sides' next writes) must emit tokens
    identical to a sharing-disabled run — and the hit/split machinery must
    actually fire, or the parity is vacuous."""
    cfg, params = smoke_model
    assert supports_prefix_sharing(cfg)
    rng = np.random.default_rng(7)
    donor = rng.integers(2, cfg.vocab_size, size=10).astype(np.int32)
    recipient = np.concatenate(
        [donor, rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)])

    def run(sharing):
        eng = ServeEngine(cfg, params, max_len=64, slots=2,
                          prefill_slots=2, paged=True, page_size=4,
                          prefix_sharing=sharing)
        eng.add_request(donor, max_new_tokens=8)
        eng.step()                  # donor prefills + registers its pages
        eng.add_request(recipient, max_new_tokens=8)
        for _ in range(200):        # donor decodes next to the recipient
            eng.step()
            if not eng.in_flight() and not eng.scheduler.pending():
                break
        eng.pool.check_balanced()
        return ({r.rid: tuple(r.out_tokens) for r in eng._finished},
                eng.metrics.as_dict()["pool"])

    shared_tokens, shared_pool = run(True)
    plain_tokens, plain_pool = run(False)
    assert shared_tokens == plain_tokens
    assert shared_pool["prefix_hits"] >= 1, "prefix reuse never fired"
    assert shared_pool["prefix_tokens_reused"] >= 8
    assert shared_pool["cow_splits"] >= 1, "no copy-on-write was exercised"
    assert plain_pool["prefix_hits"] == 0 and plain_pool["cow_splits"] == 0


# ---------------------------------------------------------------------------
# Property: lifecycle balance across families x modes x seeds
# ---------------------------------------------------------------------------

def _lifecycle_property(smoke, family, mode, seed):
    cfg, params = smoke
    trace = trace_lib.make_trace(family, seed=seed, vocab=cfg.vocab_size,
                                 edges=EDGES, n=6)
    eng = _engine(cfg, params, mode, paged=True,
                  allow_overflow=(family == "overflow_heavy"))
    tokens, _ = _serve(eng, trace)
    assert len(tokens) == len(trace)
    eng.pool.check_balanced()
    pm = eng.metrics.as_dict()["pool"]
    assert pm["page_allocs"] == pm["page_frees"] > 0


if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @settings(max_examples=6, deadline=None)
    @given(family=st.sampled_from(trace_lib.FAMILIES),
           mode=st.sampled_from(MODES), seed=st.integers(0, 3))
    def test_paged_lifecycle_property(smoke_model, family, mode, seed):
        _lifecycle_property(smoke_model, family, mode, seed)
else:
    @pytest.mark.slow
    @pytest.mark.parametrize("family,mode,seed", [
        ("all_short", "packed", 1), ("bimodal", "chunked", 2),
        ("head_of_line", "unchunked", 3), ("overflow_heavy", "packed", 0),
    ])
    def test_paged_lifecycle_property(smoke_model, family, mode, seed):
        # hypothesis unavailable: run a fixed sample of the property grid.
        _lifecycle_property(smoke_model, family, mode, seed)


# ---------------------------------------------------------------------------
# Pool unit invariants: double-free, non-contiguous writes, admission math
# ---------------------------------------------------------------------------

def _tiny_pool(cfg, n_pages=8, page=4, max_len=16):
    import jax.numpy as jnp

    return PagedKVPool(cfg, n_pages=n_pages, page=page, max_len=max_len,
                       dtype=jnp.float32)


def test_pool_double_release_raises(smoke_model):
    cfg, _ = smoke_model
    pool = _tiny_pool(cfg)
    pool.register_request(0, 8)
    pool.prepare_span(0, 0, 8)
    assert pool.release(0) == 2
    with pytest.raises(KeyError):
        pool.release(0)                         # lifecycle bug, never silent
    pool.check_balanced()


def test_pool_noncontiguous_write_raises(smoke_model):
    cfg, _ = smoke_model
    pool = _tiny_pool(cfg)
    pool.register_request(0, 16)
    with pytest.raises(ValueError):
        pool.prepare_span(0, 8, 4)              # skips the first two pages
    pool.release(0)
    pool.check_balanced()


def test_pool_reservation_admission(smoke_model):
    """can_admit accounts every resident's worst-case remaining demand plus
    CoW slack, so a granted admission can never exhaust the pool
    mid-flight (the _alloc RuntimeError stays unreachable)."""
    cfg, _ = smoke_model
    pool = _tiny_pool(cfg, n_pages=8, page=4, max_len=32)
    assert pool.can_admit(8)                    # 2 pages + 2 slack <= 8 free
    pool.register_request(0, 8)
    # Resident 0 reserves 2+2; a second 8-token request needs 2+2 more.
    assert pool.can_admit(8)
    pool.register_request(1, 8)
    assert not pool.can_admit(4)                # 2+2 free pages short
    for rid in (0, 1):
        pool.prepare_span(rid, 0, 8)            # worst case actually lands
        pool.release(rid)
    pool.check_balanced()


# ---------------------------------------------------------------------------
# Teardown on mid-flight eviction / cancel (fleet fault tolerance)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_cancel_mid_prefill_teardown(smoke_model):
    """Cancelling a request mid-chunked-prefill releases its pages
    refcount-balanced (no leak, no double-free), is idempotent, and leaves
    the survivor's greedy tokens untouched."""
    cfg, params = smoke_model
    rng = np.random.default_rng(11)
    long_p = rng.integers(2, cfg.vocab_size, size=40).astype(np.int32)
    short_p = rng.integers(2, cfg.vocab_size, size=6).astype(np.int32)

    eng = _engine(cfg, params, "chunked", paged=True, budget=8)
    victim = eng.add_request(long_p, max_new_tokens=NEW_TOKENS)
    other = eng.add_request(short_p, max_new_tokens=NEW_TOKENS)
    eng.step()                   # budget 8 << 40: victim is mid-prefill
    assert any(j.req.rid == victim for j in eng._chunking), \
        "setup: victim should be partially prefilled"
    req = eng.cancel(victim)
    assert req is not None and req.rid == victim
    assert eng.cancel(victim) is None        # already gone: no double-free
    for _ in range(200):
        eng.step()
        if not eng.in_flight() and not eng.scheduler.pending():
            break
    eng.pool.check_balanced()
    pm = eng.metrics.as_dict()["pool"]
    assert pm["page_allocs"] == pm["page_frees"]
    tokens = {r.rid: tuple(r.out_tokens) for r in eng._finished}
    assert victim not in tokens and other in tokens
    # The survivor's tokens match a run that never saw the cancelled
    # request (greedy parity: cancellation must not corrupt shared state).
    solo = _engine(cfg, params, "chunked", paged=True, budget=8)
    solo_rid = solo.add_request(short_p, max_new_tokens=NEW_TOKENS)
    solo.run_until_done(max_steps=200)
    assert tokens[other] == tuple(
        next(r for r in solo._finished if r.rid == solo_rid).out_tokens)


@pytest.mark.slow
def test_paged_evict_all_mid_flight_balanced(smoke_model):
    """evict_all with a full pipeline (decoding + mid-prefill + ready +
    queued) releases every page, leaves the pool balanced, and the engine
    stays serviceable: a re-admitted evicted prompt reproduces a fresh
    engine's tokens (re-prefill from the prompt, not the torn-down
    cache)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(12)
    mk = lambda n: rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
    prompts = [mk(6), mk(40), mk(30), mk(5)]
    eng = _engine(cfg, params, "chunked", paged=True, budget=8,
                  prefill_slots=2)
    for p in prompts:
        assert eng.add_request(p, max_new_tokens=NEW_TOKENS) is not None
    eng.step()
    eng.step()                   # mix of decode slots, partial, queued
    finished = {r.rid for r in eng._finished}
    evicted = eng.evict_all()
    assert {r.rid for r in evicted} == set(range(len(prompts))) - finished
    assert eng.in_flight() == 0 and eng.scheduler.pending() == 0
    eng.pool.check_balanced()
    pm = eng.metrics.as_dict()["pool"]
    assert pm["page_allocs"] == pm["page_frees"]
    # Re-admission after teardown: same engine, evicted prompt, same
    # greedy tokens as a never-disturbed engine.
    rid = eng.add_request(prompts[1], max_new_tokens=NEW_TOKENS)
    assert rid is not None
    eng.run_until_done(max_steps=200)
    eng.pool.check_balanced()
    redone = next(r for r in eng._finished if r.rid == rid)
    fresh = _engine(cfg, params, "chunked", paged=True, budget=8)
    fresh_rid = fresh.add_request(prompts[1], max_new_tokens=NEW_TOKENS)
    fresh.run_until_done(max_steps=200)
    assert tuple(redone.out_tokens) == tuple(
        next(r for r in fresh._finished if r.rid == fresh_rid).out_tokens)


@pytest.mark.slow
def test_paged_cancel_shared_prefix_donor(smoke_model):
    """Cancelling a donor whose pages a resident recipient still maps must
    not pull the shared pages out from under the recipient: refcounts keep
    them alive, the recipient's tokens match a sharing-disabled run, and
    the drained pool balances (prefix-registry consistency after the
    donor's teardown)."""
    cfg, params = smoke_model
    assert supports_prefix_sharing(cfg)
    rng = np.random.default_rng(7)
    donor = rng.integers(2, cfg.vocab_size, size=10).astype(np.int32)
    recipient = np.concatenate(
        [donor, rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)])

    def run(sharing, cancel_donor):
        eng = ServeEngine(cfg, params, max_len=64, slots=2,
                          prefill_slots=2, paged=True, page_size=4,
                          prefix_sharing=sharing)
        d = eng.add_request(donor, max_new_tokens=8)
        eng.step()               # donor prefills + registers its pages
        eng.add_request(recipient, max_new_tokens=8)
        eng.step()               # recipient admitted, maps donor pages
        if cancel_donor:
            assert eng.cancel(d) is not None
        for _ in range(200):
            eng.step()
            if not eng.in_flight() and not eng.scheduler.pending():
                break
        eng.pool.check_balanced()
        return ({r.rid: tuple(r.out_tokens) for r in eng._finished},
                eng.metrics.as_dict()["pool"])

    cancelled, shared_pool = run(True, True)
    plain, _ = run(False, False)
    assert 0 not in cancelled, "cancelled donor must not finish"
    assert cancelled[1] == plain[1], \
        "recipient tokens corrupted by cancelling its prefix donor"
    assert shared_pool["prefix_hits"] >= 1, "prefix reuse never fired"
    assert shared_pool["page_allocs"] == shared_pool["page_frees"]


# ---------------------------------------------------------------------------
# Bugfix pins: LRU layout cache / same-step re-admission / ring boundary
# ---------------------------------------------------------------------------

def test_pack_fn_cache_is_lru(smoke_model):
    """A hot packed layout touched between bursts of cold layouts must
    survive cap-many insertions without retracing (FIFO eviction drops the
    oldest INSERTION — exactly the steady-state hot layout)."""
    cfg, params = smoke_model
    eng = _engine(cfg, params, "packed")
    cap = eng.PACK_FN_CACHE_CAP
    hot = ((0, 4),)
    hot_fn = eng._pack_fn(hot)
    cold = 0
    for burst in range(4):                      # 4 bursts of (cap - 1) colds
        for _ in range(cap - 1):
            cold += 1
            eng._pack_fn(((0, 4), (cold, 1)))
        # The hot layout is touched between bursts — recency protects it.
        assert eng._pack_fn(hot) is hot_fn, \
            f"hot layout evicted after burst {burst} (FIFO behavior)"
    assert len(eng._pack_fns) <= cap


def test_freed_slot_readmits_same_step(smoke_model):
    """Headroom freed by a request finishing in a step's decode is usable
    by admission in the SAME step: fill the only slot, let the request
    finish, and assert the queued request produces its first token on the
    very step the slot freed (not one step later)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(5)
    mk = lambda n: rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
    for mode in ("unchunked", "chunked"):
        eng = _engine(cfg, params, mode, slots=1, prefill_slots=1,
                      max_queue=4)
        assert eng.add_request(mk(5), max_new_tokens=2) is not None
        eng.step()                              # A prefills + first token
        assert eng.add_request(mk(6), max_new_tokens=2) is not None
        eng.step()                              # A's last decode frees slot
        done = {r.rid for r in eng._finished}
        assert 0 in done, f"{mode}: request A should have finished"
        live = ([r for r in eng._active if r is not None]
                + [j.req for j in eng._chunking]
                + [p[0] for p in eng._ready]
                + eng._finished)
        b = next(r for r in live if r.rid == 1)
        assert b.out_tokens, \
            f"{mode}: freed capacity not re-admitted in the same step"


@pytest.mark.slow
def test_ring_cache_exact_boundary_parity():
    """Ring-cache (windowed local_attn) wraparound pin: chunk boundaries
    landing exactly ON the ring's cache_len (= window) — a chunk ENDING at
    the boundary, the next STARTING there, and a prompt spanning 2x the
    window — must reproduce whole-prompt prefill logits, and the wrapped
    cache must decode identically afterwards."""
    import jax.numpy as jnp

    cfg = configs.get_smoke("gemma2-9b")
    w = cfg.attn_window
    assert w and w >= 4
    params = api.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    max_len = 3 * w
    # Boundary-adversarial lengths: exactly w, one past w, exactly 2w.
    for total, cuts in (
        (w, (w,)),                  # single chunk ends exactly at cache_len
        (w + 1, (w, 1)),            # second chunk STARTS at the boundary
        (2 * w, (w - 1, w + 1)),    # a chunk CROSSES the wrap point
        (2 * w, (w, w)),            # both edges land on boundaries
    ):
        prompt = rng.integers(2, cfg.vocab_size,
                              size=(1, total)).astype(np.int32)
        ref_logits, ref_state = api.prefill(
            params, cfg, {"tokens": jnp.asarray(prompt)}, max_len=max_len,
            dtype=jnp.float32, ring_local=True)
        st = api.make_serve_state(cfg, 1, max_len, jnp.float32,
                                  ring_local=True)
        pos = 0
        for c in cuts:
            lg, st = api.prefill_chunk(
                params, cfg, jnp.asarray(prompt[:, pos:pos + c]), st, pos)
            pos += c
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_logits),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"cuts={cuts} total={total}")
        # The wrapped ring must also READ back identically: greedy-decode
        # a few tokens from both states and compare step logits.
        tok_r = jnp.argmax(ref_logits[:, :cfg.vocab_size], -1)[:, None]
        tok_c = jnp.argmax(lg[:, :cfg.vocab_size], -1)[:, None]
        for _ in range(3):
            dr, ref_state = api.decode_step(params, cfg,
                                            tok_r.astype(jnp.int32),
                                            ref_state)
            dc, st = api.decode_step(params, cfg, tok_c.astype(jnp.int32),
                                     st)
            np.testing.assert_allclose(np.asarray(dc), np.asarray(dr),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"decode after cuts={cuts}")
            tok_r = jnp.argmax(dr[:, :cfg.vocab_size], -1)[:, None]
            tok_c = jnp.argmax(dc[:, :cfg.vocab_size], -1)[:, None]


# ---------------------------------------------------------------------------
# In-place pool: aliasing of the compiled programs, and donation safety
# ---------------------------------------------------------------------------

def _paged_programs(cfg, params, eng):
    """The engine's three pool-backed programs with arguments of the types
    the engine gives them: decode, a chunk that reads a prefix page, and a
    two-segment pack."""
    import jax.numpy as jnp

    state = api.make_paged_state(cfg, eng.dtype)
    table = jnp.zeros((eng.pool.n_pt,), jnp.int32)
    toks = jnp.zeros((1, PAGE), jnp.int32)
    arrays = eng.pool.arrays
    return {
        "decode": (eng._decode_paged, params, toks[:, :1], state, arrays,
                   table),
        "chunk": (eng._chunk_fn(max(EDGES), PAGE), params, toks, state,
                  arrays, table),
        "pack": (eng._pack_fn(((PAGE, PAGE // 2), (0, PAGE // 2))), params,
                 toks, (state, state), arrays, (table, table)),
    }


@pytest.mark.parametrize("program", ["decode", "chunk", "pack"])
def test_paged_programs_update_pool_in_place(smoke_model, program):
    """Decode and chunk donate the pool and update it in place: the
    compiled program aliases the whole pool and its temporaries stay far
    below it (each layer reads only the request's pages and writes only its
    new rows). The packed program does not donate (a warm-up may run it on
    the live pool and drop the result): it aliases nothing, and still keeps
    no pool-sized temporary."""
    cfg, params = smoke_model
    eng = _engine(cfg, params, "packed", paged=True, pool_pages=320)
    assert eng.pool.n_pages >= 4 * eng.pool.n_pt
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.pool.arrays))
    fn, *args = _paged_programs(cfg, params, eng)[program]
    mem = fn.lower(*args).compile().memory_analysis()
    if program == "pack":
        assert mem.alias_size_in_bytes == 0
        assert mem.temp_size_in_bytes < pool_bytes
    else:
        assert mem.alias_size_in_bytes >= pool_bytes
        assert mem.temp_size_in_bytes < pool_bytes / 4


def test_donated_pool_serves_back_to_back_streams(smoke_model):
    """Every decode and chunk launch consumes the pool it was given. An
    engine that served one stream serves a second exactly as a fresh
    engine does, and the arrays it held before serving are gone (donated,
    not copied). Chunked mode, so that no (undonating) pack runs."""
    cfg, params = smoke_model
    first = trace_lib.make_trace("bimodal", seed=1, vocab=cfg.vocab_size,
                                 edges=EDGES, n=4)
    second = trace_lib.make_trace("all_short", seed=2, vocab=cfg.vocab_size,
                                  edges=EDGES, n=4)
    eng = _engine(cfg, params, "chunked", paged=True)
    before = jax.tree.leaves(eng.pool.arrays)
    _serve(eng, first)
    assert all(a.is_deleted() for a in before)
    done = len(eng._finished)
    _serve(eng, second)
    again = [tuple(r.out_tokens) for r in eng._finished[done:]]
    fresh = _engine(cfg, params, "chunked", paged=True)
    _serve(fresh, second)
    assert again == [tuple(r.out_tokens) for r in fresh._finished]
    eng.pool.check_balanced()


def test_warm_packs_keeps_the_pool(smoke_model):
    """``chipbench.harness.warm_packs`` runs the packed program on the
    engine's live pool and drops what it returns. The packed program does
    not donate, so the pool survives and the engine then serves as a fresh
    one does."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench import harness

    cfg, params = smoke_model
    trace = trace_lib.make_trace("bimodal", seed=3, vocab=cfg.vocab_size,
                                 edges=EDGES, n=4)
    eng = _engine(cfg, params, "packed", paged=True)
    harness.warm_packs(eng, [((0, 8), (0, 8)), ((PAGE, 8), (0, 4))])
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.pool.arrays))
    warmed, _ = _serve(eng, trace)
    fresh, _ = _serve(_engine(cfg, params, "packed", paged=True), trace)
    assert warmed == fresh
