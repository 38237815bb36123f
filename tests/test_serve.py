"""Serving engine: batched requests, slot reuse, greedy decode determinism."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.models import api
from repro.serve.engine import ServeEngine


@pytest.fixture(scope="module")
def engine():
    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, max_len=64, slots=2)


def test_generates_requested_tokens(engine):
    rid = engine.add_request(np.asarray([5, 6, 7]), max_new_tokens=8)
    done = engine.run_until_done()
    assert len(done) == 1 and done[0].rid == rid
    assert len(done[0].out_tokens) == 8
    assert all(0 <= t < engine.cfg.vocab_size for t in done[0].out_tokens)


def test_batched_requests_and_slot_reuse(engine):
    for i in range(5):  # > slots => queueing + reuse
        engine.add_request(np.asarray([1, 2, 3, i + 1]), max_new_tokens=4)
    done = engine.run_until_done()
    assert len(done) == 5
    assert all(len(r.out_tokens) == 4 for r in done)


def test_greedy_determinism(engine):
    p = np.asarray([9, 8, 7, 6])
    engine.add_request(p, max_new_tokens=6)
    a = engine.run_until_done()[0].out_tokens
    engine.add_request(p, max_new_tokens=6)
    b = engine.run_until_done()[0].out_tokens
    assert a == b


# ---------------------------------------------------------------------------
# Plan hit rate: bucketed admission lands on exact plan cells.
# ---------------------------------------------------------------------------

def _bucket_plan(edges, slots, max_len, hardware):
    from repro.core import HARDWARE_REGISTRY
    from repro.core.plans import compile_plan
    from repro.launch.compile_plans import serve_bucket_cells

    cells = serve_bucket_cells(["qwen2-1.5b"], edges, slots, max_len,
                               smoke=True)
    return compile_plan([(k, p, "float32", HARDWARE_REGISTRY[hardware])
                         for k, p in cells])


def test_bucketed_plan_hit_rate_exact():
    """Bucketed prefills resolve exactly; raw FIFO shapes do not."""
    from repro import kernels
    from repro.core import HARDWARE_REGISTRY
    from repro.serve import BucketPolicy, ServeEngine, ShapeBucketScheduler

    kernels.register_all()
    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    plan = _bucket_plan((8, 16), slots=2, max_len=32, hardware="tpu_v5e")

    bucketed = ServeEngine(
        cfg, params, max_len=32, slots=2, plans=plan,
        hardware=HARDWARE_REGISTRY["tpu_v5e"],
        scheduler=ShapeBucketScheduler(BucketPolicy((8, 16))))
    fifo = ServeEngine(cfg, params, max_len=32, slots=2, plans=plan,
                       hardware=HARDWARE_REGISTRY["tpu_v5e"])
    for eng in (bucketed, fifo):
        eng.add_request(np.asarray([5, 6, 7]), max_new_tokens=2)      # len 3
        eng.add_request(np.asarray([5, 6, 7, 8, 9, 1, 2, 3, 4, 5, 6]),
                        max_new_tokens=2)                             # len 11
        assert len(eng.run_until_done()) == 2

    # Decode tiles resolve exactly for both (same engine geometry).
    assert bucketed.metrics.plan_hit_rate("decode") == 1.0
    assert fifo.metrics.plan_hit_rate("decode") == 1.0
    # Prefill: bucketed pads 3->8 and 11->16 (compiled cells); FIFO's raw
    # lengths only nearest-shape resolve.
    assert bucketed.metrics.plan_hit_rate("prefill") == 1.0
    assert fifo.metrics.plan_hit_rate("prefill") == 0.0
    srcs = fifo.metrics.as_dict()["plan"]["by_phase"]["prefill"]
    assert srcs["nearest_shape"] > 0
    assert (bucketed.metrics.plan_hit_rate("prefill")
            > fifo.metrics.plan_hit_rate("prefill"))


# ---------------------------------------------------------------------------
# Tile plumbing: a resolved plan reaches the model's kernel call sites.
# ---------------------------------------------------------------------------

def test_tiles_reach_attention_call_site(monkeypatch):
    """api.prefill(tiles=...) must parameterize the attention lowering."""
    from repro.core.tiling import TileShape
    from repro.models import attention as attn_mod

    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": np.arange(8, dtype=np.int32)[None] + 2}
    seen = []
    real_ref = attn_mod.flash_attention_ref

    def spy(q, k, v, **kw):
        seen.append(kw.get("chunk"))
        return real_ref(q, k, v, **kw)

    monkeypatch.setattr(attn_mod, "flash_attention_ref", spy)
    tiles = {"flash_attention": TileShape((8, 4))}
    logits_t, _ = api.prefill(params, cfg, batch, max_len=16, tiles=tiles)
    assert 4 in seen                      # bkv -> reference KV chunk
    seen.clear()
    logits_d, _ = api.prefill(params, cfg, batch, max_len=16)
    assert seen and 4 not in seen         # default chunk path
    # Same math either way — the tile changes the lowering, not the result.
    np.testing.assert_allclose(np.asarray(logits_t), np.asarray(logits_d),
                               rtol=2e-5, atol=2e-5)


def test_engine_threads_resolved_tiles_into_prefill():
    """A plan-backed engine's per-bucket prefill consumes the plan's tile."""
    from repro.core import HARDWARE_REGISTRY
    from repro.models import attention as attn_mod
    from repro.serve import BucketPolicy, ServeEngine, ShapeBucketScheduler

    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    plan = _bucket_plan((16,), slots=2, max_len=32, hardware="tpu_v5e")
    exact = plan.lookup("flash_attention",
                        dict(sq=16, skv=16, d=cfg.head_dim_,
                             hq=cfg.n_heads, hkv=cfg.n_kv_heads, window=0),
                        "float32", "tpu_v5e")
    assert exact is not None

    seen = []
    real_ref = attn_mod.flash_attention_ref

    def spy(q, k, v, **kw):
        seen.append(kw.get("chunk"))
        return real_ref(q, k, v, **kw)

    eng = ServeEngine(cfg, params, max_len=32, slots=2, plans=plan,
                      hardware=HARDWARE_REGISTRY["tpu_v5e"],
                      scheduler=ShapeBucketScheduler(BucketPolicy((16,))))
    eng.add_request(np.asarray([5, 6, 7]), max_new_tokens=2)
    try:
        attn_mod.flash_attention_ref = spy
        eng.run_until_done()
    finally:
        attn_mod.flash_attention_ref = real_ref
    # The prefill trace saw the plan's bkv (clamped to seq 16) as its chunk.
    expect = min(exact.tile[1], 16)
    assert expect in seen


def test_launcher_exits_nonzero_on_fleet_exhausted(tmp_path, monkeypatch):
    """A fleet that cannot drain is a failed run, not a warning."""
    from repro.launch import compile_cache, serve
    from repro.serve import FleetExhausted, FleetRouter

    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cache"))

    def exhausted(self, max_steps=1000):
        raise FleetExhausted(max_steps, {"tpu_v5e": {"in_flight": 1,
                                                     "queued": 1}})

    monkeypatch.setattr(FleetRouter, "run_until_done", exhausted)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--fleet", "tpu_v5e", "--scheduler", "bucket",
                    "--requests", "2", "--new-tokens", "2"])
    assert exc.value.code not in (0, None)


def test_launcher_refuses_unusable_plan_artifact(tmp_path, monkeypatch):
    """An artifact that cannot be read is an error, never heuristics."""
    from repro.launch import compile_cache, serve

    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cache"))
    bad = tmp_path / "plans.json"
    bad.write_text('{"schema_version": 99, "entries": []}')
    with pytest.raises(SystemExit) as exc:
        serve.main(["--tile-plans", str(bad), "--requests", "1"])
    assert exc.value.code not in (0, None)
