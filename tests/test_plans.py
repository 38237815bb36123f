"""AOT tile plans: artifact round-trip, resolution order, hot-path no-sweep.

Covers the plan-store contract end to end: save/load with schema checking,
corrupt-file recovery, exact-hit vs nearest-shape vs cross-hardware
resolution (with the transfer warning), Autotuner cache interop, and the
acceptance property that ServeEngine/Trainer construction resolves tiles
from a compiled plan without ever invoking ``Autotuner.sweep``.
"""
import json
import warnings

import jax
import numpy as np
import pytest

import repro.kernels.flash_attention.ops  # noqa: F401  (registers kernels)
import repro.kernels.matmul.ops  # noqa: F401
from repro import configs
from repro.core import (
    PLAN_SCHEMA_VERSION, PRODUCTION_TARGET, TPU_V5E, TPU_V6E, Autotuner,
    TilingPolicy,
)
from repro.core.autotuner import Autotuner as AutotunerClass
from repro.core.plans import (
    PlanSchemaError, PlanTransferWarning, PlanVersionWarning, TilePlan,
    compile_plan,
)
from repro.core.tiling import TileShape
from repro.data.pipeline import DataConfig
from repro.launch import compile_plans as compile_plans_cli
from repro.launch.specs import kernel_problems
from repro.models import api
from repro.serve.engine import ServeEngine
from repro.train.trainer import Trainer, TrainerConfig

PROB = dict(m=1024, k=1024, n=1024)


@pytest.fixture(scope="module")
def plan():
    return compile_plan([
        ("matmul", PROB, "bfloat16", TPU_V5E),
        ("matmul", dict(m=2048, k=1024, n=1024), "bfloat16", TPU_V6E),
    ])


# -- artifact round-trip ----------------------------------------------------

def test_roundtrip(tmp_path, plan):
    path = str(tmp_path / "plans.json")
    plan.save(path)
    loaded = TilePlan.load(path)
    assert len(loaded) == len(plan) == 2
    orig = plan.lookup("matmul", PROB, "bfloat16", TPU_V5E.name)
    back = loaded.lookup("matmul", PROB, "bfloat16", TPU_V5E.name)
    assert back is not None and back.tile == orig.tile
    assert back.curve == orig.curve and back.curve  # full sensitivity curve
    assert json.load(open(path))["schema_version"] == PLAN_SCHEMA_VERSION


def test_corrupt_artifact_recovery(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(PlanSchemaError):
        TilePlan.load(str(bad))
    assert TilePlan.load_or_none(str(bad)) is None
    assert TilePlan.load_or_none(str(tmp_path / "missing.json")) is None
    assert TilePlan.load_or_none(None) is None


def test_schema_version_and_field_validation(tmp_path, plan):
    path = tmp_path / "stale.json"
    d = plan.to_dict()
    d["schema_version"] = PLAN_SCHEMA_VERSION + 1
    path.write_text(json.dumps(d))
    with pytest.raises(PlanSchemaError, match="schema version"):
        TilePlan.load(str(path))

    d = plan.to_dict()
    del d["entries"][0]["tile"]
    with pytest.raises(PlanSchemaError, match="missing field"):
        TilePlan.from_dict(d)

    d = plan.to_dict()
    d["entries"][0]["tile"] = [0, -1]
    with pytest.raises(PlanSchemaError, match="bad tile"):
        TilePlan.from_dict(d)


@pytest.mark.parametrize("old_version", [1, 2])
def test_old_schema_artifact_loads_with_warning(tmp_path, plan, old_version):
    """The v1 -> v2 (packed_prefill serving cells) and v2 -> v3 (refinement
    provenance) bumps are clean: old artifacts still load — entries intact,
    resolutions unchanged — but emit PlanVersionWarning so operators
    recompile."""
    path = tmp_path / f"v{old_version}.json"
    d = plan.to_dict()
    assert d["schema_version"] == PLAN_SCHEMA_VERSION == 3
    d["schema_version"] = old_version
    path.write_text(json.dumps(d))
    with pytest.warns(PlanVersionWarning,
                      match=f"old schema version {old_version}"):
        loaded = TilePlan.load(str(path))
    assert len(loaded) == len(plan)
    assert loaded.resolve("matmul", PROB, "bfloat16",
                          TPU_V5E).source == "exact"
    # load_or_none keeps the degrade-don't-crash contract for compat loads.
    with pytest.warns(PlanVersionWarning):
        assert TilePlan.load_or_none(str(path)) is not None


def test_type_malformed_entries_degrade_not_crash(tmp_path, plan):
    # Coercion failures (str score, ragged curve point) must be schema
    # errors so load_or_none degrades instead of crashing serve/train init.
    for mutate in (
        lambda es: es[0].__setitem__("score_s", "fast"),
        lambda es: es[0].__setitem__("curve", [[[1, 2, 3]]]),
        lambda es: es.__setitem__(0, 5),  # non-object entry
    ):
        d = plan.to_dict()
        mutate(d["entries"])
        path = tmp_path / "malformed.json"
        path.write_text(json.dumps(d))
        with pytest.raises(PlanSchemaError):
            TilePlan.load(str(path))
        assert TilePlan.load_or_none(str(path)) is None


# -- resolution order -------------------------------------------------------

def test_exact_hit(plan):
    res = plan.resolve("matmul", PROB, "bfloat16", TPU_V5E)
    assert res.source == "exact"
    assert res.tile == plan.lookup("matmul", PROB, "bfloat16",
                                   TPU_V5E.name).tile


def test_nearest_shape_same_hardware(plan):
    with warnings.catch_warnings():
        warnings.simplefilter("error", PlanTransferWarning)  # must not fire
        res = plan.resolve("matmul", dict(m=4096, k=1024, n=1024),
                           "bfloat16", TPU_V5E)
    assert res.source == "nearest_shape"
    assert res.distance > 0
    # The donor tile must be legal for the target problem (clamped).
    assert all(d <= m for d, m in zip(res.tile.dims, (4096, 1024, 1024)))


def test_cross_hardware_transfer_warns(plan):
    # v6e has no entry for PROB's shape family only on other hardware? It
    # does have m=2048 — so ask for a dtype/hw cell that only v5e covers.
    only_v5e = compile_plan([("matmul", PROB, "bfloat16", TPU_V5E)])
    with pytest.warns(PlanTransferWarning, match="not portable"):
        res = only_v5e.resolve("matmul", PROB, "bfloat16", TPU_V6E)
    assert res.source == "cross_hardware"
    assert res.donor_hardware == TPU_V5E.name
    assert np.isfinite(res.score_s)


def test_resolution_priority(plan):
    # Target (m=2048, v5e): the v6e entry matches the problem EXACTLY but
    # sits on other hardware; the v5e entry is a nearest-shape neighbour.
    # Same-hardware nearest-shape must win over cross-hardware exact.
    res = plan.resolve("matmul", dict(m=2048, k=1024, n=1024),
                       "bfloat16", TPU_V5E)
    assert res.source == "nearest_shape"
    assert res.entry.hardware == TPU_V5E.name


def test_resolve_unknown_kernel_returns_none(plan):
    assert plan.resolve("nope", dict(x=1), "bfloat16", TPU_V5E) is None


def test_fallbacks_can_be_disabled(plan):
    only_v5e = compile_plan([("matmul", PROB, "bfloat16", TPU_V5E)])
    assert only_v5e.resolve("matmul", PROB, "bfloat16", TPU_V6E,
                            allow_transfer=False) is None
    assert plan.resolve("matmul", dict(m=4096, k=1024, n=1024), "bfloat16",
                        TPU_V5E, allow_nearest=False,
                        allow_transfer=False) is None


# -- Autotuner / policy interop ---------------------------------------------

def test_autotuner_plan_lookup_skips_sweep(tmp_path, plan):
    cache = str(tmp_path / "cache.json")
    at = Autotuner(cache_path=cache, plans=plan)
    tile = at.best_tile("matmul", PROB, "bfloat16", TPU_V5E)
    assert at.sweep_count == 0
    assert tile == plan.resolve("matmul", PROB, "bfloat16", TPU_V5E).tile
    # The hit lands in the persistent cache tagged with its provenance...
    entry = at.cached()[Autotuner._key("matmul", PROB, "bfloat16",
                                       TPU_V5E.name)]
    assert entry["source"] == "plan:exact"
    # ...and a fresh plan-less Autotuner serves it from the cache file.
    at2 = Autotuner(cache_path=cache)
    assert at2.best_tile("matmul", PROB, "bfloat16", TPU_V5E) == tile
    assert at2.sweep_count == 0


def test_autotuner_does_not_persist_approximate_tiles(tmp_path):
    # Cross-hardware and nearest-shape tiles are provisional; they must not
    # enter the durable cache — even when a LATER exact hit flushes the
    # whole cache — so a corrected artifact wins after restart.
    cache = str(tmp_path / "cache.json")
    only_v5e = compile_plan([("matmul", PROB, "bfloat16", TPU_V5E)])
    at = Autotuner(cache_path=cache, plans=only_v5e)
    with pytest.warns(PlanTransferWarning):
        at.best_tile("matmul", PROB, "bfloat16", TPU_V6E)
    near_prob = dict(m=2048, k=1024, n=1024)
    at.best_tile("matmul", near_prob, "bfloat16", TPU_V5E)  # nearest_shape
    at.best_tile("matmul", PROB, "bfloat16", TPU_V5E)       # exact -> flush
    assert at.sweep_count == 0
    v6e_key = Autotuner._key("matmul", PROB, "bfloat16", TPU_V6E.name)
    near_key = Autotuner._key("matmul", near_prob, "bfloat16", TPU_V5E.name)
    v5e_key = Autotuner._key("matmul", PROB, "bfloat16", TPU_V5E.name)
    assert at.cached()[v6e_key]["source"] == "plan:cross_hardware"  # in-mem
    assert at.cached()[near_key]["source"] == "plan:nearest_shape"
    durable = json.load(open(cache))
    assert v5e_key in durable
    assert v6e_key not in durable and near_key not in durable


def test_autotuner_falls_back_to_sweep_off_plan(plan):
    at = Autotuner(plans=plan)
    at.best_tile("flash_attention",
                 dict(sq=512, skv=512, d=128, hq=4, hkv=4, window=0),
                 "bfloat16", TPU_V5E)
    assert at.sweep_count == 1  # kernel not in the plan: lazy tuning remains


def test_policy_consults_plans_first(plan):
    pol = TilingPolicy(mode="heuristic", hardware=TPU_V5E, plans=plan)
    assert pol.tile_for("matmul", PROB) == plan.resolve(
        "matmul", PROB, "bfloat16", TPU_V5E).tile


def test_policy_tuned_mode_cache_outranks_plan(plan):
    # Tuned mode goes through the autotuner so an exact cache entry (e.g. a
    # measured tile) is not shadowed by an approximate plan resolution.
    at = Autotuner(plans=plan)
    measured = TileShape((8, 128, 128))
    at._cache[Autotuner._key("matmul", PROB, "bfloat16",
                             TPU_V5E.name)] = {"tile": list(measured.dims)}
    pol = TilingPolicy(mode="tuned", hardware=TPU_V5E, autotuner=at,
                       plans=plan)
    assert pol.tile_for("matmul", PROB) == measured
    assert at.sweep_count == 0


def test_robust_mode_ignores_plans(plan):
    # Robust mode's contract is the fleet worst-case minimum; a plan entry
    # for one hardware model must not silently replace it.
    with_plans = TilingPolicy(mode="robust", fleet=(TPU_V5E, TPU_V6E),
                              hardware=TPU_V5E, plans=plan)
    without = TilingPolicy(mode="robust", fleet=(TPU_V5E, TPU_V6E),
                           hardware=TPU_V5E)
    assert with_plans.tile_for("matmul", PROB) == without.tile_for(
        "matmul", PROB)


# -- hot-path wiring: no sweep in serve/train -------------------------------

def _forbid_sweeps(monkeypatch):
    def boom(self, *a, **kw):
        raise AssertionError("Autotuner.sweep invoked on the hot path")
    monkeypatch.setattr(AutotunerClass, "sweep", boom)


def test_serve_engine_resolves_without_sweep(monkeypatch):
    cfg = configs.get_smoke("qwen2-1.5b")
    probs = kernel_problems(cfg, 2, 64, "decode")
    plan = _precompiled_plan(probs)      # AOT compile: sweeps happen HERE
    _forbid_sweeps(monkeypatch)          # ...and nowhere past this point
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_len=64, slots=2, plans=plan)
    assert set(engine.tiles) == set(probs)
    assert all(r.source == "exact"
               for r in engine.tile_resolutions.values())
    engine.add_request(np.asarray([5, 6, 7]), max_new_tokens=4)
    done = engine.run_until_done()
    assert len(done[0].out_tokens) == 4


def test_trainer_resolves_without_sweep(monkeypatch, tmp_path):
    cfg = configs.get_smoke("qwen2-1.5b")
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4)
    plan = _precompiled_plan(kernel_problems(cfg, 4, 32, "train"))
    _forbid_sweeps(monkeypatch)
    trainer = Trainer(
        cfg, data_cfg,
        TrainerConfig(steps=1, checkpoint_dir=str(tmp_path / "ck")),
        plans=plan)
    assert trainer.tiles and all(
        r.source == "exact" for r in trainer.tile_resolutions.values())


def test_trainer_tolerates_corrupt_plan_artifact(tmp_path):
    bad = tmp_path / "plans.json"
    bad.write_text("garbage")
    cfg = configs.get_smoke("qwen2-1.5b")
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=4)
    trainer = Trainer(
        cfg, data_cfg,
        TrainerConfig(steps=1, checkpoint_dir=str(tmp_path / "ck"),
                      tile_plans=str(bad)))
    assert trainer.tiles == {}  # degraded, not crashed


def _precompiled_plan(problems):
    jobs = [(k, p, "float32", PRODUCTION_TARGET)
            for k, p in problems.items()]
    return compile_plan(jobs)


# -- compile CLI ------------------------------------------------------------

def test_compile_plans_cli(tmp_path):
    out = str(tmp_path / "plans.json")
    compile_plans_cli.main([
        "--out", out, "--archs", "qwen2-1.5b",
        "--hardware", "tpu_v5e", "tpu_v6e", "--curve-cap", "8",
    ])
    plan = TilePlan.load(out)
    assert len(plan.kernels()) >= 3          # matmul, flash_attention, bilinear
    assert len(plan.hardware_names()) >= 2   # the acceptance floor
    for e in plan.entries():
        assert len(e.curve) <= 8
        assert e.tile.dims == e.curve[0][0]  # curve is score-sorted


def test_compile_plans_cli_serve_buckets(tmp_path):
    """--serve-buckets compiles the scheduler's prefill + decode cells."""
    out = str(tmp_path / "plans.json")
    compile_plans_cli.main([
        "--out", out, "--archs", "qwen2-1.5b", "--hardware", "tpu_v5e",
        "--dtypes", "float32", "--curve-cap", "4",
        "--serve-buckets", "16,32", "--serve-slots", "2",
        "--serve-max-len", "64",
    ])
    plan = TilePlan.load(out)
    assert plan.meta["serve_buckets"] == [16, 32]
    # Full-arch prefill cells for each edge (batch=1 -> m=edge tokens).
    cfg = configs.get_arch("qwen2-1.5b")
    for edge in (16, 32):
        assert plan.lookup(
            "matmul", dict(m=edge, k=cfg.d_model, n=cfg.d_ff),
            "float32", "tpu_v5e") is not None
    # Decode cell at the slot batch.
    assert plan.lookup(
        "matmul", dict(m=2, k=cfg.d_model, n=cfg.d_ff),
        "float32", "tpu_v5e") is not None


# -- decode cells: the paper's cross-model claim, asserted for decode --------

def _decode_prob(skv, b=4, d=128, hq=12, hkv=2, window=0):
    return dict(b=b, skv=skv, d=d, hq=hq, hkv=hkv, window=window)


DECODE_CACHE_LENS = (1024, 8192, 32768)


def test_decode_cells_pick_different_bkv_across_hardware():
    """Compile decode-cell plans for two modelled hardware targets and
    assert the cost model picks a different KV split for at least one cell
    — the paper's cross-model claim, now asserted for the decode kernel."""
    from repro.core.plans import compile_entry

    best = {}
    for hw in (TPU_V5E, TPU_V6E):
        for skv in DECODE_CACHE_LENS:
            entry = compile_entry("flash_decode", _decode_prob(skv),
                                  "float32", hw)
            best[(hw.name, skv)] = entry.tile[0]
    diverged = [skv for skv in DECODE_CACHE_LENS
                if best[("tpu_v5e", skv)] != best[("tpu_v6e", skv)]]
    assert diverged, f"no decode cell diverged across hardware: {best}"


def test_decode_cell_goldens():
    """Golden tiles: VMEM capacity bounds the split size per model (v6e has
    2x the VMEM of v5e, so its K/V double-buffer admits a 2x split), and
    small caches keep the whole-cache split (one DMA, no combine)."""
    from repro.core.plans import compile_entry

    expect = {
        ("tpu_v5e", 1024): 1024,
        ("tpu_v5e", 8192): 4096,
        ("tpu_v5e", 32768): 4096,
        ("tpu_v6e", 1024): 1024,
        ("tpu_v6e", 8192): 8192,
        ("tpu_v6e", 32768): 8192,
    }
    for (hw_name, skv), bkv in expect.items():
        hw = TPU_V5E if hw_name == "tpu_v5e" else TPU_V6E
        entry = compile_entry("flash_decode", _decode_prob(skv), "float32",
                              hw)
        assert entry.tile.dims == (bkv,), (
            f"{hw_name} skv={skv}: got {entry.tile}, want ({bkv},)")
        assert entry.dominant == "memory"      # decode is bandwidth-bound
        assert entry.sensitivity > 1.0         # the curve is not flat
        assert entry.curve[0][0] == entry.tile.dims


def test_decode_cells_resolve_for_serve_geometry():
    """kernel_problems' decode cells include flash_decode, and a plan
    compiled from them resolves exactly for the engine geometry."""
    cfg = configs.get_smoke("qwen2-1.5b")
    probs = kernel_problems(cfg, 2, 64, "decode")
    assert "flash_decode" in probs
    assert probs["flash_decode"]["skv"] == 64
    assert probs["flash_decode"]["b"] == 2
    assert "flash_attention" not in probs      # decode is its own kernel
    assert "flash_attention" in kernel_problems(cfg, 2, 64, "prefill")
    plan = _precompiled_plan(probs)
    res = plan.resolve("flash_decode", probs["flash_decode"], "float32",
                       PRODUCTION_TARGET)
    assert res is not None and res.source == "exact"
    assert 64 % res.tile[0] == 0               # legal split for the cache


# -- packed-prefill cells: pack width diverges per hardware model ------------

def _pack_prob(sq, d=128, hq=12, hkv=2, window=0):
    return dict(sq=sq, skv=sq, d=d, hq=hq, hkv=hkv, window=window)


PACK_BUCKET_EDGES = (512, 1024)


def test_packed_cells_pick_different_pack_width_across_hardware():
    """For the SAME bucket set, v5e and v6e compile different pack widths:
    VMEM bounds the resident packed query block, and v6e carries 2x the
    VMEM — the paper's per-model optimum on the pack-width tile axis."""
    from repro.core.plans import compile_entry

    best = {}
    for hw in (TPU_V5E, TPU_V6E):
        for sq in PACK_BUCKET_EDGES:
            entry = compile_entry("packed_prefill", _pack_prob(sq),
                                  "float32", hw)
            best[(hw.name, sq)] = entry.tile[0]
    diverged = [sq for sq in PACK_BUCKET_EDGES
                if best[("tpu_v5e", sq)] != best[("tpu_v6e", sq)]]
    assert diverged, f"no packed cell diverged across hardware: {best}"


def test_packed_cell_goldens():
    """Golden pack widths: the fixed per-step dispatch cost makes wider
    packs strictly cheaper until the resident pack block exhausts VMEM, so
    the optimum is the VMEM-bounded maximum — 2x wider on v6e (2x VMEM)
    than v5e for the same bucket edge."""
    from repro.core.plans import compile_entry

    expect = {
        ("tpu_v5e", 512): (2048, 256),
        ("tpu_v6e", 512): (4096, 256),
        ("tpu_v5e", 1024): (2048, 256),
        ("tpu_v6e", 1024): (4096, 256),
    }
    for (hw_name, sq), tile in expect.items():
        hw = TPU_V5E if hw_name == "tpu_v5e" else TPU_V6E
        entry = compile_entry("packed_prefill", _pack_prob(sq), "float32",
                              hw)
        assert entry.tile.dims == tile, (
            f"{hw_name} sq={sq}: got {entry.tile}, want {tile}")
        assert entry.tile[0] > sq            # pack spans > 1 segment
        assert entry.dominant == "memory"    # dispatch amortization regime
        assert entry.sensitivity > 1.0       # the curve is not flat
        assert entry.curve[0][0] == entry.tile.dims


def test_kernel_problems_packed_kind():
    """kind="packed_prefill" maps the attention cell onto the packed
    kernel (and nothing else changes vs prefill)."""
    cfg = configs.get_smoke("qwen2-1.5b")
    packed = kernel_problems(cfg, 1, 64, "packed_prefill")
    prefill = kernel_problems(cfg, 1, 64, "prefill")
    assert "packed_prefill" in packed
    assert "flash_attention" not in packed
    assert packed["packed_prefill"] == prefill["flash_attention"]
    assert packed["matmul"] == prefill["matmul"]


def test_serve_bucket_cells_include_packed():
    """compile_plans --serve-buckets sweeps a packed-prefill cell per
    bucket edge, so serving artifacts can resolve pack widths exactly."""
    from repro.launch.compile_plans import serve_bucket_cells

    cells = serve_bucket_cells(["qwen2-1.5b"], (16, 32), slots=2,
                               max_len=64, smoke=True)
    packed_sqs = {dict(p)["sq"] for k, p in cells if k == "packed_prefill"}
    assert packed_sqs == {16, 32}
    chunked_sqs = {dict(p)["sq"] for k, p in cells if k == "chunked_prefill"}
    assert chunked_sqs == {16, 32}


# -- kv_page cells: paged-pool page geometry diverges per hardware model -----

def _page_prob(skv, d=128, hkv=8):
    return dict(skv=skv, d=d, hkv=hkv)


KV_PAGE_CACHE_LENS = (1024, 8192, 32768)


def test_kv_page_cells_pick_different_page_across_hardware():
    """For the SAME cache length, v5e and v6e compile different KV page
    sizes: VMEM bounds the resident page a gather/append works on, and v6e
    carries 2x the VMEM — the paper's per-model tile optimum applied to
    the paged pool's page-geometry axis (serve/pool.py)."""
    from repro.core.plans import compile_entry

    best = {}
    for hw in (TPU_V5E, TPU_V6E):
        for skv in KV_PAGE_CACHE_LENS:
            entry = compile_entry("kv_page", _page_prob(skv), "bfloat16", hw)
            best[(hw.name, skv)] = entry.tile[0]
    diverged = [skv for skv in KV_PAGE_CACHE_LENS
                if best[("tpu_v5e", skv)] != best[("tpu_v6e", skv)]]
    assert diverged, f"no kv_page cell diverged across hardware: {best}"


def test_kv_page_cell_goldens():
    """Golden page sizes: larger pages amortize per-page table/DMA
    bookkeeping (fewer pages per request) until the resident page block
    exhausts the VMEM share — so the optimum is the VMEM-bounded maximum,
    2x larger on v6e (2x VMEM) than v5e at steady state, and a short cache
    keeps the whole-cache single page."""
    from repro.core.plans import compile_entry

    expect = {
        ("tpu_v5e", 1024): 1024,
        ("tpu_v5e", 8192): 1024,
        ("tpu_v5e", 32768): 1024,
        ("tpu_v6e", 1024): 1024,
        ("tpu_v6e", 8192): 2048,
        ("tpu_v6e", 32768): 2048,
    }
    for (hw_name, skv), page in expect.items():
        hw = TPU_V5E if hw_name == "tpu_v5e" else TPU_V6E
        entry = compile_entry("kv_page", _page_prob(skv), "bfloat16", hw)
        assert entry.tile.dims == (page,), (
            f"{hw_name} skv={skv}: got {entry.tile}, want ({page},)")
        assert entry.dominant == "memory"    # paging is a bandwidth story
        assert entry.sensitivity > 1.0       # the curve is not flat
        assert entry.curve[0][0] == entry.tile.dims


def test_kernel_problems_decode_includes_kv_page():
    """The kv_page cell rides the decode geometry (the steady-state page
    reader), so --serve-buckets artifacts sweep it with no extra flag."""
    from repro.launch.compile_plans import serve_bucket_cells

    cfg = configs.get_smoke("qwen2-1.5b")
    probs = kernel_problems(cfg, 2, 64, "decode")
    assert "kv_page" in probs
    assert probs["kv_page"]["skv"] == 64
    assert "kv_page" not in kernel_problems(cfg, 1, 64, "prefill")
    cells = serve_bucket_cells(["qwen2-1.5b"], (16, 32), slots=2,
                               max_len=64, smoke=True)
    assert {dict(p)["skv"] for k, p in cells if k == "kv_page"} == {64}


def test_paged_engine_reads_page_from_plan():
    """A paged ServeEngine built on a compiled plan adopts the resolved
    kv_page tile as its pool's page size — the plan actually shapes the
    pool, it is not just bookkeeping."""
    from repro.core.plans import compile_plan as _compile

    cfg = configs.get_smoke("qwen2-1.5b")
    probs = kernel_problems(cfg, 2, 64, "decode")
    plan = _compile([(k, p, "float32", PRODUCTION_TARGET)
                     for k, p in probs.items()])
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64, slots=2, plans=plan,
                      hardware=PRODUCTION_TARGET, paged=True)
    res = plan.resolve("kv_page", probs["kv_page"], "float32",
                       PRODUCTION_TARGET)
    assert res is not None and res.source == "exact"
    assert eng.pool is not None
    assert eng.pool.page == int(res.tile[0])
    assert eng.max_len % eng.pool.page == 0 or eng.pool.n_pt * \
        eng.pool.page >= eng.max_len


# -- wall-clock measure path -------------------------------------------------

def test_measure_fn_gated_off_without_tpu():
    """On a host backend make_measure_fn must return None (analytic
    fallback) and compile_plan with the factory must equal analytic."""
    from repro.launch.measure import make_measure_fn

    problem = dict(m=64, k=64, n=128)
    assert make_measure_fn("matmul", problem, "float32",
                           PRODUCTION_TARGET) is None
    analytic = compile_plan([("matmul", problem, "float32",
                              PRODUCTION_TARGET)])
    with_factory = compile_plan(
        [("matmul", problem, "float32", PRODUCTION_TARGET)],
        measure_fn_factory=make_measure_fn)
    assert with_factory.meta["measured_jobs"] == 0
    a = analytic.lookup("matmul", problem, "float32", PRODUCTION_TARGET.name)
    b = with_factory.lookup("matmul", problem, "float32",
                            PRODUCTION_TARGET.name)
    assert a.tile == b.tile and a.score_s == b.score_s


@pytest.mark.parametrize("kind,hw_name,expect", [
    ("TPU v5 lite", "tpu_v5e", True),
    ("TPU v5 lite", "tpu_v6e", False),
    ("TPU v6 lite", "tpu_v6e", True),
    ("TPU v99", "tpu_v5e", KeyError),
])
def test_measure_gate_follows_device_kind(kind, hw_name, expect,
                                          monkeypatch):
    """On a TPU backend only the descriptor the running chip's device_kind
    maps to is timed; an unknown kind is an error, never a default."""
    from types import SimpleNamespace

    from repro.core import HARDWARE_REGISTRY
    from repro.launch.measure import hardware_available

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda: [SimpleNamespace(device_kind=kind)])
    hw = HARDWARE_REGISTRY[hw_name]
    if expect is KeyError:
        with pytest.raises(KeyError):
            hardware_available(hw)
    else:
        assert hardware_available(hw) is expect


def test_measure_fn_drives_sweep_selection():
    """A measure_fn's wall-clock scores outrank the analytic model in
    compile_entry (the real-TPU path, exercised with a fake measurer)."""
    from repro.core.plans import compile_entry

    problem = dict(m=64, k=64, n=128)
    analytic_best = compile_entry("matmul", problem, "float32",
                                  PRODUCTION_TARGET).tile
    # Fake hardware: every tile is "measured" slow except one non-optimal
    # candidate, which must win over the analytic favorite.
    target = None

    def fake_measure(tile):
        nonlocal target
        if target is None and tile != analytic_best:
            target = tile
        return 1e-9 if tile == target else 1.0

    entry = compile_entry("matmul", problem, "float32", PRODUCTION_TARGET,
                          measure_fn=fake_measure)
    assert entry.tile == target
    assert entry.tile != analytic_best
    assert entry.score_s == 1e-9
