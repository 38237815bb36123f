"""``chip_smoke.py`` on the CPU: its serving phase at smoke size with the
Pallas kernels in interpret mode, its refusal to run without a TPU, and the
compile-cache helper every entry point shares."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.hardware import TPU_V5E
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_runs_every_kernel_site(tmp_path, monkeypatch):
    """The smoke's serving phase, at smoke size: every request completes
    with all its tokens, and the flash_decode and FF matmul sites run the
    Pallas kernels (interpret mode) with no tile fallback."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    # Set: the helper then leaves JAX's (import-time) cache setting alone.
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "cache"))
    smoke = _load_smoke()
    shape = dict(buckets=(16, 32), max_len=64, slots=2)
    plans = smoke.compile_plan_artifact(TPU_V5E, tmp_path, full=False,
                                        dtype="float32", **shape)
    result, rows, failures = smoke.serve_phase(
        plans, TPU_V5E, full=False, dtype="float32", requests=3,
        new_tokens=3, prompt_len=(10, 30), **shape)
    assert failures == []
    assert len(result["requests"]) == 3
    assert all(len(r.out_tokens) == 3 for r in result["requests"])
    sites = {(r["kernel"], r["phase"]): r for r in rows}
    for site in smoke.KERNEL_SITES:
        assert sites[site]["impl"] == ["pallas"], sites[site]
        assert sites[site]["tile_fallback"] == 0
    for site in smoke.REFERENCE_SITES:
        assert sites[site]["impl"] == ["reference"], sites[site]


def test_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SMOKE)], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    lines = proc.stdout.strip().splitlines()
    if lines:
        with pytest.raises(json.JSONDecodeError):
            json.loads(lines[-1])


def test_compile_cache_dir_is_env_or_fixed(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/some/cache")
    assert compile_cache.compile_cache_dir() == "/some/cache"
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/cache"
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
    monkeypatch.delenv(compile_cache.ENV)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert Path(first) == ROOT / ".jax_cache"
