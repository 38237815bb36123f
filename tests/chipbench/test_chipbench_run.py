"""One run of the benchmark's harness on the CPU, at a tiny size.

The test steers past the harness's look for a chip (and the chip's peak
table and hardware descriptor) and drives the rest of a run: weights,
plan, engine, warm-up, window, reference check, result line. With a
served token altered where the engine produces it, the same run must come
out not correct.
"""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import run, spec  # noqa: E402

TINY = {
    "source": "test", "arch": "h2o-danube-1.8b", "model_type": "mistral",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 16,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
    "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "sliding_window": 64,
    "attention_bias": False, "tie_word_embeddings": False,
    "dtype": "bfloat16",
    "serve": {"slots": 4, "max_len": 128, "bucket_edges": [16, 32],
              "prefill_slots": 2, "step_token_budget": 0, "max_queue": 256,
              "pad_id": 0},
    "reduced": [], "assumed": [],
}
# qwen2's shape: bias on q, k and v, a tied head, no window. 16 query heads
# of 16, so no head is padded and the program groups them as published.
QWEN2 = dict(TINY, arch="qwen2-1.5b", model_type="qwen2",
             num_attention_heads=16, num_key_value_heads=2,
             sliding_window=0, attention_bias=True, tie_word_embeddings=True)
SHAPES = {"danube": TINY, "qwen2": QWEN2}
MIX = {"generator": "open_loop", "base_seed": 7,
       "arrivals": {"kind": "gamma", "cv": 2.0},
       "prompt_tokens": {"kind": "uniform", "min": 8, "max": 60},
       "output_tokens": {"kind": "uniform", "min": 8, "max": 16}}
PEAKS = {"flops_bf16_per_s": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = 0.1


@pytest.fixture
def tiny_cell(request, tmp_path, monkeypatch):
    """A one-cell benchmark at a tiny size, of the shape the test names
    (``danube`` unless parametrised)."""
    shape = SHAPES[getattr(request, "param", "danube")]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    files = {"BENCHMARK.json": bench, "chipbench/configs/tiny.json": shape,
             "chipbench/traffic/mix.json": MIX,
             "chipbench/cells/tiny.mix.json": {
                 "rate_per_s": 12.0,
                 "check": {"min_tokens": 200, "max_logit_gap": LIMIT}}}
    for name, data in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data))
    from repro.core import HARDWARE_REGISTRY, hardware

    monkeypatch.setattr(spec, "DATA_ROOT", tmp_path)
    monkeypatch.setattr(run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "peaks_for", lambda kind: PEAKS)
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    monkeypatch.setattr(hardware, "for_device_kind",
                        lambda kind: HARDWARE_REGISTRY["tpu_v5e"])
    return "tiny.mix"


def _run(cell, capsys, trace=0):
    assert run.main(["--workload", cell, "--seed", str(2**31 + 12345),
                     "--seconds", "2", "--trace", str(trace)]) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("tiny_cell", sorted(SHAPES), indirect=True)
def test_run_prints_a_result_line(tiny_cell, capsys):
    result, err = _run(tiny_cell, capsys)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert result["correct"], result["checks"]
    assert err.strip().splitlines()[-1].startswith("sampled_requests_short")


def test_altered_token_is_not_correct(tiny_cell, capsys, monkeypatch):
    from repro.serve import engine as engine_mod

    real_argmax = engine_mod.jnp.argmax
    calls = {"n": 0}

    def altered(x, *a, **k):
        calls["n"] += 1
        top = real_argmax(x, *a, **k)
        return (top + 1) % x.shape[-1] if calls["n"] % 7 == 0 else top

    monkeypatch.setattr(engine_mod.jnp, "argmax", altered)
    result, _ = _run(tiny_cell, capsys)
    assert not result["correct"]
    assert result["checks"]["max_logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("tiny_cell", sorted(SHAPES), indirect=True)
def test_traced_run_reports_per_layer_metrics(tiny_cell, capsys):
    result, _ = _run(tiny_cell, capsys, trace=1)
    # On the CPU the trace has no TPU plane: the host-clock and counter
    # readers report, the device-trace readers find nothing and are left
    # out.
    assert {"decode_batch_mean", "decode_step_ms", "compiles_in_window",
            "decode_mfu", "prefill_mfu"} <= set(result["metrics"])
    assert not {"matmul_roofline.decode", "matmul_roofline.prefill",
                "device_idle_share"} & set(result["metrics"])
    assert result["correct"], result["checks"]


def test_traced_stretch_holds_prefill_and_decode(tiny_cell, capsys,
                                                 monkeypatch):
    """The profiled stretch runs on until it has held a prefill step and a
    pure decode step, so the trace's readers of each phase find steps."""
    seen = {}
    real = run.per_layer

    def capture(cell, data):
        seen["data"] = data
        return real(cell, data)

    monkeypatch.setattr(run, "per_layer", capture)
    result, _ = _run(tiny_cell, capsys, trace=1)
    traced = [step for step, _ in seen["data"].traced]
    assert traced and run.both_phases(traced)
    assert result["device"]["window_s"] > 0


@pytest.mark.parametrize("seed", [3, 2**31 + 77, 4242])
def test_warm_up_leaves_nothing_to_compile(tiny_cell, capsys, seed):
    """Warm-up runs every program the cell's traffic can reach (every
    chunk, every packed layout), so no program is built in the window,
    whatever order the seed puts the traffic in."""
    assert run.main(["--workload", tiny_cell, "--seed", str(seed),
                     "--seconds", "3", "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["metrics"]["compiles_in_window"]["value"] == 0


def test_control_fails_the_limit(tiny_cell, capsys):
    """The reference in int8 and in fp8, put in the program's place, reads
    gaps above the cell's limit; the program's own tokens stay below."""
    from chipbench import control

    assert control.main(["--workload", tiny_cell, "--seeds", "5,6,7,8",
                         "--seconds", "2", "--modes", "int8,fp8"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 4
    for line in lines:
        print(line)
        assert line["served_max_gap"] <= LIMIT < line["fp8_max_gap"]
