"""The benchmark's yardstick on the CPU: traffic and work counts.

Each generator is a pure function of its seed and stays inside its mix's
ranges; every seed offers the same sequence of sizes and arrivals,
rotated. The work
counts match numbers worked by hand for qwen2-1.5b and h2o-danube-1.8b.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import spec  # noqa: E402
from chipbench.work import dense  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("mix_name", MIXES)
def test_generator_is_a_pure_function_of_its_seed(mix_name):
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{mix_name}.json")
                     .read_text())
    gen = spec.load_module("generators", mix["generator"])
    big = 2**31 + 977
    a = gen.schedule(mix, 2.0, 30.0, big, 32000)
    b = gen.schedule(mix, 2.0, 30.0, big, 32000)
    c = gen.schedule(mix, 2.0, 30.0, big + 1, 32000)
    assert len(a) == len(c) == 60
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    # Another seed: the same sizes and gaps, rotated to start elsewhere.
    def requests(arrivals):
        gaps = np.diff([x.due_s for x in arrivals] + [30.0])
        return [(len(x.prompt), x.max_new_tokens, round(g, 9))
                for x, g in zip(arrivals, gaps)]

    ra, rc = requests(a), requests(c)
    assert ra != rc
    assert any(ra == rc[k:] + rc[:k] for k in range(len(rc)))
    due = [x.due_s for x in a]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(x.prompt) <= hi for x in a)
    lo, hi = mix["output_tokens"]["min"], mix["output_tokens"]["max"]
    assert all(lo <= x.max_new_tokens <= hi for x in a)
    assert all(x.prompt.min() >= 2 and x.prompt.max() < 32000 for x in a)


def test_decode_step_work_danube():
    conf = _config("h2o-danube-1.8b")
    # FF per token: 2 flops x 3 GEMMs x 2560 x 6912 x 24 layers.
    assert dense.ff(conf, 1)[0] == 2_548_039_680
    # FF bytes of a 4-request step: weights once (3 x 2560 x 6912 x 2 B x
    # 24) plus 4 rows in and out (2 x 4 x 2560 x 2 B x 24).
    assert dense.ff(conf, 4)[1] == 2_548_039_680 + 983_040
    # Step of 4 requests at 100..400 cached positions: FF 4 x 2548039680,
    # projections 4 x 2 x (2560 x 48 x 80 + 32 x 80 x 2560) x 24,
    # head 4 x 2 x 2560 x 32000, attention 4 x 32 x 80 x 1000 x 24.
    ctx = (100, 200, 300, 400)
    assert dense.decode_step_flops(conf, ctx) == (
        10_192_158_720 + 3_145_728_000 + 655_360_000 + 245_760_000)
    # Keys and values read: 2 x 8 heads x 80 x 1000 positions x 2 B x 24.
    assert dense.decode_attention(conf, ctx)[1] == 61_440_000


def test_prefill_chunk_work_qwen2():
    conf = _config("qwen2-1.5b")
    # One unpadded 512-token prompt, one chunk: FF 512 x 6 x 1536 x 8960
    # x 28, projections 512 x 2 x (1536 x 16 x 128 + 12 x 128 x 1536) x 28,
    # causal attention 4 x 12 x 128 x (512 x 513 / 2) x 28, head once
    # (2 x 1536 x 151936).
    assert dense.prefill_flops(conf, [(512, 0, 512, 0)]) == (
        1_183_800_360_960 + 157_840_048_128 + 22_592_618_496
        + 466_747_392)
    # The same chunk as the second half of a 1024 bucket whose first 600
    # positions are pads: 424 real tokens at positions 600..1023 attending
    # over 1..424 real keys; no head (the prompt ends there, so it counts).
    real = dense.real_tokens(512, 512, 600)
    assert real == 424
    got = dense.prefill_flops(conf, [(1024, 512, 512, 600)])
    assert got == (424 * 2_312_110_080 + 424 * 308_281_344
                   + 172_032 * (424 * 425 // 2) + 466_747_392)


def test_least_seconds_is_the_larger_bound():
    peaks = {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert dense.least_seconds((197e12, 1.0), peaks) == pytest.approx(1.0)
    assert dense.least_seconds((1.0, 819e9), peaks) == pytest.approx(1.0)
