"""The reduction from a profiler trace to per-layer numbers, checked on a
small trace (``trace_excerpt.json``: two engine steps, with operation and
program names as a TPU v5e trace of the benchmark gives them, and the
expected numbers worked by hand)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace_reduce as tr  # noqa: E402

HERE = Path(__file__).resolve().parent


def _event(e, device=-1):
    return tr.Event(e["name"], e["start"], e["end"], device,
                    tuple(sorted(e.get("stats", {}).items())))


@pytest.fixture(scope="module")
def excerpt():
    data = json.loads((HERE / "trace_excerpt.json").read_text())
    profile = tr.Profile(
        [_event(e, 0) for e in data["ops"]],
        [_event(e, 0) for e in data["modules"]],
        [_event(e) for e in data["spans"]], 1)
    return profile, data["expected"]


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.length([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert tr.overlap([(0, 10)], [(1, 2), (5, 7)]) == 3


def test_busy_and_idle_share(excerpt):
    profile, want = excerpt
    assert tr.busy_seconds(profile) == pytest.approx(want["busy_s"])
    assert tr.idle_share(profile) == pytest.approx(want["idle_share"])


def test_kernel_time_by_name_and_phase(excerpt):
    profile, want = excerpt
    steps = tr.steps(profile)
    assert sorted(steps) == want["steps"]
    # Decode programs run the FF matmul on a decode batch of rows (mm.30:
    # 1 row), prefill programs on a chunk of prompt tokens (mm.7: 512).
    decode = tr.decode_programs(profile, rows=4)
    assert sorted(decode) == want["decode_programs"]
    for kernel in ("matmul", "flash_decode"):
        got = tr.kernel_seconds(profile, kernel, decode)
        assert got["decode"] == pytest.approx(want[kernel]["decode"])
        assert got["prefill"] == pytest.approx(want[kernel]["prefill"])


def test_out_rows_reads_the_result_shape():
    def op(name):
        return tr.Event(name, 0.0, 1.0, 0)

    assert tr.out_rows(op("%mm.30 = bf16[1,6912]{1,0:T(2,128)(2,1)S(1)} "
                          "custom-call(bf16[1,2560]{1,0} %p)")) == 1
    assert tr.out_rows(op("%mm.7 = bf16[2,512,6912]{2,1,0} "
                          "custom-call(bf16[2,512,2560] %p)")) == 1024
    assert tr.out_rows(op("no shape here")) == 0


def test_breakdown(excerpt):
    profile, want = excerpt
    top = tr.top_ops(profile, n=3)
    assert [name for name, _ in top] == want["top_ops"]
    gaps = dict(tr.idle_gaps(profile))
    for name, seconds in want["idle_gaps"].items():
        assert gaps[name] == pytest.approx(seconds)
