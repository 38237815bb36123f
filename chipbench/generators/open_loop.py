"""Open-loop traffic: requests due on a fixed schedule, whatever the server does.

A mix file (``traffic/<mix>.json``) gives the arrival process and the
prompt and output length distributions. One sequence of requests (each
with its prompt length, output length and the gap to the next) is drawn
from the mix's own ``base_seed``, so every run seed offers the same work;
the run seed rotates that sequence, starting it at a request of its own,
and draws the prompts' token ids. A rotation keeps every burst of the
sequence together, so seeds differ in where the window starts and not in
how the requests cluster. The gaps are rescaled so that
``n = round(rate * seconds)`` requests fall due in ``[0, seconds)`` at
exactly the mean rate.

Distributions (each with optional ``min``/``max`` clipping for lengths):

- ``{"kind": "poisson"}``: exponential gaps;
- ``{"kind": "gamma", "cv": c}``: Gamma gaps with coefficient of variation
  ``c`` (shape ``1 / c**2``), bursty for ``c > 1``;
- ``{"kind": "lognormal", "median": m, "sigma": s}``;
- ``{"kind": "uniform", "min": lo, "max": hi}`` (integers, both ends in).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

# Token ids below this are never drawn: 0 is the scheduler's pad id.
FIRST_TOKEN_ID = 2


@dataclasses.dataclass
class Arrival:
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = spec["kind"]
    if kind == "poisson":
        return rng.exponential(1.0, n)
    if kind == "gamma":
        shape = 1.0 / float(spec["cv"]) ** 2
        return rng.gamma(shape, 1.0 / shape, n)
    raise ValueError(f"unknown arrival kind {kind!r}")


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = spec["kind"]
    if kind == "lognormal":
        x = np.exp(np.log(float(spec["median"]))
                   + float(spec["sigma"]) * rng.standard_normal(n))
    elif kind == "uniform":
        x = rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    else:
        raise ValueError(f"unknown length kind {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def length_range(spec: dict):
    """(shortest, longest) length a length spec can draw."""
    return int(spec["min"]), int(spec["max"])


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """The window's requests, in due order."""
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(int(mix["base_seed"]))
    gaps = _gaps(mix["arrivals"], n, base)
    prompts = _lengths(mix["prompt_tokens"], n, base)
    outputs = _lengths(mix["output_tokens"], n, base)
    gaps = gaps * (n / rate) / gaps.sum()
    # The run seed rotates the fixed sequence and draws the token ids.
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    start = int(rng.integers(n))
    gaps, prompts, outputs = (np.roll(x, -start)
                              for x in (gaps, prompts, outputs))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(float(due[i]),
                    rng.integers(FIRST_TOKEN_ID, vocab, int(prompts[i]),
                                 dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]
