"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests the engine finished
in it is drawn from the run seed: the one with the most served tokens,
then others in a seeded order until the sample holds ``min_tokens``
served tokens. For each, the reference runs once over the prompt as the
engine prefilled it (left-padded with the pad id to its admitted length,
by the bucket rule of the configuration's serving settings) followed by
the served tokens, and reads the logits of every position that produced a
served token. A served token's gap is how far its reference logit lies
below the reference's best logit at that position; the check compares
the widest gap of the sample with the cell's limit. Greedy decoding gives
gap 0 up to rounding. ``control_gaps`` reads the same gap for the token
that a lower-precision reference would put first.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from chipbench import reference


def admitted_length(n: int, edges) -> int:
    """The bucket rule: the smallest edge that covers ``n``, else the
    smallest multiple of the largest edge."""
    for edge in edges:
        if n <= edge:
            return edge
    return math.ceil(n / edges[-1]) * edges[-1]


def sample(served, seed: int, min_tokens: int) -> List:
    """Finished requests: the longest, then a seeded order, up to
    ``min_tokens`` served tokens."""
    done = [s for s in served if s.req.done]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.req.out_tokens), -s.rid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    picked, total = [longest], len(longest.req.out_tokens)
    for i in rng.permutation(len(rest)):
        if total >= min_tokens:
            break
        picked.append(rest[i])
        total += len(rest[i].req.out_tokens)
    return picked


def sequence(prompt, tokens, serve: dict):
    """(reference input, positions whose logits produced ``tokens``)."""
    prompt = np.asarray(prompt, np.int32)
    pad = admitted_length(len(prompt), serve["bucket_edges"]) - len(prompt)
    seq = np.concatenate([np.full(pad, serve["pad_id"], np.int32), prompt,
                          np.asarray(tokens[:-1], np.int32)])
    first = pad + len(prompt) - 1
    return seq, np.arange(first, first + len(tokens))


def gaps(params, conf: dict, picked, control_modes=()) -> Dict[str, list]:
    """Per-request arrays of gaps: ``"served"`` for the served tokens, and
    one entry per control mode for the token that mode puts first."""
    out = {"served": []}
    out.update({m: [] for m in control_modes})
    for s in picked:
        tokens = list(s.req.out_tokens)
        seq, want = sequence(s.req.prompt, tokens, conf["serve"])
        ref = reference.logits(params, conf, seq, want)
        best = ref.max(axis=-1)
        out["served"].append(best - ref[np.arange(len(tokens)), tokens])
        for mode in control_modes:
            low = reference.logits(params, conf, seq, want, mode=mode)
            pick = low.argmax(axis=-1)
            out[mode].append(best - ref[np.arange(len(tokens)), pick])
    return out


def verdict(picked, gap_arrays, limit: float):
    """(correct, numbers compared): each number with its limit. An empty
    sample reads an infinite gap."""
    short = sum(len(s.req.out_tokens) != s.max_new_tokens for s in picked)
    widest = max((float(g.max()) for g in gap_arrays), default=math.inf)
    checks = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "sampled_requests_short": {"value": short, "limit": 0},
    }
    return widest <= limit and short == 0, checks
