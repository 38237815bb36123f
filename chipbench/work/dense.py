"""Operations and bytes of a dense decoder's serving steps.

``conf`` is a configuration file (``configs/<config>.json``); every count
follows the published shapes (real heads and vocabulary, no padding) and
the served dtype. Each function returns ``(flops, bytes)``: the least work
the step needs, with every weight read once per step, whatever the number
of requests in it, and activations read and written once. A multiply-add
is two operations.
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(conf: dict):
    d = conf["hidden_size"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    return (d, conf["intermediate_size"], heads, kv, hd,
            conf["num_hidden_layers"], conf["vocab_size"])


def dtype_bytes(conf: dict) -> int:
    return DTYPE_BYTES[conf["dtype"]]


def ff(conf: dict, tokens: int):
    """The SwiGLU feed-forward GEMMs of every layer over ``tokens`` rows:
    weights once, the input rows read and the output rows written."""
    d, f, _, _, _, layers, _ = _dims(conf)
    b = dtype_bytes(conf)
    flops = 2 * 3 * d * f * tokens * layers
    nbytes = (3 * d * f + 2 * tokens * d) * b * layers
    return flops, nbytes


def attention_proj(conf: dict, tokens: int):
    """The q, k, v and output projections of every layer."""
    d, _, heads, kv, hd, layers, _ = _dims(conf)
    b = dtype_bytes(conf)
    params = d * (heads + 2 * kv) * hd + heads * hd * d
    flops = 2 * params * tokens * layers
    nbytes = (params + 2 * tokens * d) * b * layers
    return flops, nbytes


def head(conf: dict, tokens: int):
    """The output head over ``tokens`` rows."""
    d, _, _, _, _, _, vocab = _dims(conf)
    b = dtype_bytes(conf)
    return 2 * d * vocab * tokens, (d * vocab + tokens * d) * b + \
        tokens * vocab * b


def decode_attention(conf: dict, contexts):
    """Attention of one decoded token per entry of ``contexts`` (the cache
    positions each attends over): every layer reads those positions' keys
    and values once."""
    d, _, heads, kv, hd, layers, _ = _dims(conf)
    b = dtype_bytes(conf)
    positions = int(sum(contexts))
    flops = 4 * heads * hd * positions * layers
    nbytes = 2 * kv * hd * positions * b * layers
    return flops, nbytes


def prefill_attention(conf: dict, start: int, tokens: int, pad: int = 0):
    """Causal attention of the prompt positions ``start..start+tokens-1``
    over the unpadded prompt: position p attends over the real positions
    ``pad..p`` (the window, where one is stated, bounds them)."""
    _, _, heads, kv, hd, layers, _ = _dims(conf)
    b = dtype_bytes(conf)
    window = conf.get("sliding_window") or 0
    pos = np.arange(max(start, pad), start + tokens)
    keys = pos - pad + 1
    if window:
        keys = np.minimum(keys, window)
    flops = 4 * heads * hd * int(keys.sum()) * layers
    nbytes = 2 * kv * hd * int(start + tokens - pad) * b * layers
    return flops, nbytes


def decode_step_flops(conf: dict, contexts) -> int:
    """Model operations of one decode step of ``len(contexts)`` requests."""
    n = len(contexts)
    return (ff(conf, n)[0] + attention_proj(conf, n)[0] + head(conf, n)[0]
            + decode_attention(conf, contexts)[0])


def real_tokens(start: int, take: int, pad: int) -> int:
    """Prompt tokens of ``start..start+take-1`` past the ``pad`` left pads."""
    return take - max(0, min(pad, start + take) - start)


def prefill_flops(conf: dict, segments) -> int:
    """Model operations of the unpadded prompt tokens of ``segments``
    ((admitted length, start, tokens, pad) each); the head counts once per
    prompt, on the segment that ends it."""
    total = 0
    for admit, start, take, pad in segments:
        real = real_tokens(start, take, pad)
        total += (ff(conf, real)[0] + attention_proj(conf, real)[0]
                  + prefill_attention(conf, start, take, pad)[0])
        if start + take == admit:
            total += head(conf, 1)[0]
    return total


def least_seconds(work, peaks: dict) -> float:
    """The roofline bound of ``(flops, bytes)``: the longer of the compute
    time at the bf16 peak and the memory time at the HBM bandwidth."""
    flops, nbytes = work
    return max(flops / peaks["flops_bf16_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
