"""Plain reference forward pass of a dense decoder, from its published config.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``, with
no kernels, cache, paging, packing or batching: RMSNorm (the published
epsilon), rotary embeddings on the rotate-half convention with the
published ``rope_theta``, grouped-query attention in which query head
``h`` reads key/value head ``h // (heads / kv_heads)``, causal with the
published sliding window, SiLU-gated feed-forward, and the (tied or own)
output head. It imports nothing of the program and reads the weights only
through ``model.published_view``, inside its own jitted call.

``mode`` computes the same function in a lower precision, for the control
of the correctness check: ``"int8"`` rounds every projection's weights per
output channel and its inputs per token to 8-bit integers, ``"fp8"`` to
float8 e4m3 with the same scaling; attention and norms stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.model import published_view

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512      # attention is computed this many query rows at a time
LENGTH_STEP = 512      # inputs are padded at the end to a multiple of this
WANT_STEP = 128        # so are the positions read out
CONF_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "rms_norm_eps", "sliding_window", "rope_theta", "vocab_size",
             "attention_bias", "tie_word_embeddings")


def _quantize(x, axis, mode):
    """x rounded to ``mode`` with a symmetric scale per slice along
    ``axis`` (the reduced dimension), returned in float32."""
    if mode == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if mode == "fp8":
        # float8 e4m3: 3 mantissa bits, normal exponents down to -6 (below
        # that, steps of 2**-9), largest value 448; round to nearest.
        scale = jnp.maximum(amax, 1e-30) / 448.0
        y = x / scale
        exp = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
        step = 2.0 ** (exp - 3)
        return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * scale
    raise ValueError(f"unknown mode {mode!r}")


def _mm(x, w, mode):
    """x [..., k] @ w [k, n] in float32 at highest precision, with both
    operands first rounded to ``mode``."""
    x = _quantize(x.astype(jnp.float32), -1, mode)
    w = _quantize(w.astype(jnp.float32), 0, mode)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, kv_of_head):
    """Causal grouped-query attention. q [S, H, D], k/v [S, Hkv, D]."""
    s, _, d = q.shape
    k = k[:, kv_of_head]
    v = v[:, kv_of_head]
    kpos = jnp.arange(s)

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QUERY_BLOCK, 0)
        qpos = q0 + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k,
                            precision=HIGHEST) * d ** -0.5
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, s, QUERY_BLOCK))
    return out.reshape(s, *q.shape[1:])


@functools.partial(jax.jit, static_argnames=("conf_key", "mode", "kv_map"))
def _forward(params, tokens, want, *, conf_key, mode, kv_map):
    conf = dict(conf_key)
    weights = published_view(params, conf)
    heads, kv_heads = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, eps = conf["head_dim"], conf["rms_norm_eps"]
    window = conf["sliding_window"]
    kv_of_head = jnp.asarray(
        kv_map if kv_map is not None
        else [h // (heads // kv_heads) for h in range(heads)])
    s = tokens.shape[0]
    x = weights["embed"][tokens].astype(jnp.float32)

    def layer(x, w):
        h = _rms(x, w["norm1"], eps)
        q = _mm(h, w["wq"].reshape(h.shape[-1], -1), mode)
        k = _mm(h, w["wk"].reshape(h.shape[-1], -1), mode)
        v = _mm(h, w["wv"].reshape(h.shape[-1], -1), mode)
        q, k, v = (t.reshape(s, -1, hd) for t in (q, k, v))
        if "bq" in w:
            q = q + w["bq"].astype(jnp.float32)
            k = k + w["bk"].astype(jnp.float32)
            v = v + w["bv"].astype(jnp.float32)
        q = _rope(q, conf["rope_theta"])
        k = _rope(k, conf["rope_theta"])
        o = _attention(q, k, v, window, kv_of_head)
        x = x + _mm(o.reshape(s, heads * hd), w["wo"].reshape(heads * hd, -1),
                    mode)
        h = _rms(x, w["norm2"], eps)
        g = _mm(h, w["w1"], mode)
        u = _mm(h, w["w3"], mode)
        return x + _mm(jax.nn.silu(g) * u, w["w2"], mode), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rms(x[want], weights["final_norm"], eps)
    return _mm(x, weights["head"], mode)


def logits(params, conf: dict, tokens, want, mode: str = "f32",
           kv_map=None) -> np.ndarray:
    """Float32 logits [len(want), vocab] at the positions ``want`` of the
    sequence ``tokens`` (position p attends over positions 0..p), from the
    program-layout ``params`` that ``model.make_params`` drew.
    ``kv_map`` overrides which key/value head each query head reads."""
    tokens = np.asarray(tokens, np.int32)
    want = np.asarray(want, np.int32)
    n = len(want)
    tokens = np.pad(tokens, (0, -len(tokens) % LENGTH_STEP))
    want = np.pad(want, (0, -n % WANT_STEP), mode="edge")
    conf_key = tuple((k, conf[k] or 0) for k in CONF_KEYS)
    out = _forward(params, jnp.asarray(tokens), jnp.asarray(want),
                   conf_key=conf_key, mode=mode,
                   kv_map=None if kv_map is None else tuple(kv_map))
    return np.asarray(out)[:n]
