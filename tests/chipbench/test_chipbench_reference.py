"""The plain reference against the program, on the CPU at small sizes.

Where the program's query heads need no padding (a multiple of 16), its
prefill logits agree with the reference's. The reference groups query
heads as published (head ``h`` reads key/value head ``h // (heads /
kv_heads)``), and it tells that grouping apart from the one a naive
padding of 12 query heads to 16 gives (``h // 8`` in place of ``h //
6``): a program that grouped its heads so could not pass a cell's check.
These tests read the benchmark's own code only, so they hold whichever
way the program lays out its padded heads.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import model, reference  # noqa: E402


def _conf(arch, heads, kv, bias, tied, window):
    return {"arch": arch, "hidden_act": "silu", "hidden_size": 64,
            "intermediate_size": 96, "num_hidden_layers": 2,
            "num_attention_heads": heads, "num_key_value_heads": kv,
            "head_dim": 8, "vocab_size": 128, "rms_norm_eps": 1e-6,
            "rope_theta": 10000.0, "sliding_window": window,
            "attention_bias": bias, "tie_word_embeddings": tied,
            "dtype": "float32"}


def _program_logits(cfg, params, tokens):
    """Every position's logits from the program's model forward."""
    from repro.models import transformer

    full = jax.jit(lambda p, t: transformer.forward(
        p, cfg, t, remat=False).logits)(params, jnp.asarray(tokens)[None])
    return np.asarray(full[0, :, :cfg.vocab_size], np.float32)


def _inputs(conf):
    """The arch config, the weights and 40 prompt tokens a test reads."""
    cfg = model.arch_config(conf)
    params = model.make_params(cfg, conf, seed=3)
    tokens = np.random.default_rng(0).integers(2, 128, 40).astype(np.int32)
    return cfg, params, tokens


def _distance(logits, ref):
    """Widest gap from ``ref``, relative to ``ref``'s largest logit."""
    return float(np.max(np.abs(logits - ref)) / np.max(np.abs(ref)))


def _compare(conf):
    cfg, params, tokens = _inputs(conf)
    ref = reference.logits(params, conf, tokens, np.arange(40))
    return _distance(_program_logits(cfg, params, tokens), ref), cfg


@pytest.mark.parametrize("arch,heads,kv,bias,tied,window", [
    ("h2o-danube-1.8b", 16, 4, False, False, 24),
    ("qwen2-1.5b", 16, 2, True, True, 0),
])
def test_program_agrees_with_reference_without_head_padding(
        arch, heads, kv, bias, tied, window):
    err, cfg = _compare(_conf(arch, heads, kv, bias, tied, window))
    assert cfg.padded_heads == heads
    assert err < 1e-4


def _reference_gap(conf, kv_map):
    """Relative distance of the reference's logits under ``kv_map`` from
    its logits with the default grouping."""
    _, params, tokens = _inputs(conf)
    want = np.arange(40)
    return _distance(
        reference.logits(params, conf, tokens, want, kv_map=kv_map),
        reference.logits(params, conf, tokens, want))


def test_reference_groups_heads_as_published():
    conf = _conf("qwen2-1.5b", 12, 2, True, True, 0)
    assert _reference_gap(conf, tuple(h // 6 for h in range(12))) < 1e-6


def test_reference_tells_padded_grouping_apart():
    """12 query heads padded to 16 and grouped 8 to a key/value head make
    heads 6 and 7 read key/value head 0, where the published model has
    them read head 1: the reference reads far off under that grouping."""
    conf = _conf("qwen2-1.5b", 12, 2, True, True, 0)
    assert _reference_gap(conf, tuple(h // 8 for h in range(12))) > 1e-2
