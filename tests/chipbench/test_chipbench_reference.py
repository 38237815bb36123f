"""The plain reference against the program, on the CPU at small sizes.

Where the program's query heads need no padding (a multiple of 16), its
prefill logits agree with the reference's. Where they do (qwen2-1.5b's 12
heads are padded to 16), the program reads key/value head ``h // 8``
instead of the published ``h // 6``: its logits then disagree with the
reference and agree with a reference that groups heads as the program
does. That is the fault that keeps qwen2-1.5b out of the benchmark.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import control, model, reference  # noqa: E402


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


def _compare(conf, kv_map=None):
    cfg = model.arch_config(conf)
    params = model.make_params(cfg, conf, seed=3)
    tokens = np.random.default_rng(0).integers(2, 128, 40).astype(np.int32)
    prog = _program_logits(cfg, params, tokens)
    ref = reference.logits(params, conf, tokens, np.arange(40),
                           kv_map=kv_map)
    return float(np.max(np.abs(prog - ref)) / np.max(np.abs(ref))), cfg


@pytest.mark.parametrize("arch,heads,kv,bias,tied,window", [
    ("h2o-danube-1.8b", 16, 4, False, False, 24),
    ("qwen2-1.5b", 16, 2, True, True, 0),
])
def test_program_agrees_with_reference_without_head_padding(
        arch, heads, kv, bias, tied, window):
    err, cfg = _compare(_conf(arch, heads, kv, bias, tied, window))
    assert cfg.padded_heads == heads
    assert err < 1e-4


def test_padded_heads_read_the_wrong_kv_head():
    conf = _conf("qwen2-1.5b", 12, 2, True, True, 0)
    err, cfg = _compare(conf)
    assert cfg.padded_heads == 16
    assert err > 1e-2
    witness, _ = _compare(conf, kv_map=control.program_kv_map(cfg))
    assert witness < 1e-4
