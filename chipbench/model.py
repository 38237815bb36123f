"""A configuration file as the program runs it, and its weights.

``arch_config`` turns ``configs/<config>.json`` (the published config's
keys) into the program's ``ArchConfig``: it starts from the program's own
registry entry for ``arch`` and sets every size and constant the program
exposes to the file's value, so the program runs the configuration as
stated. ``make_params`` draws the weights from the seed on the device, in
the program's parameter layout and the served dtype, in one jitted call.
``published_view`` maps that layout back to the published one (real heads
and vocabulary rows only, norm scales as ``1 + w``) for the plain
reference; it reads only the arrays this module made.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# Scales of the random weights. Every projection is N(0, 1/fan_in); the two
# that write into the residual stream (attention out, FF down) are further
# divided by sqrt(2 * layers), so the residual stream grows slowly with
# depth. The head gives logits of standard deviation about LOGIT_STD.
LOGIT_STD = 4.0
UNTIED_EMBED_STD = 1.0
NORM_STD = 0.1
BIAS_STD = 0.1


def key_seed(seed: int) -> int:
    """A 31-bit PRNG seed from any whole-number run seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def arch_config(conf: dict):
    from repro import configs

    base = configs.get_arch(conf["arch"])
    n_layers = int(conf["num_hidden_layers"])
    mixers = {spec.mixer for spec in base.layers()}
    if mixers - {"attn", "local_attn"} or base.moe is not None:
        raise ValueError(f"{conf['arch']}: only dense attention models")
    window = int(conf.get("sliding_window") or 0)
    mixer = "local_attn" if window else "attn"
    pattern = tuple(dataclasses.replace(base.layers()[0], mixer=mixer)
                    for _ in range(n_layers))
    return dataclasses.replace(
        base, n_layers=n_layers, d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]), d_ff=int(conf["intermediate_size"]),
        vocab_size=int(conf["vocab_size"]), attn_window=window,
        layer_pattern=pattern if base.layer_pattern else (),
        qkv_bias=bool(conf["attention_bias"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), act=conf["hidden_act"],
    ).validate()


def _std(name: str, conf: dict) -> float:
    d, layers = int(conf["hidden_size"]), int(conf["num_hidden_layers"])
    residual = 1.0 / math.sqrt(2 * layers)
    if name == "embed":
        return (LOGIT_STD / math.sqrt(d) if conf["tie_word_embeddings"]
                else UNTIED_EMBED_STD)
    if name == "lm_head":
        return LOGIT_STD / math.sqrt(d)
    if name.endswith("_w"):
        return NORM_STD
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name in ("wq", "wk", "wv", "w1", "w3"):
        return 1.0 / math.sqrt(d)
    if name == "wo":
        heads, hd = int(conf["num_attention_heads"]), int(conf["head_dim"])
        return residual / math.sqrt(heads * hd)
    if name == "w2":
        return residual / math.sqrt(int(conf["intermediate_size"]))
    raise ValueError(f"no weight scale for parameter {name!r}")


def make_params(cfg, conf: dict, seed: int):
    """The program-layout weights, drawn from ``seed`` on the device.
    Vocabulary rows past ``vocab_size`` (the program pads its vocabulary)
    are zero."""
    from repro.models import api

    dtype = jnp.dtype(conf["dtype"])
    shapes = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0), dtype))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    vocab = int(conf["vocab_size"])

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = path[-1].key
            arr = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                    dtype) * _std(name, conf)
            if name == "embed":
                arr = arr * (jnp.arange(leaf.shape[0]) < vocab)[:, None]
            elif name == "lm_head":
                arr = arr * (jnp.arange(leaf.shape[1]) < vocab)[None, :]
            out.append(arr.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(key_seed(seed)))


def published_view(params, conf: dict) -> dict:
    """The weights in the published layout, for the reference. Works on
    arrays or inside a trace."""
    heads, vocab = int(conf["num_attention_heads"]), int(conf["vocab_size"])
    [[seg]] = params["segments"]  # one scanned run of identical layers
    attn, ff = seg["attn"], seg["ff"]
    layers = {
        "norm1": 1.0 + seg["norm1_w"].astype(jnp.float32),
        "norm2": 1.0 + seg["norm2_w"].astype(jnp.float32),
        "wq": attn["wq"][:, :, :heads], "wk": attn["wk"], "wv": attn["wv"],
        "wo": attn["wo"][:, :heads],
        "w1": ff["w1"], "w3": ff["w3"], "w2": ff["w2"],
    }
    if conf["attention_bias"]:
        layers.update(bq=attn["bq"][:, :heads], bk=attn["bk"], bv=attn["bv"])
    embed = params["embed"][:vocab]
    head = (embed.T if conf["tie_word_embeddings"]
            else params["lm_head"][:, :vocab])
    return {"embed": embed, "head": head, "layers": layers,
            "final_norm": 1.0 + params["final_norm_w"].astype(jnp.float32)}
