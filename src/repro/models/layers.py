"""Model primitives: param definitions, norms, RoPE, activations, linear.

Parameters are plain pytrees (nested dicts of arrays). Every parameter is
declared as a :class:`ParamDef` carrying its *logical* sharding axes; the
distributed layer maps logical axes -> mesh axes, so model code never names
mesh axes directly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axes, len == len(shape)
    init: str = "fan_in"              # fan_in | normal | zeros | ones
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def init_tree(defs: Dict[str, Any], key: jax.Array, dtype) -> Dict[str, Any]:
    """Materialize a nested dict of ParamDefs into arrays (deterministic).

    Every array is drawn in ``dtype`` itself, so a bf16 tree never passes
    through a float32 copy (``jax.jit`` it to build the tree on device)."""
    flat, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )
    out = []
    for i, d in enumerate(flat):
        k = jax.random.fold_in(key, i)
        if d.init == "zeros":
            arr = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            arr = jnp.ones(d.shape, dtype)
        elif d.init == "normal":
            arr = jax.random.normal(k, d.shape, dtype) * d.scale
        elif d.init == "fan_in":
            fan_in = d.shape[0] if len(d.shape) > 1 else d.shape[0]
            std = d.scale / math.sqrt(max(fan_in, 1))
            arr = jax.random.normal(k, d.shape, dtype) * std
        else:
            raise ValueError(f"unknown init {d.init}")
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def axes_tree(defs: Dict[str, Any]) -> Dict[str, Any]:
    """The parallel pytree of logical-axes tuples."""
    return jax.tree.map(
        lambda d: d.axes, defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6, offset: float = 1.0):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (offset + w.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True)}[name]


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x, positions, theta: float = 10000.0):
    """x [..., S, H, D] (or [..., S, D]); positions [..., S] int32."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                              # [D/2]
    ang = positions[..., None].astype(jnp.float32) * inv    # [..., S, D/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if x.ndim == positions.ndim + 2:                        # head axis present
        sin, cos = sin[..., None, :], cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
