"""Tiled matmul Pallas TPU kernel with tunable (bm, bk, bn) block shapes.

The canonical MXU kernel: grid (m/bm, n/bn, k/bk) with the contraction
dimension innermost ("arbitrary" semantics), f32 accumulator in VMEM scratch,
cast on the final k step. The right operand may be a stack of weights
``[L, K, N]`` (a scanned layer stack's): the layer to multiply by is a
scalar-prefetch index that the weight's block index map reads, so the
kernel streams that layer's blocks straight from the stack in HBM. A
sliced-out layer handed to a custom call is first copied whole by XLA
(into HBM, or into VMEM where it fits), which costs a weight read of its
own outside the kernel. The (bm, bk, bn) space is registered with the
tile autotuner — the LM stack asks the TilingPolicy for block shapes instead
of hard-coding them (the paper's methodology as infrastructure).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(layer_ref, a_ref, b_ref, out_ref, acc_ref, *, n_k: int):
    del layer_ref                     # read by the weight's index map only
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    tile: tuple[int, int, int] = (256, 512, 256),
    out_dtype=None,
    interpret: bool = False,
    layer=None,
) -> jnp.ndarray:
    """``a`` [M, K] @ ``b`` [K, N] -> [M, N] with block shapes (bm, bk, bn).
    With ``layer`` (an int32 scalar, traced or not), ``b`` is a stack
    [L, K, N] and the product is ``a @ b[layer]``, read from the stack."""
    if layer is None:
        if b.ndim != 2:
            raise ValueError(f"bad matmul weight {b.shape}: [K, N] expected")
        b, layer = b[None], 0
    if a.ndim != 2 or b.ndim != 3 or a.shape[1] != b.shape[1]:
        raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
    m, k = a.shape
    n = b.shape[2]
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = (min(t, s) for t, s in zip(tile, (m, k, n)))
    if m % bm or k % bk or n % bn:
        raise ValueError(f"tile {(bm, bk, bn)} must divide problem {(m, k, n)}")

    n_k = k // bk
    kernel = functools.partial(_matmul_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // bm, n // bn, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, l: (i, kk)),
                pl.BlockSpec((pl.squeezed, bk, bn),
                             lambda i, j, kk, l: (l[0], kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), a, b)
