"""Wall-clock tile measurement for plan compilation (real hardware only).

The paper timed every tile candidate on each GPU; the plan compiler defaults
to the analytic cost model because CI and laptops have no TPU. This module
supplies the paper-faithful path when real hardware *is* present:
``make_measure_fn`` returns a ``MeasureFn`` (tile -> seconds) that runs the
kernel's jitted Pallas op on synthetic operands with warmup, which the
autotuner then prefers over analytic scores (``SweepEntry.measured_s``
outranks ``cost.total_s``).

Gating: measurement requires the running jax backend to be a TPU whose
``device_kind`` maps to the very descriptor being measured (a v5e cannot
time a v6e cell, let alone a GTX260 one). On host backends it returns None
and the caller keeps the analytic model; a TPU backend that fails to
initialise raises.

``make_cell_timer`` wraps the same machinery as the *always-available*
timing path shared by plan compilation and the serving engines' shadow
execution (``repro.serve.refine``): wall-clock when the running chip is the
descriptor's, the analytic cost-model score otherwise.
"""
from __future__ import annotations

import logging
import time
from typing import Callable, Mapping, Optional

import numpy as np

from repro.core.hardware import HardwareModel
from repro.core.tiling import TileShape

log = logging.getLogger("repro.measure")

MeasureFn = Callable[[TileShape], float]


def _np_dtype(dtype: str):
    import jax.numpy as jnp

    return jnp.dtype(dtype)


def _matmul_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.matmul.ops import mm

    rng = np.random.default_rng(0)
    m, k, n = problem["m"], problem["k"], problem["n"]
    a = jnp.asarray(rng.standard_normal((m, k)), _np_dtype(dtype))
    b = jnp.asarray(rng.standard_normal((k, n)), _np_dtype(dtype))
    return lambda tile: mm(a, b, tile=tuple(tile))


def _flash_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import attend

    rng = np.random.default_rng(0)
    sq, skv, d = problem["sq"], problem["skv"], problem["d"]
    hq, hkv = problem["hq"], problem["hkv"]
    window = problem.get("window", 0) or None
    q = jnp.asarray(rng.standard_normal((1, hq, sq, d)), _np_dtype(dtype))
    k = jnp.asarray(rng.standard_normal((1, hkv, skv, d)), _np_dtype(dtype))
    v = jnp.asarray(rng.standard_normal((1, hkv, skv, d)), _np_dtype(dtype))
    return lambda tile: attend(q, k, v, window=window, tile=tuple(tile))


def _flash_decode_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.flash_attention.decode import split_legal
    from repro.kernels.flash_attention.ops import attend_decode

    rng = np.random.default_rng(0)
    b, skv, d = problem["b"], problem["skv"], problem["d"]
    hq, hkv = problem["hq"], problem["hkv"]
    window = problem.get("window", 0) or None
    dt = _np_dtype(dtype)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, hkv, skv, d)), dt)
    pos = jnp.asarray(skv - 1, jnp.int32)       # steady state: full cache

    def call(tile):
        if not split_legal(int(tile[0]), skv):
            # The Pallas kernel cannot run this split on the chip; score it
            # infeasible so the sweep never certifies a tile the serve
            # path would then reject.
            return None
        return attend_decode(q, k, v, pos=pos, window=window,
                             bkv=int(tile[0]))

    return call


def _ssd_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.ssd.ops import ssd

    rng = np.random.default_rng(0)
    s, h, p, n = problem["s"], problem["h"], problem["p"], problem["n"]
    dt = _np_dtype(dtype)
    x = jnp.asarray(rng.standard_normal((1, s, h, p)), dt)
    dts = jnp.asarray(rng.uniform(0.01, 0.1, (1, s, h)), dt)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, (h,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((1, s, n)), dt)
    C = jnp.asarray(rng.standard_normal((1, s, n)), dt)
    return lambda tile: ssd(x, dts, A, Bm, C, chunk=int(tile[0]))


def _rglru_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.rglru.ops import rglru

    rng = np.random.default_rng(0)
    s, f = problem["s"], problem["f"]
    dt = _np_dtype(dtype)
    x = jnp.asarray(rng.standard_normal((1, s, f)), dt)
    r = jnp.asarray(rng.uniform(0.0, 1.0, (1, s, f)), dt)
    i = jnp.asarray(rng.uniform(0.0, 1.0, (1, s, f)), dt)
    a = jnp.asarray(rng.standard_normal((f,)), jnp.float32)
    return lambda tile: rglru(x, r, i, a, tile=tuple(tile))


def _bilinear_call(problem: Mapping[str, int], dtype: str):
    import jax.numpy as jnp

    from repro.kernels.bilinear.ops import upscale

    rng = np.random.default_rng(0)
    src = jnp.asarray(
        rng.standard_normal((problem["src_h"], problem["src_w"])),
        _np_dtype(dtype))
    return lambda tile: upscale(src, problem["scale"], tile=tuple(tile))


_BUILDERS = {
    "matmul": _matmul_call,
    "flash_attention": _flash_call,
    "flash_decode": _flash_decode_call,
    "ssd": _ssd_call,
    "rglru": _rglru_call,
    "bilinear": _bilinear_call,
}


def hardware_available(hw: HardwareModel) -> bool:
    """True when the running chip is ``hw``: a TPU backend whose
    ``device_kind`` maps to that descriptor. Host backends are never any
    descriptor; an unknown TPU kind raises (``hardware.for_device_kind``)."""
    import jax

    from repro.core.hardware import for_device_kind

    if jax.default_backend() != "tpu":
        return False
    return for_device_kind(jax.devices()[0].device_kind).name == hw.name


def make_measure_fn(
    kernel: str,
    problem: Mapping[str, int],
    dtype: str,
    hw: HardwareModel,
    warmup: int = 2,
    iters: int = 5,
) -> Optional[MeasureFn]:
    """A tile -> wall-clock-seconds hook for one cell, or None.

    None (analytic path) when the running backend is not the chip ``hw``
    describes (see :func:`hardware_available`), or the kernel has no
    operand builder. A
    builder call may itself return None for a candidate its kernel cannot
    legally run (e.g. a non-dividing decode split); that candidate measures
    +inf and never wins the sweep.
    """
    if not hardware_available(hw):
        return None
    builder = _BUILDERS.get(kernel)
    if builder is None:
        log.info("no wallclock builder for kernel %r; analytic only", kernel)
        return None
    import math

    import jax

    call = builder(problem, dtype)

    def measure(tile: TileShape) -> float:
        for _ in range(warmup):  # first iteration compiles
            out = call(tile)
            if out is None:
                return math.inf
            jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = call(tile)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    return measure


def make_cell_timer(
    kernel: str,
    problem: Mapping[str, int],
    dtype: str,
    hw: HardwareModel,
    warmup: int = 1,
    iters: int = 3,
) -> MeasureFn:
    """The shared timing path for plan compilation AND shadow execution.

    Wall-clock via :func:`make_measure_fn` when the running chip is
    ``hw``; the analytic cost-model score otherwise.
    Unlike ``make_measure_fn`` (which returns None off-hardware so the
    compiler can distinguish measured from analytic artifacts), this always
    returns a callable — shadow steps must produce *a* comparable number on
    every backend, and on modelled-only targets that number is the same
    analytic score the plan was ranked by.
    """
    fn = make_measure_fn(kernel, problem, dtype, hw,
                         warmup=warmup, iters=iters)
    if fn is not None:
        return lambda tile: fn(TileShape(tuple(tile)))
    from repro.core.plans import score_tile

    return lambda tile: score_tile(kernel, TileShape(tuple(tile)),
                                   dict(problem), dtype, hw)
