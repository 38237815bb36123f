"""Ahead-of-time compiles of the served path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
compile qwen2-1.5b's kernels at its published widths in bf16, at the tiles
the serving plan resolves, for a described ``v5e:2x2`` topology, and check
that each program really contains the Pallas kernel (``tpu_custom_call``).
They catch what interpret-mode tests cannot: block shapes the chip's
compiler refuses (unaligned slices, too much VMEM).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, kernels
from repro.core.hardware import TPU_V5E
from repro.core.plans import compile_plan
from repro.launch.specs import kernel_problems

QWEN = configs.get_arch("qwen2-1.5b")
BUCKET, MAX_LEN, SLOTS = 512, 1024, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A persistent-cache entry written for a described chip cannot be read
    # back without one; keep these compiles out of any cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _plan_tile(kernel, batch, seq_len, kind):
    """The tile the analytic serving plan resolves for one qwen2-1.5b cell
    (what ``chip_smoke.py`` compiles and serves with)."""
    kernels.register_all()
    problem = kernel_problems(QWEN, batch, seq_len, kind)[kernel]
    plan = compile_plan([(kernel, problem, "bfloat16", TPU_V5E)])
    return tuple(plan.lookup(kernel, problem, "bfloat16",
                             TPU_V5E.name).tile.dims)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ff_program(m, tile, one_chip, monkeypatch):
    """The model's FF call site (both GEMM orientations, row padding) as
    the chip would run it: Pallas on, interpret off."""
    from repro.models import flags, transformer

    monkeypatch.setattr(flags, "pallas_enabled", lambda: True)
    monkeypatch.setattr(flags, "pallas_interpret", lambda: False)
    d, f = QWEN.d_model, QWEN.d_ff
    p = {"w1": _sds((d, f), jnp.bfloat16, one_chip),
         "w3": _sds((d, f), jnp.bfloat16, one_chip),
         "w2": _sds((f, d), jnp.bfloat16, one_chip)}
    x = _sds((1, m, d), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda p, x: transformer._dense_ff(p, QWEN, x, tile=tile))
    return fn.lower(p, x)


def _flash_attention_program(one_chip):
    from repro.kernels.flash_attention.flash_attention import flash_attention

    hd = QWEN.head_dim_
    q = _sds((1, QWEN.n_heads, BUCKET, hd), jnp.bfloat16, one_chip)
    kv = _sds((1, QWEN.n_kv_heads, BUCKET, hd), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, tile=(512, 512)))
    return fn.lower(q, kv, kv)


def _flash_decode_program(one_chip):
    from repro.kernels.flash_attention.decode import flash_decode

    (bkv,) = _plan_tile("flash_decode", SLOTS, MAX_LEN, "decode")
    hd = QWEN.head_dim_
    q = _sds((1, QWEN.n_heads, hd), jnp.bfloat16, one_chip)
    kv = _sds((1, QWEN.n_kv_heads, MAX_LEN, hd), jnp.bfloat16, one_chip)
    pos = _sds((), jnp.int32, one_chip)
    fn = jax.jit(lambda q, k, v, pos: flash_decode(q, k, v, pos=pos,
                                                   bkv=bkv))
    return fn.lower(q, kv, kv, pos)


@pytest.mark.parametrize("case", [
    "ff_prefill_512", "ff_prefill_packed_640", "ff_decode",
    "flash_attention_512", "flash_decode_1024",
])
def test_kernel_compiles_for_v5e(case, one_chip, monkeypatch):
    if case == "ff_prefill_512":
        lowered = _ff_program(
            BUCKET, _plan_tile("matmul", 1, BUCKET, "prefill"), one_chip,
            monkeypatch)
    elif case == "ff_prefill_packed_640":
        # A pack of a 512 and a 128 chunk: rows padded to the tile.
        lowered = _ff_program(
            640, _plan_tile("matmul", 1, BUCKET, "prefill"), one_chip,
            monkeypatch)
    elif case == "ff_decode":
        # The engine decodes one request per launch: m = 1.
        lowered = _ff_program(
            1, _plan_tile("matmul", SLOTS, MAX_LEN, "decode"), one_chip,
            monkeypatch)
    elif case == "flash_attention_512":
        lowered = _flash_attention_program(one_chip)
    else:
        lowered = _flash_decode_program(one_chip)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("skv", [64, 96, 128, 320, 1000, 1024, 1536, 4096])
def test_decode_cell_certifies_only_chip_legal_splits(skv):
    """Every bkv the flash_decode cell can certify (best tile and curve)
    divides the cache and is a lane multiple or the whole cache — the
    kernel's [1, bkv] kv_pos block is refused by the chip otherwise."""
    kernels.register_all()
    problem = dict(kernel_problems(QWEN, SLOTS, skv, "decode")
                   ["flash_decode"])
    plan = compile_plan([("flash_decode", problem, "bfloat16", TPU_V5E)])
    entry = plan.lookup("flash_decode", problem, "bfloat16", TPU_V5E.name)
    for (bkv,) in [tuple(entry.tile.dims)] + [d for d, _ in entry.curve]:
        assert skv % bkv == 0 and (bkv % 128 == 0 or bkv == skv), bkv
    # A nearest-shape resolution for another cache length stays legal.
    other = dict(problem, skv=skv * 3 // 2)
    res = plan.resolve("flash_decode", other, "bfloat16", TPU_V5E,
                       allow_transfer=False)
    if res is not None:
        (bkv,) = res.tile.dims
        assert other["skv"] % bkv == 0
        assert bkv % 128 == 0 or bkv == other["skv"]


@pytest.mark.parametrize("arch,program,start", [
    ("h2o-danube-1.8b", "decode", None), ("h2o-danube-1.8b", "chunk", 512),
    ("h2o-danube-1.8b", "chunk", 2048), ("qwen2-1.5b", "decode", None),
    ("qwen2-1.5b", "chunk", 512),
])
def test_paged_program_updates_pool_in_place_for_v5e(arch, program, start,
                                                     one_chip, monkeypatch):
    """The paged decode and chunk programs of h2o-danube-1.8b (head_dim 80)
    and qwen2-1.5b (head_dim 128), as the engine builds them at published
    widths in bf16 (4096 positions, 24 pages of 2048, a chunk inside a page
    and one at a page boundary), compiled for a v5e: they alias the donated
    pool and keep no pool-sized temporary, and the FF kernels read each
    layer's weights from the stack (no op makes a layer's [d, f] weight).
    The TPU compiler can turn a read of the request's pages into copies of
    the whole pool (an XLA gather did) where the CPU compiler shows
    nothing.

    Known and bounded here: qwen2-1.5b's chunk program relayouts its K
    pool at entry and exit (the loop carries K with rows minor, to match
    the chunk's projected keys), one K pool of temporaries; before the
    pool was updated in place it held the whole pool."""
    from repro.models import api, flags
    from repro.serve import ServeEngine

    cfg = configs.get_arch(arch)
    monkeypatch.setattr(flags, "pallas_enabled", lambda: True)
    monkeypatch.setattr(flags, "pallas_interpret", lambda: False)
    make_pool = api.make_paged_pool
    monkeypatch.setattr(api, "make_paged_pool",
                        lambda *a: jax.eval_shape(lambda: make_pool(*a)))
    kernels.register_all()
    cells = [(k, p, "bfloat16", TPU_V5E) for kind, batch, n in (
        ("decode", SLOTS, 4096), ("chunked_prefill", 1, BUCKET))
        for k, p in kernel_problems(cfg, batch, n, kind).items()]

    def sds(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = sds(jax.eval_shape(lambda: api.init_params(
        cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    eng = ServeEngine(cfg, params, max_len=4096, slots=SLOTS,
                      dtype=jnp.bfloat16, plans=compile_plan(cells),
                      hardware=TPU_V5E, paged=True, pool_pages=24,
                      page_size=2048)
    pool = sds(eng.pool.arrays)

    def nbytes(leaves):
        return sum(a.size * a.dtype.itemsize for a in leaves)

    pool_bytes = nbytes(jax.tree.leaves(pool))
    k_bytes = nbytes(jax.tree.leaves(jax.tree.map(
        lambda leaf: leaf["k_pages"], pool,
        is_leaf=lambda leaf: isinstance(leaf, dict) and "k_pages" in leaf)))
    state = sds(jax.eval_shape(lambda: api.make_paged_state(cfg,
                                                            eng.dtype)))
    table = _sds((eng.pool.n_pt,), jnp.int32, one_chip)
    if program == "decode":
        fn, toks = eng._decode_paged, _sds((1, 1), jnp.int32, one_chip)
    else:
        fn = eng._chunk_fn(4096, start)
        toks = _sds((1, BUCKET), jnp.int32, one_chip)
    compiled = fn.lower(params, toks, state, pool, table).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if arch == "qwen2-1.5b" and program == "chunk":
        assert mem.temp_size_in_bytes < k_bytes + pool_bytes / 16
    else:
        assert mem.temp_size_in_bytes < pool_bytes / 4
    d, f = cfg.d_model, cfg.d_ff
    assert not re.search(rf"\[({d},{f}|{f},{d})\]", compiled.as_text())
