"""Tiled matmul Pallas kernel vs jnp oracle — shape/dtype/tile sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.matmul.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref


@pytest.mark.parametrize("mkn", [(64, 64, 64), (128, 256, 64), (32, 512, 128)])
@pytest.mark.parametrize("tile", [(32, 64, 32), (64, 128, 64)])
def test_shapes_tiles(mkn, tile):
    m, k, n = mkn
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32)
    out = matmul(a, b, tile=tile, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)])
def test_dtypes(dtype, tol):
    ka, kb = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(ka, (64, 128), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (128, 64), jnp.float32).astype(dtype)
    out = matmul(a, b, tile=(32, 64, 64), interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_k_accumulation_order():
    """Many k-steps accumulate in f32 regardless of input dtype."""
    ka, kb = jax.random.split(jax.random.PRNGKey(2))
    a = jax.random.normal(ka, (32, 1024), jnp.bfloat16)
    b = jax.random.normal(kb, (1024, 32), jnp.bfloat16)
    out = matmul(a, b, tile=(32, 128, 32), interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2, atol=3e-1)


def test_indivisible_raises():
    a = jnp.zeros((33, 64), jnp.float32)
    b = jnp.zeros((64, 64), jnp.float32)
    with pytest.raises(ValueError):
        matmul(a, b, tile=(32, 64, 64), interpret=True)


@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_weights_read_the_layer(layer):
    """With a stack of weights [L, K, N] and a layer index (traced, as a
    scanned layer stack passes it), the kernel multiplies by that layer."""
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(ka, (16, 128), jnp.float32)
    b = jax.random.normal(kb, (3, 128, 256), jnp.float32)
    run = jax.jit(lambda a, b, i: matmul(a, b, tile=(8, 64, 128),
                                         interpret=True, layer=i))
    out = run(a, b, jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_ref(a, b[layer])),
                               rtol=1e-4, atol=1e-4)


def test_scanned_stack_feeds_the_kernel_its_weights(monkeypatch):
    """In a scanned layer stack the FF kernel gets the stacked weights and
    the layer index (XLA never slices a layer out before the kernel), and
    the logits equal the einsum lowering's."""
    from repro import configs
    from repro.models import api
    from repro.models import transformer as T

    cfg = configs.get_smoke("qwen2-1.5b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    ref = T.forward(params, cfg, toks).logits
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    stacked = []
    dense_ff = T._dense_ff

    def spy(p, *args, layer=None, **kw):
        stacked.append(layer is not None and p["w1"].ndim == 3)
        return dense_ff(p, *args, layer=layer, **kw)

    monkeypatch.setattr(T, "_dense_ff", spy)
    tile = (8, cfg.d_model, cfg.d_ff)
    out = T.forward(params, cfg, toks, tiles={"matmul": tile}).logits
    assert stacked and all(stacked)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
