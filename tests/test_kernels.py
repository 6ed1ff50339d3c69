"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (the kernels run
in the Pallas interpreter on the CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


RNG = np.random.default_rng(42)


def _arr(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), jnp.dtype(dtype))


# --------------------------------------------------------------------------
@pytest.mark.parametrize("B,F", [(128, 64), (128, 256), (256, 96),
                                 (384, 512)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cos_xi", [0.0, 0.5, 0.866])
def test_cosine_weight(B, F, dtype, cos_xi):
    a, s, dz = _arr((B, F), dtype), _arr((B, F), dtype), _arr((B, F), dtype)
    w = ops.cosine_weight(a, s, cos_xi)
    w_ref = ref.cosine_weight_ref(a, s, cos_xi)
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(128, 32), (128, 4, 16), (256, 8, 8, 4)])
def test_weighted_cotangent(shape):
    a, s, dz = _arr(shape, "float32"), _arr(shape, "float32"), \
        _arr(shape, "float32")
    w, wdz = ops.weighted_cotangent(a, s, dz, 0.3)
    wdz_ref = ref.weighted_cotangent_ref(a, s, dz, 0.3)
    np.testing.assert_allclose(np.asarray(wdz), np.asarray(wdz_ref),
                               rtol=1e-4, atol=1e-5)


def test_cosine_weight_thresholding_exact_zero():
    a = jnp.ones((128, 8), jnp.float32)
    s = -jnp.ones((128, 8), jnp.float32)          # cos = -1 < any threshold
    w = ops.cosine_weight(a, s, 0.5)
    assert (np.asarray(w) == 0.0).all()


# --------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,hd", [(1, 256, 2, 64), (2, 512, 1, 32),
                                      (1, 1024, 2, 128)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (False, 0)])
def test_flash_attention(B, S, H, hd, causal, window):
    q, k, v = (_arr((B, S, H, hd), "float32") for _ in range(3))
    o = ops.flash_attention(q, k, v, causal=causal, window=window)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    q, k, v = (_arr((1, 256, 2, 64), "bfloat16") for _ in range(3))
    o = ops.flash_attention(q, k, v)
    o_ref = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        rtol=5e-2, atol=5e-2)


def test_flash_matches_model_blockwise_path():
    """The kernel and the model's _blockwise_sdpa agree (same oracle)."""
    from repro.models import layers as L
    B, S, H, hd = 1, 512, 2, 64
    q, k, v = (_arr((B, S, H, hd), "float32") for _ in range(3))
    pos = jnp.arange(S, dtype=jnp.int32)
    o_model = L._blockwise_sdpa(q, k, v, pos, pos, causal=True, window=0)
    o_kernel = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(7,), (1000,), (33, 17), (4, 5, 6),
                                   (1024, 96)])
@pytest.mark.parametrize("lr", [0.01, 0.1])
def test_fused_adagrad(shape, lr):
    g = _arr(shape, "float32")
    acc = jnp.abs(_arr(shape, "float32"))
    u, a2 = ops.fused_adagrad(g, acc, lr, 1e-10)
    ur, ar = ref.fused_adagrad_ref(g, acc, lr, 1e-10)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(ar), rtol=1e-6,
                               atol=1e-7)


def test_fused_adagrad_bf16_grad():
    g = _arr((256, 64), "bfloat16")
    acc = jnp.abs(_arr((256, 64), "float32"))
    u, a2 = ops.fused_adagrad(g, acc, 0.01, 1e-10)
    ur, ar = ref.fused_adagrad_ref(g, acc, 0.01, 1e-10)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur), rtol=1e-5,
                               atol=1e-6)


def test_optimizer_pallas_path_matches_plain():
    """adagrad(use_pallas=True) == adagrad() on a small pytree."""
    from repro.optim import adagrad, apply_updates
    params = {"w": _arr((64, 32), "float32"), "b": _arr((32,), "float32")}
    grads = {"w": _arr((64, 32), "float32"), "b": _arr((32,), "float32")}
    o1, o2 = adagrad(0.05), adagrad(0.05, use_pallas=True)
    s1, s2 = o1.init(params), o2.init(params)
    u1, s1 = o1.update(grads, s1)
    u2, s2 = o2.update(grads, s2)
    for k in params:
        np.testing.assert_allclose(np.asarray(u1[k]), np.asarray(u2[k]),
                                   rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------
# flash attention custom-VJP (forward + backward kernels)
# --------------------------------------------------------------------------
import jax  # noqa: E402


@pytest.mark.parametrize("B,S,H,hd,causal,window",
                         [(1, 256, 2, 64, True, 0),
                          (2, 512, 1, 32, True, 128),
                          (1, 256, 2, 64, False, 0)])
def test_flash_vjp_forward_and_backward(B, S, H, hd, causal, window):
    from repro.kernels.flash_attention_bwd import flash_attention_vjp
    q, k, v = (_arr((B, S, H, hd), "float32") for _ in range(3))
    o = flash_attention_vjp(q, k, v, causal, window)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)

    f_k = lambda *a: jnp.sum(jnp.sin(
        flash_attention_vjp(*a, causal, window)))
    f_r = lambda *a: jnp.sum(jnp.sin(
        ref.flash_attention_ref(*a, causal=causal, window=window)))
    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)
