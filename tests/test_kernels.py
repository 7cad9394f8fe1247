"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import quantize
from repro.kernels import hgq_quantize, pack_weights, qmatmul_any
from repro.kernels.hgq_quantize.ref import hgq_quantize_ref
from repro.kernels.qmatmul.ref import pack_ref, qmatmul_ref

KEY = jax.random.PRNGKey(7)

QUANT_SHAPES = [((64, 256), ()), ((64, 256), (256,)), ((64, 256), (64, 256)),
                ((3, 5, 100), ()), ((3, 5, 100), (100,)), ((7,), (7,)),
                ((33, 130), (130,)), ((1, 128), (1, 128)), ((2, 2, 2, 64), ())]


@pytest.mark.parametrize("shape,fshape", QUANT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hgq_quantize_matches_ref(shape, fshape, dtype):
    x = (jax.random.normal(KEY, shape) * 4).astype(dtype)
    f = jax.random.uniform(KEY, fshape, minval=-1, maxval=8) if fshape \
        else jnp.float32(3.7)
    got = hgq_quantize(x, jnp.asarray(f), interpret=True)
    want = hgq_quantize_ref(x, jnp.broadcast_to(jnp.asarray(f), x.shape))
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_hgq_quantize_grads_match_algorithm1():
    x = jax.random.normal(KEY, (8, 128))
    f = jnp.full((128,), 3.0)
    gx_k = jax.grad(lambda v: jnp.sum(hgq_quantize(v, f, interpret=True)))(x)
    gx_c = jax.grad(lambda v: jnp.sum(quantize(v, f)))(x)
    np.testing.assert_allclose(gx_k, gx_c)
    gf_k = jax.grad(lambda v: jnp.sum(hgq_quantize(x, v, interpret=True)))(f)
    gf_c = jax.grad(lambda v: jnp.sum(quantize(x, v)))(f)
    np.testing.assert_allclose(gf_k, gf_c, rtol=1e-5, atol=1e-6)


MM_SHAPES = [(8, 128, 128), (16, 256, 384), (5, 100, 77), (1, 896, 1024),
             (17, 900, 300), (128, 512, 256)]


@pytest.mark.parametrize("M,K,N", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_matches_ref(M, K, N, dtype):
    x = (jax.random.normal(KEY, (M, K)) * 0.5).astype(dtype)
    w = jax.random.normal(KEY, (K, N)) * 0.1
    f = jax.random.uniform(KEY, (N,), minval=2, maxval=7)
    wi, s = pack_weights(w, f)
    got = qmatmul_any(x, wi, s, interpret=True)
    want = qmatmul_ref(x, wi, s)
    assert got.dtype == x.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_pack_weights_representable():
    """Packing at the trained bits keeps every quantized weight exact when
    |w| < 2^(7-f) (int8 mantissa range)."""
    w = jax.random.normal(KEY, (64, 32)) * 0.25
    f = jnp.full((32,), 6.0)
    wi, s = pack_weights(w, f)
    wq = wi.astype(jnp.float32) * s[None, :]
    from repro.core.quantizer import quantize_inference
    np.testing.assert_allclose(wq, quantize_inference(w, jnp.float32(6.0)),
                               atol=1e-7)


def test_pack_per_parameter_uses_channel_max():
    w = jnp.ones((4, 2)) * 0.25
    f = jnp.array([[2., 1.], [6., 1.], [2., 1.], [2., 1.]])
    wi, s = pack_weights(w, f)
    assert float(s[0]) == 2.0 ** -6  # max f in channel 0
    assert float(s[1]) == 2.0 ** -1


# --------------------------- sub-8-bit widths ------------------------------

@pytest.mark.parametrize("bits", [4, 5, 8])
def test_pack_ref_clips_to_width_grid(bits):
    """Sub-8-bit grids clip symmetrically to +-(2^(b-1)-1) so nibble
    packing and error feedback never see the asymmetric minimum; int8
    keeps the full (-128, 127) range."""
    w = jnp.linspace(-4.0, 4.0, 64).reshape(32, 2)
    f = jnp.full((2,), 6.0)
    m, s = pack_ref(w, f, bits)
    lo, hi = (-128, 127) if bits == 8 else \
        (-(2 ** (bits - 1) - 1), 2 ** (bits - 1) - 1)
    assert m.dtype == jnp.int8
    assert int(m.min()) == lo and int(m.max()) == hi


@pytest.mark.parametrize("bits", [4, 6, 8])
def test_pack_linear_caps_channel_to_width(bits):
    """pack_linear never saturates at any width: the per-channel grid
    cap shrinks 2^-f until the channel amax fits the b-wide mantissa,
    so dequant error stays within half a step everywhere."""
    from repro.kernels.qmatmul.ops import pack_linear
    w = jax.random.normal(KEY, (32, 16))
    m, s = pack_linear(w, None, bits)
    qmax = 127 if bits == 8 else 2 ** (bits - 1) - 1
    assert int(jnp.max(jnp.abs(m))) <= qmax
    err = jnp.abs(m.astype(jnp.float32) * s[None, :] - w)
    assert float(jnp.max(err - s[None, :] / 2)) <= 1e-6


def test_qmatmul_batched():
    x = jax.random.normal(KEY, (2, 3, 256))
    w = jax.random.normal(KEY, (256, 128)) * 0.1
    wi, s = pack_weights(w, jnp.float32(6.0))
    got = qmatmul_any(x, wi, s, interpret=True)
    want = qmatmul_ref(x.reshape(-1, 256), wi, s).reshape(2, 3, 128)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
