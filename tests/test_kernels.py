"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

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


def _stored(m, nibbles, transposed):
    """Int8 mantissas ``[K, N]`` in the layout the packed tree stores:
    ``[N, K]`` for a tied table, nibbles along the stored rows."""
    from repro.kernels.qmatmul.ops import pack_nibbles
    w = m.T if transposed else m
    return pack_nibbles(w, axis=0) if nibbles else w


MM_SHAPES = [(8, 128, 128), (16, 256, 384), (5, 100, 77), (1, 896, 1024),
             (17, 900, 300), (128, 512, 256)]
# nibble-stored [K/2, N] weights at a small K and qwen2-0.5b's K (896 for
# q/k/v/o/gate/up, 4864 for down), at decode and prefill row counts
NIB_SHAPES = [(M, K, N) for K, N in ((64, 40), (896, 256), (4864, 128))
              for M in (1, 16, 32)]
MM_CASES = ([pytest.param(*s, False, id="-".join(map(str, s)))
             for s in MM_SHAPES]
            + [pytest.param(*s, True, id="nib-" + "-".join(map(str, s)))
               for s in NIB_SHAPES])


@pytest.mark.parametrize("M,K,N,nibbles", MM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_matches_ref(M, K, N, nibbles, dtype):
    """int8 ``[K, N]`` weights, or int4 mantissas handed to the kernel as
    the ``[K/2, N]`` nibble bytes they are stored in (unpacked in VMEM)."""
    x = (jax.random.normal(KEY, (M, K)) * 0.5).astype(dtype)
    w = jax.random.normal(KEY, (K, N)) * 0.1
    f = jax.random.uniform(KEY, (N,), minval=2, maxval=7)
    wi, s = pack_weights(w, f)
    if nibbles:
        wi = jnp.clip(wi, -8, 7)
        got = qmatmul_any(x, _stored(wi, True, False), s, nibbles=True,
                          interpret=True)
    else:
        got = qmatmul_any(x, wi, s, interpret=True)
    want = qmatmul_ref(x, wi, s)
    assert got.dtype == x.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("nibbles", [False, True], ids=["int8", "nib"])
@pytest.mark.parametrize("vocab", [384, 2500])
def test_qmatmul_tied_table_as_stored(vocab, nibbles):
    """The tied head reads the ``[vocab, d]`` table as stored (a
    transposed operand): 2500 rows leave a partial last block of the
    1152-row tiles at d = 896."""
    from repro.kernels.qmatmul.ops import pack_linear, qmatmul_tiles
    d = 896
    bn = qmatmul_tiles(8, d, vocab // 2 if nibbles else vocab, nibbles)[1]
    assert vocab == 384 or (vocab // (1 + nibbles)) % bn
    x = jax.random.normal(KEY, (2, 4, d))
    m, _ = pack_linear(jax.random.normal(KEY, (d, vocab)) * 0.1, None,
                       4 if nibbles else 8)
    got = qmatmul_any(x, _stored(m, nibbles, True), nibbles=nibbles,
                      transposed=True, interpret=True)
    want = qmatmul_ref(x.reshape(8, d), m, jnp.ones((vocab,)))
    np.testing.assert_allclose(got.reshape(8, vocab), want, rtol=2e-5,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("Ks,Ns,nibbles", [(896, 896, False),
                                            (896, 128, False),
                                            (448, 4864, True),
                                            (2432, 896, True),
                                            (896, 151936, False)],
                         ids=["q_o", "k_v", "gate_up", "down", "head"])
def test_qmatmul_tiles_never_pad_qwen2_weights(Ks, Ns, nibbles):
    """qwen2-0.5b's packed serving weights (stored rows along K and N)
    all split K into whole blocks at decode and prefill row counts."""
    from repro.kernels.qmatmul.ops import qmatmul_tiles
    for M in (1, 16, 32):
        bm, bn, bk = qmatmul_tiles(M, Ks, Ns, nibbles)
        assert bk is not None and Ks % bk == 0 and bm == M


@pytest.mark.parametrize("nibbles", [False, True], ids=["int8", "nib"])
def test_qmatmul_pads_k_without_a_tile(monkeypatch, nibbles):
    """A K with no fitting multiple-of-128 divisor (here: a budget of
    4096 values, K = 200) takes the last-resort path, zero-padded K,
    and still matches the reference."""
    from repro.kernels.qmatmul import ops
    monkeypatch.setattr(ops, "BLOCK_ELEMS", 1 << 12)
    K, N = 200, 48
    assert ops.qmatmul_tiles(4, K // 2 if nibbles else K, N, nibbles)[2] \
        is None
    x = jax.random.normal(KEY, (4, K))
    m, s = ops.pack_linear(jax.random.normal(KEY, (K, N)) * 0.1, None,
                           4 if nibbles else 8)
    got = qmatmul_any(x, _stored(m, nibbles, False), s, nibbles=nibbles,
                      interpret=True)
    np.testing.assert_allclose(got, qmatmul_ref(x, m, s), rtol=2e-5,
                               atol=2e-5)


def _pallas_operands(fn, *args):
    """Each ``pallas_call`` in ``fn``'s jaxpr, as the list of its
    operands: the index of the argument an operand is (through nested
    jits), or None for anything computed."""
    closed = jax.make_jaxpr(fn)(*args)
    found = []

    def walk(jaxpr, origin):
        for eqn in jaxpr.eqns:
            ins = [origin.get(v) if isinstance(v, Var) else None
                   for v in eqn.invars]
            if eqn.primitive.name == "pallas_call":
                found.append(ins)
            elif "jaxpr" in eqn.params:
                inner = eqn.params["jaxpr"]
                inner = getattr(inner, "jaxpr", inner)
                walk(inner, {v: o for v, o in zip(inner.invars, ins)
                             if o is not None})
    walk(closed.jaxpr, {v: i for i, v in enumerate(closed.jaxpr.invars)})
    return found


def test_qmatmul_int8_weight_unpadded():
    """At K = 896 (no multiple of 512) the int8 weight is the kernel's
    operand itself: K is one whole block, so nothing pads it."""
    x = jnp.ones((32, 896))
    wi, s = pack_weights(jax.random.normal(KEY, (896, 256)) * 0.1,
                         jnp.float32(6.0))
    calls = _pallas_operands(
        lambda a, w, c: qmatmul_any(a, w, c, interpret=True), x, wi, s)
    assert len(calls) == 1 and 1 in calls[0], calls


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
