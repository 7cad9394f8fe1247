"""Every Pallas kernel family compiles for a TPU v5e at qwen2-0.5b's
published decode widths (d_model 896, 14 heads, 2 KV heads of 64, d_ff
4864, vocab 151,936).

Nothing runs: each case compiles, with ``interpret=False``, for one
chip of a described ``v5e:2x2`` topology, and asserts the kernel is in
the HLO as a ``tpu_custom_call``.  This is where Mosaic refuses a lane
shuffle, a scalar bitcast or a tile larger than VMEM, at no chip time.
The topology is described inside a fixture (never at import): only one
process may load the TPU compiler library, so under several test
workers only the worker that runs this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.hgq_quantize.kernel import hgq_quantize_2d
from repro.kernels.kv_dequant import ops as kv
from repro.kernels.qmatmul.ops import qmatmul_any
from repro.kernels.wire_pack import ops as wire

D, H, KV, HD, FF, VOCAB = 896, 14, 2, 64, 4864, 151936
SLOTS, RING = 4, 512
KERNEL = dict(use_kernel=True, interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return compiled


@pytest.mark.parametrize("K,N", [(D, D), (D, KV * HD), (D, FF), (FF, D),
                                 (D, VOCAB)],
                         ids=["q_o", "k_v", "gate_up", "down", "lm_head"])
def test_qmatmul(shape, K, N):
    _assert_kernel(lambda x, w, s: qmatmul_any(x, w, s, interpret=False),
                   shape((SLOTS, K), jnp.float32), shape((K, N), jnp.int8),
                   shape((N,), jnp.float32))


@pytest.mark.parametrize("K,N,nibbles,transposed",
                         [(D, FF, True, False), (FF, D, True, False),
                          (D, VOCAB, False, True)],
                         ids=["gate_up_nib", "down_nib", "tied_head"])
def test_qmatmul_as_stored(shape, K, N, nibbles, transposed):
    """The serving forward's weights as the packed tree stores them:
    nibbles ``[K/2, N]`` and the tied ``[vocab, d]`` table.  The weight
    reaches the kernel as stored (no int8 op writes HBM), and the
    head needs no weight-sized temporaries (291.7 MB when it was
    transposed and padded per call)."""
    stored = (N, K) if transposed else (K // 2 if nibbles else K, N)

    def mm(x, w, s):
        return qmatmul_any(x, w, None if transposed else s,
                           nibbles=nibbles, transposed=transposed,
                           interpret=False)
    compiled = _assert_kernel(mm, shape((SLOTS, K), jnp.float32),
                              shape(stored, jnp.int8),
                              shape((N,), jnp.float32))
    # int8 results written to HBM; the compiler's own prefetches of the
    # weight into VMEM (memory space S(1)) move it once, as read
    s8_hbm = [line for line in compiled.as_text().splitlines()
              if " = s8[" in line and "parameter(" not in line
              and "S(1)" not in line.split(" = ", 1)[1].split(" ", 1)[0]]
    assert not s8_hbm, s8_hbm
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_kv_quantize_rows(shape):
    _assert_kernel(lambda x: kv.kv_quantize(x, 4, **KERNEL),
                   shape((SLOTS, 1, KV, HD), jnp.float32))


def test_kv_dequant_rows(shape):
    _assert_kernel(lambda q, f: kv.kv_dequant(q, f, **KERNEL),
                   shape((SLOTS, RING, KV, HD), jnp.int8),
                   shape((SLOTS, RING, KV), jnp.int8))


@pytest.mark.parametrize("packed,use_pf", [(False, False), (True, False),
                                           (False, True)],
                         ids=["plain", "packed", "use_pf"])
def test_kv_attention_rows(shape, packed, use_pf):
    hdm = HD // 2 if packed else HD

    def attend(qh, km, kf, vm, vf, qpos, tpos, pf):
        return kv.kv_attention_decode(
            qh, km, kf, vm, vf, qpos, tpos, window=None, n_kv=KV,
            probs_f=pf if use_pf else None, **KERNEL)
    _assert_kernel(attend, shape((SLOTS, 1, H, HD), jnp.float32),
                   shape((SLOTS, RING, KV, hdm), jnp.int8),
                   shape((SLOTS, RING, KV), jnp.int8),
                   shape((SLOTS, RING, KV, hdm), jnp.int8),
                   shape((SLOTS, RING, KV), jnp.int8),
                   shape((SLOTS, 1), jnp.int32), shape((SLOTS, RING), jnp.int32),
                   shape((), jnp.float32))


def test_wire_quantize_leaf(shape):
    # 24 stacked layers of one d_model x 64 slice: rows far wider than a
    # VMEM tile, so the column tiling is what lets this compile
    _assert_kernel(lambda r, a: wire.quantize_leaf(r, a, 8, **KERNEL),
                   shape((24, D * 64), jnp.float32), shape((24,), jnp.float32))


def test_wire_quantize_chunks(shape):
    _assert_kernel(lambda e, s: wire.quantize_chunks(e, s, 4, **KERNEL),
                   shape((2, 1 << 18), jnp.float32),
                   shape((2, 1 << 18), jnp.float32))


def test_wire_pack(shape):
    _assert_kernel(lambda q: wire.pack_chunks(q, **KERNEL),
                   shape((2, 1 << 18), jnp.int8))


def test_wire_dequant(shape):
    _assert_kernel(lambda q, s: wire.dequant_sum(q, s, 1, 2, **KERNEL),
                   shape((2, 1 << 18), jnp.int32),
                   shape((2, 1 << 18), jnp.float32))


@pytest.mark.parametrize("fshape", [(), (D,)],
                         ids=["per_tensor", "per_channel"])
def test_hgq_quantize_2d(shape, fshape):
    _assert_kernel(lambda x, f: hgq_quantize_2d(x, f, interpret=False),
                   shape((SLOTS * 64, D), jnp.float32),
                   shape(fshape, jnp.float32))
