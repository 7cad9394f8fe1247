"""Pallas TPU kernels: quantized-KV-cache store and fused attention read.

Decode is KV-cache-bandwidth-bound: the ring buffer is read in full every
tick while only one row per slot is written.  Storing mantissas on the
per-row 2^-f grid (``kv_bits`` from the precision plan) and dequantizing
*inside* the attention read means HBM streams int8/nibble bytes instead
of fp — the read kernel touches each cache byte exactly once:

  * ``kv_quantize_rows``    — amax over the head dim -> capped 2^-f grid
    -> round/clip -> int8 mantissas AND the int8 grid exponent, one pass
  * ``kv_dequant_rows``     — ``q * 2^-f`` decode (tests / plain readers)
  * ``kv_attention_rows``   — the fused decode read: scores against int8
    mantissas with the k exponents folded into the score columns, online
    mask/softmax, probs requantization, and the v exponents folded into
    the prob rows — the cache never exists dequantized in HBM.  Nibble-
    packed (``kv_bits <= 4``) caches unpack in VMEM into lo/hi planes
    (even head columns, then odd), and ops.py hands the queries in and
    takes the outputs back in that planar column order.

The exponent application rides the last (slot) axis of the score matrix,
so both dequants are row-vector broadcasts — no transposed per-column
scales anywhere.  Grid math reuses ``hgq_quantize``'s exact exponent-
field exp2 and the bitcast ``floor_log2`` twin from ``wire_pack``;
``ref.py`` holds the jnp reference (tests/test_kv_dequant.py pins the
elementwise kernels bit-identical in interpret mode, the fused read
numerically tight); ``ops.py`` picks the backend and handles padding.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret
from ..hgq_quantize.kernel import DEFAULT_BLOCK_ROWS, LANE, _exact_exp2
from ..wire_pack.kernel import _floor_log2_pos

NEG_INF = -1e30


def _grid_exponent_math(amax, qmax):
    """amax -> the capped grid exponent f of ``qmatmul.grid_exponent``:
    largest f with amax * 2^f inside +-qmax, backing off one where
    rounding would still saturate."""
    fcap = _floor_log2_pos(qmax / jnp.maximum(amax, 1e-12))
    return jnp.where(jnp.floor(amax * _exact_exp2(fcap) + 0.5) > qmax,
                     fcap - 1.0, fcap)


def _unpack_planes(packed):
    """[W, hdm] nibble bytes -> [W, 2 * hdm] sign-extended mantissas in
    planar order: the low nibbles (even head columns) fill the first hdm
    lanes, the high nibbles (odd columns) the rest.  Up to that column
    order this is ``qmatmul.unpack_nibbles``; Mosaic cannot interleave
    lanes, and hdm is lane-aligned, so the planes concatenate in place.
    The shifts run in int32 (Mosaic has no int8 shifts); arithmetic
    shifts of the sign-extended byte give the int8 results exactly."""
    p = packed.astype(jnp.int32)
    lo = jax.lax.shift_right_arithmetic(jax.lax.shift_left(p, 28), 28)
    hi = jax.lax.shift_right_arithmetic(p, 4)
    return jnp.concatenate([lo, hi], axis=-1)


def _kv_quantize_kernel(x_ref, q_ref, f_ref, *, qmax):
    x = x_ref[...]                                  # [br, hd] fp32
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    f = _grid_exponent_math(amax, qmax)             # [br, 1]
    q = jnp.clip(jnp.round(x * _exact_exp2(f)), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    f_ref[...] = f.astype(jnp.int8)


def _kv_dequant_kernel(q_ref, f_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) \
        * _exact_exp2(-f_ref[...].astype(jnp.float32))


def _kv_attention_kernel(q_ref, km_ref, kf_ref, vm_ref, vf_ref, mask_ref,
                         pf_ref, o_ref, *, scale, packed, use_pf):
    qc = q_ref[0, 0]                                # [SG, hd] fp32
    km = km_ref[0, 0]                               # [W, hdm] int8
    vm = vm_ref[0, 0]
    if packed:
        km = _unpack_planes(km)
        vm = _unpack_planes(vm)
    kf = kf_ref[0, 0, 0].astype(jnp.float32)        # [W]
    vf = vf_ref[0, 0, 0].astype(jnp.float32)
    maskb = mask_ref[0] != 0                        # [SG, W]
    # k dequant folds into the score's slot axis: a [1, W] row broadcast
    s = jax.lax.dot_general(
        qc, km.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # [SG, W]
    s = s * (_exact_exp2(-kf)[None, :] * scale)
    s = jnp.where(maskb, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pt = jnp.exp(s - m)
    pt = jnp.where(maskb, pt, 0.0)
    if use_pf:
        # quantize_inference on the probs grid: floor(p * 2^f + 0.5) * 2^-f
        # pf is an SMEM scalar: broadcast before the exponent bitcast
        pf = _exact_exp2(jnp.floor(
            jnp.full((1, pt.shape[-1]), pf_ref[0, 0]) + 0.5))
        pt = jnp.floor(pt * pf + 0.5) / pf
    l = jnp.sum(pt, axis=-1, keepdims=True)
    pv = (pt / jnp.maximum(l, 1e-20)) * _exact_exp2(-vf)[None, :]
    o_ref[0, 0] = jnp.dot(pv, vm.astype(jnp.float32),
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "block_rows",
                                             "interpret"))
def kv_quantize_rows(rows: jax.Array, *, bits: int = 8,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: Optional[bool] = None):
    """[R, hd] fp32 rows -> (int8 mantissas [R, hd], int8 grid exponents
    [R]); hd must be lane-aligned (ops.py pads with zeros, which never
    move a row's amax)."""
    from ..qmatmul.ops import mantissa_max
    R, P = rows.shape
    assert P % LANE == 0, f"cols {P} must be lane-aligned"
    br = min(block_rows, R)
    grid = (pl.cdiv(R, br),)
    kern = functools.partial(_kv_quantize_kernel,
                             qmax=float(mantissa_max(bits)))
    tile = pl.BlockSpec((br, P), lambda i: (i, 0))
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    q, f = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile],
        out_specs=[tile, col],
        out_shape=[jax.ShapeDtypeStruct((R, P), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.int8)],
        interpret=resolve_interpret(interpret),
    )(rows.astype(jnp.float32))
    return q, f[:, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def kv_dequant_rows(q: jax.Array, f: jax.Array, *,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: Optional[bool] = None) -> jax.Array:
    """[R, hd] int8 mantissas + [R] int8 exponents -> fp32 ``q * 2^-f``."""
    R, P = q.shape
    assert P % LANE == 0, f"cols {P} must be lane-aligned"
    br = min(block_rows, R)
    grid = (pl.cdiv(R, br),)
    tile = pl.BlockSpec((br, P), lambda i: (i, 0))
    col = pl.BlockSpec((br, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _kv_dequant_kernel,
        grid=grid,
        in_specs=[tile, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, P), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, f.reshape(R, 1))


@functools.partial(jax.jit, static_argnames=("scale", "packed", "use_pf",
                                             "interpret"))
def kv_attention_rows(qg: jax.Array, km: jax.Array, kf: jax.Array,
                      vm: jax.Array, vf: jax.Array, mask: jax.Array,
                      pf: jax.Array, *, scale: float, packed: bool,
                      use_pf: bool, interpret: Optional[bool] = None):
    """Fused dequant-attention decode read, one (batch row, kv head) per
    grid cell.

    ``qg`` [B, KV, SG, hd] fp32 (SG = query rows x grouped heads, each
    query row repeated G times); ``km``/``vm`` [B, KV, W, hdm] int8
    mantissas (hdm = hd, or hd // 2 nibble-packed); ``kf``/``vf``
    [B, KV, 1, W] int8 slot exponents; ``mask`` [B, SG, W] int8
    (0 = slot invisible to that query row); ``pf`` [1, 1] fp32 probs
    grid exponent (read iff ``use_pf``).  W and hd lane-aligned
    (ops.py pads; padded slots carry mask 0).  Returns [B, KV, SG, hd]
    fp32 attention outputs.  Packed, ``qg`` and the result carry their
    head columns in the planar order of :func:`_unpack_planes`.
    """
    B, KV, SG, HD = qg.shape
    W = km.shape[2]
    assert HD % LANE == 0 and W % LANE == 0, (HD, W)
    hdm = km.shape[3]
    kern = functools.partial(_kv_attention_kernel, scale=scale,
                             packed=packed, use_pf=use_pf)
    q_spec = pl.BlockSpec((1, 1, SG, HD), lambda b, k: (b, k, 0, 0))
    m_spec = pl.BlockSpec((1, 1, W, hdm), lambda b, k: (b, k, 0, 0))
    f_spec = pl.BlockSpec((1, 1, 1, W), lambda b, k: (b, k, 0, 0))
    mask_spec = pl.BlockSpec((1, SG, W), lambda b, k: (b, 0, 0))
    pf_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kern,
        grid=(B, KV),
        in_specs=[q_spec, m_spec, f_spec, m_spec, f_spec, mask_spec,
                  pf_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, SG, HD), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(qg.astype(jnp.float32), km, kf, vm, vf, mask,
      pf.reshape(1, 1).astype(jnp.float32))
