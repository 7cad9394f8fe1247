"""Pallas TPU kernels: fused wire compression for the gradient collective.

Phase 1 of the int8-on-the-wire exchange is three elementwise sweeps in
the reference path — grid-exponent from the pmax'd amax, saturating
quantize, nibble pack — plus a fourth to materialize the error-feedback
residual.  Each kernel here fuses its stage into one VMEM pass over
(block_rows, DEFAULT_BLOCK_COLS) tiles — columns are tiled too, because one
bucket row of a full-size model is far wider than VMEM:

  * ``wire_quantize_rows``  — amax -> 2^-f grid -> round/clip -> int8
    mantissas AND the fp32 residual, per stacked-layer row, one pass
  * ``wire_quantize_sflat`` — same with a per-position scale (the 2D
    sliced path, where one device's slice crosses layer-row boundaries)
  * ``wire_pack_rows``      — two int4 mantissas per byte (wire format
    of sub-5-bit plan widths), ``qmatmul.pack_nibbles`` on lo/hi planes
    that ops.py splits off (Mosaic cannot interleave lanes)
  * ``wire_dequant_rows``   — phase-2 decode ``q * 2^shift * s / n``

The grid math reuses ``hgq_quantize``'s exact exponent-field exp2
(integer shifts, never an ulp off) with the bitcast twin of
``core.quantizer.floor_log2``; the mantissa range comes from
``qmatmul.mantissa_max``.  ``ref.py`` holds the jnp reference these are
asserted bit-identical to (tests/test_wire_pack.py, interpret mode);
``ops.py`` picks the backend and handles padding/alignment.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_interpret
from ..hgq_quantize.kernel import DEFAULT_BLOCK_ROWS, LANE, _exact_exp2

# 256 x 1024 fp32 tiles: 1 MiB each, so the widest kernel (two fp32
# inputs, fp32 + int8 outputs, double-buffered) stays near 7 MiB of VMEM
DEFAULT_BLOCK_COLS = 1024


def _floor_log2_pos(x):
    """floor(log2 x) for positive *normal* fp32 via exponent-field
    extraction — bit-identical to ``core.quantizer.floor_log2`` (frexp)
    on that domain, and integer ops only, so it lowers in-kernel.  The
    grid ratio qmax/max(amax, 1e-12) is normal for every finite amax a
    gradient can produce."""
    ex = (jax.lax.bitcast_convert_type(x, jnp.int32) >> 23) & 0xFF
    return ex.astype(jnp.float32) - 127.0


def _grid_scale_math(amax, qmax):
    """amax -> the 2^-f wire grid step; the exact math of
    ``qmatmul.grid_exponent`` + ``_exp2i(-f)``: cap f so amax fits in
    +-qmax mantissas, backing off one where rounding would still
    saturate."""
    fcap = _floor_log2_pos(qmax / jnp.maximum(amax, 1e-12))
    f = jnp.where(jnp.floor(amax * _exact_exp2(fcap) + 0.5) > qmax,
                  fcap - 1.0, fcap)
    return _exact_exp2(-f)


def _quantize_rows_kernel(x_ref, a_ref, q_ref, s_ref, r_ref, *, qmax):
    s = _grid_scale_math(a_ref[...], qmax)        # [br, 1]
    x = x_ref[...]
    q = jnp.clip(jnp.round(x / s), -qmax, qmax)   # integral fp32
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = s
    r_ref[...] = x - q * s


def _quantize_sflat_kernel(x_ref, s_ref, q_ref, r_ref, *, qmax):
    s = s_ref[...]                                # same tile shape as x
    x = x_ref[...]
    q = jnp.clip(jnp.round(x / s), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    r_ref[...] = x - q * s


def _pack_kernel(lo_ref, hi_ref, o_ref):
    # int32 math (Mosaic has no int8 shifts); the packed byte lies in
    # [-128, 127], so the narrowing cast is exact
    lo = lo_ref[...].astype(jnp.int32)
    hi = hi_ref[...].astype(jnp.int32)
    o_ref[...] = jnp.bitwise_or(jnp.bitwise_and(lo, 0x0F),
                                jnp.left_shift(hi, 4)).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, o_ref, *, mul, n):
    o_ref[...] = q_ref[...].astype(jnp.float32) * mul * s_ref[...] / n


def _tiles(R: int, C: int, block_rows: int):
    """(grid, [br, bc] tile spec, [br, 1] row-column spec) over an
    [R, C] array, bc <= DEFAULT_BLOCK_COLS; edge blocks may be partial
    (elementwise kernels only write in bounds)."""
    br, bc = min(block_rows, R), min(DEFAULT_BLOCK_COLS, C)
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    col = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    return (pl.cdiv(R, br), pl.cdiv(C, bc)), tile, col


_STATIC = ("block_rows", "interpret")


@functools.partial(jax.jit, static_argnames=("bits",) + _STATIC)
def wire_quantize_rows(rows: jax.Array, amax: jax.Array, *, bits: int = 8,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: Optional[bool] = None):
    """[L, P] fp32 rows + [L] amax -> (int8 [L, P], scale [L],
    residual fp32 [L, P]); P must be lane-aligned (ops.py pads)."""
    from ..qmatmul.ops import mantissa_max
    L, P = rows.shape
    assert P % LANE == 0, f"cols {P} must be lane-aligned"
    grid, tile, col = _tiles(L, P, block_rows)
    kern = functools.partial(_quantize_rows_kernel,
                             qmax=float(mantissa_max(bits)))
    q, s, r = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile, col],
        out_specs=[tile, col, tile],
        out_shape=[jax.ShapeDtypeStruct((L, P), jnp.int8),
                   jax.ShapeDtypeStruct((L, 1), jnp.float32),
                   jax.ShapeDtypeStruct((L, P), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rows.astype(jnp.float32), amax.reshape(L, 1).astype(jnp.float32))
    return q, s[:, 0], r


@functools.partial(jax.jit, static_argnames=("bits",) + _STATIC)
def wire_quantize_sflat(x: jax.Array, s: jax.Array, *, bits: int = 8,
                        block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: Optional[bool] = None):
    """[R, C] fp32 + per-position [R, C] scale -> (int8, residual)."""
    from ..qmatmul.ops import mantissa_max
    R, C = x.shape
    assert C % LANE == 0, f"cols {C} must be lane-aligned"
    grid, tile, _ = _tiles(R, C, block_rows)
    kern = functools.partial(_quantize_sflat_kernel,
                             qmax=float(mantissa_max(bits)))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32), s.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=_STATIC)
def wire_pack_rows(lo: jax.Array, hi: jax.Array, *,
                   block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: Optional[bool] = None) -> jax.Array:
    """[R, C] int4-range mantissas of the even (``lo``) and odd (``hi``)
    columns -> [R, C] packed bytes ``lo & 0xF | hi << 4``; C must be
    lane-aligned."""
    R, C = lo.shape
    assert C % LANE == 0, f"cols {C} must be lane-aligned"
    grid, tile, _ = _tiles(R, C, block_rows)
    return pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(lo.astype(jnp.int8), hi.astype(jnp.int8))


@functools.partial(jax.jit, static_argnames=("shift", "n") + _STATIC)
def wire_dequant_rows(q: jax.Array, s: jax.Array, *, shift: int, n: int,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                         interpret: Optional[bool] = None) -> jax.Array:
    """[R, C] mantissa sums + [R, C] scale -> fp32 ``q * 2^shift * s / n``
    (the phase-2 delivered-mean decode) in one pass."""
    R, C = q.shape
    assert C % LANE == 0, f"cols {C} must be lane-aligned"
    grid, tile, _ = _tiles(R, C, block_rows)
    kern = functools.partial(_dequant_kernel, mul=float(2 ** shift), n=n)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, s.astype(jnp.float32))
