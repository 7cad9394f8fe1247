"""Pallas TPU kernel: HGQ quantizer forward (Eq. 4).

This op runs over every weight and activation on every training step — the
framework's hottest elementwise op.  The kernel fuses the (round f ->
exp2 -> scale -> floor -> unscale) chain into one VMEM pass, tiled
(block_rows, 128)-aligned for the VPU lanes.

Two broadcast layouts cover the granularity spectrum, with one body:
  * per_channel   — f is a [cols] row, broadcast across rows (a
    per_tensor scalar f is broadcast to such a row first: Mosaic cannot
    bitcast a scalar, and the row costs one lane-row of VMEM)
  * per_parameter — f has x's shape, streamed tile-by-tile beside x

The backward pass (STE in x, ln2*delta surrogate in f, Alg. 1) is attached
in ops.py via jax.custom_vjp — the kernel computes the forward only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_interpret

DEFAULT_BLOCK_ROWS = 256
LANE = 128  # TPU VPU lane width; last-dim tiles must be multiples


def _exact_exp2(fi):
    """2^fi by exponent-field construction — exact where XLA's exp2 can be
    an ulp off (fi=13, 15, 26, ...), and integer-shift only, so it lowers
    inside the kernel body.  fi must be integer-valued; clamped to the
    float32 normal range.  Inside a kernel fi must be a vector: Mosaic
    bitcasts vectors only, so broadcast a scalar exponent first."""
    biased = jnp.clip(fi, -126.0, 127.0).astype(jnp.int32) + 127
    return jax.lax.bitcast_convert_type(biased << 23, jnp.float32)


def _quantize_math(x, fi, epsilon):
    scale = _exact_exp2(fi)
    return jnp.floor(x.astype(jnp.float32) * scale + epsilon) / scale


def _kernel(x_ref, f_ref, o_ref, *, epsilon):
    fi = jnp.floor(f_ref[...] + 0.5)          # [1, cols] or x's tile shape
    o_ref[...] = _quantize_math(x_ref[...], fi, epsilon).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("epsilon", "block_rows",
                                             "interpret"))
def hgq_quantize_2d(x: jax.Array, f: jax.Array, *, epsilon: float = 0.5,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Quantize a 2-D array [rows, cols].  f: scalar, [cols], or x.shape.

    cols is padded to the 128-lane boundary by the caller (ops.py handles
    arbitrary shapes by reshaping/padding).  ``interpret=None`` compiles
    on TPU and interprets elsewhere.
    """
    rows, cols = x.shape
    assert cols % LANE == 0, f"cols {cols} must be lane-aligned"
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    x_spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    if f.ndim < 2:
        f_arg = jnp.broadcast_to(f.astype(jnp.float32), (1, cols))
        f_spec = pl.BlockSpec((1, cols), lambda i: (0, 0))
    else:
        f_arg = f.astype(jnp.float32)
        f_spec = x_spec
    return pl.pallas_call(
        functools.partial(_kernel, epsilon=epsilon),
        grid=grid,
        in_specs=[x_spec, f_spec],
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, f_arg)
