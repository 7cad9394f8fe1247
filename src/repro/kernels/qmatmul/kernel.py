"""Pallas TPU kernel: fused dequant x matmul for HGQ-packed weights.

Serving-path kernel (DESIGN.md SS2): weights live in HBM as int8 + per-output-
channel power-of-two scale (2^-f with f the trained HGQ bits).  Decode is
HBM-bandwidth-bound, so halving (bf16 -> int8) or quartering (-> int4x2,
future) the streamed weight bytes moves the memory roofline term directly.

Tiling: grid (M/bm, N/bn, K/bk), fp32 accumulator scratch in VMEM; the
per-channel scale multiplies once on the final k step (valid because the
scale is constant along K).  MXU-aligned defaults (128, 128, 512).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret


DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _qmatmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def qmatmul(x: jax.Array, w_int: jax.Array, scale: jax.Array, *,
            bm: int = DEFAULT_BM, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
            interpret: Optional[bool] = None) -> jax.Array:
    """x [M, K] fp; w_int [K, N] int8; scale [N].  Returns [M, N] in x.dtype.

    M, K, N are padded to tile boundaries by ops.py.  ``interpret=None``
    compiles on TPU and interprets elsewhere.
    """
    M, K = x.shape
    K2, N = w_int.shape
    assert K == K2 and scale.shape == (N,)
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    return pl.pallas_call(
        functools.partial(_qmatmul_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w_int, scale.reshape(1, N))
