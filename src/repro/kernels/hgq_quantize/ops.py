"""jit'd public wrapper for the HGQ quantizer kernel.

Handles arbitrary input shapes (reshape + lane padding), dispatches the
right broadcast layout, and attaches the Algorithm-1 backward pass
(straight-through in x, ``+ln2 * delta`` surrogate in f) via
``jax.custom_vjp`` so the kernel body stays forward-only.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import LANE, hgq_quantize_2d
from .ref import hgq_quantize_ref

LN2 = 0.6931471805599453


def _pad_cols(a: jax.Array) -> jax.Array:
    cols = a.shape[-1]
    pad = (-cols) % LANE
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a


def _to_2d(x: jax.Array):
    """Reshape any-rank x to [rows, cols] with lane-aligned cols."""
    if x.ndim == 0:
        return x.reshape(1, 1), x.shape
    lead = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    return x.reshape(lead, x.shape[-1]), x.shape


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def hgq_quantize(x: jax.Array, f: jax.Array, epsilon: float = 0.5,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable HGQ quantizer (Alg. 1) backed by the Pallas kernel.

    f: scalar (per_tensor), [x.shape[-1]] (per_channel) or x.shape
    (per_parameter).  ``interpret=None`` compiles the kernel on TPU and
    interprets it elsewhere; pass a bool to override.
    """
    return _forward(x, f, epsilon, interpret)


def _forward(x, f, epsilon, interpret):
    x2, orig_shape = _to_2d(x)
    cols = x2.shape[-1]
    x2p = _pad_cols(x2)
    if f.ndim == 0:
        f_arg = f
    elif f.shape == (x.shape[-1],):
        f_arg = _pad_cols(f.reshape(1, -1))[0]
    elif f.shape == x.shape:
        f_arg = _pad_cols(f.reshape(x2.shape))
    else:
        # general broadcast group shapes fall back to the reference path
        return hgq_quantize_ref(x, jnp.broadcast_to(f, x.shape))
    out = hgq_quantize_2d(x2p, f_arg, epsilon=epsilon, interpret=interpret)
    return out[..., :cols].reshape(orig_shape)


def _fwd(x, f, epsilon, interpret):
    xq = _forward(x, f, epsilon, interpret)
    delta = (x.astype(jnp.float32) - xq.astype(jnp.float32))
    fi = jnp.floor(f.astype(jnp.float32) + 0.5)
    return xq, (delta, fi, f.shape)


def _bwd(epsilon, interpret, res, g):
    delta, fi, f_shape = res
    g32 = g.astype(jnp.float32)
    # d xq / dx = 1 (STE)
    dx = g
    # d xq / df = +ln2 * delta  (Eq. 15; see core/quantizer.py)
    df_full = g32 * LN2 * delta
    # sum over broadcast axes down to f's shape
    if f_shape == ():
        df = jnp.sum(df_full)
    elif len(f_shape) == 1:
        df = jnp.sum(df_full.reshape(-1, df_full.shape[-1]), axis=0)
    else:
        df = df_full.reshape(f_shape)
    return dx, df.astype(jnp.float32)


hgq_quantize.defvjp(_fwd, _bwd)
