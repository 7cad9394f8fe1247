"""Backend dispatch + shape handling for the fused wire kernels.

Dispatch follows ``kernels.backend``: on TPU the compiled Pallas kernel
is the fast path; elsewhere the jnp reference is — XLA already fuses
the elementwise chain on CPU/GPU.  ``use_kernel``/``interpret``
overrides exist so tests can force the kernel route (interpreted) and
pin it bit-identical to the reference on any backend.

All entry points accept arbitrary shapes; lane alignment (and even-
column alignment for nibble packing) is handled here by zero/one
padding that provably round-trips: padded positions quantize to 0
mantissas under scale 1 and are sliced off before return.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..backend import resolve, use_fused_kernel
from . import kernel, ref
from .kernel import LANE
from .ref import grid_scale

__all__ = ["dequant_sum", "grid_scale", "pack_chunks", "quantize_chunks",
           "quantize_leaf", "use_fused_kernel"]


def _pad_cols(x: jax.Array, mult: int, value: float) -> jax.Array:
    pad = (-x.shape[-1]) % mult
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=value)


def quantize_leaf(rows: jax.Array, amax: jax.Array, bits: int = 8, *,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused phase-1 for one leaf in stacked-row layout: [L, P] fp32 +
    per-row pmax'd amax [L] -> (int8 mantissas, 2^-f scale [L], fp32
    error-feedback residual) — grid exponent, saturating quantize and
    residual in a single pass."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.quantize_leaf_ref(rows, amax, bits)
    L, P = rows.shape
    q, s, r = kernel.wire_quantize_rows(
        _pad_cols(jnp.asarray(rows, jnp.float32), LANE, 0.0), amax,
        bits=bits, interpret=interpret)
    return q[:, :P], s, r[:, :P]


def quantize_chunks(e: jax.Array, s: jax.Array, bits: int = 8, *,
                    use_kernel: Optional[bool] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-position-scale phase-1 (2D sliced path): [R, C] fp32 + [R, C]
    scale -> (int8 mantissas, fp32 residual)."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.quantize_chunks_ref(e, s, bits)
    R, C = e.shape
    q, r = kernel.wire_quantize_sflat(
        _pad_cols(jnp.asarray(e, jnp.float32), LANE, 0.0),
        _pad_cols(jnp.asarray(s, jnp.float32), LANE, 1.0),
        bits=bits, interpret=interpret)
    return q[:, :C], r[:, :C]


def pack_chunks(q: jax.Array, *, use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Nibble-pack int4-range mantissas along the last axis, two per
    byte (odd lengths pad one zero nibble) — the sub-5-bit wire format,
    byte-identical to ``qmatmul.pack_nibbles``."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.pack_chunks_ref(q)
    lead, C = q.shape[:-1], q.shape[-1]
    q2 = _pad_cols(jnp.asarray(q, jnp.int8).reshape((-1, C)), 2 * LANE, 0)
    packed = kernel.wire_pack_rows(q2[:, 0::2], q2[:, 1::2],
                                   interpret=interpret)
    return packed[:, :(C + 1) // 2].reshape(lead + ((C + 1) // 2,))


def dequant_sum(q: jax.Array, s: jax.Array, shift: int, n: int, *,
                use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """Fused phase-2 decode: requantized mantissa sums -> the fp32
    delivered mean contribution ``q * 2^shift * s / n``.  ``s``
    broadcasts against ``q`` (the 2D path decodes [M, C] blocks against
    a [C] slice scale)."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.dequant_sum_ref(q, s, shift, n)
    sb = jnp.broadcast_to(jnp.asarray(s, jnp.float32), q.shape)
    shape = q.shape
    C = shape[-1] if q.ndim else 1
    q2 = _pad_cols(q.reshape((-1, C)), LANE, 0)
    s2 = _pad_cols(sb.reshape((-1, C)), LANE, 1.0)
    out = kernel.wire_dequant_rows(q2, s2, shift=shift, n=n,
                                   interpret=interpret)
    return out[:, :C].reshape(shape)
