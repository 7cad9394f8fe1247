"""Public wrapper for the packed dequant-matmul: padding, batching, packing.

``pack_weights`` converts an HGQ-trained (w, f) pair into the serving
representation (int8 + per-channel 2^-f scale).  ``qmatmul_any`` handles
leading batch dims and non-aligned shapes.  ``packed_bytes`` is the TPU
serving cost model: the per-channel trained bits map channels into
{0, 4, 8} storage classes (0 = pruned — HGQ pruning carries straight
through to serving).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import qmatmul
from .ref import pack_ref


def mantissa_max(bits: int = 8) -> int:
    """Largest symmetric mantissa a ``bits``-wide signed grid carries
    (127 for int8, 7 for int4 — -2^(b-1) is excluded so chunk sums and
    error feedback stay symmetric)."""
    if not 2 <= bits <= 8:
        raise ValueError(f"grid width must be in [2, 8], got {bits!r}")
    return 2 ** (bits - 1) - 1


def grid_exponent(amax: jax.Array, bits: int = 8) -> jax.Array:
    """Largest fractional-bit exponent ``f`` whose power-of-two ``bits``-
    wide grid ``2^-f`` fits magnitudes up to ``amax`` into +-(2^(b-1)-1)
    mantissas (127 for the int8 default).  The raw cap divides two floats,
    so it can be one too high at the boundary; back off where the mantissa
    would still saturate.  Shared by :func:`channel_bits` (weight packing)
    and the quantized-wire gradient collective (``dist.collectives``)."""
    from ...core.quantizer import _exp2i, floor_log2
    qmax = float(mantissa_max(bits))
    amax = jnp.asarray(amax, jnp.float32)
    fcap = floor_log2(qmax / jnp.maximum(amax, 1e-12))
    return jnp.where(jnp.floor(amax * _exp2i(fcap) + 0.5) > qmax,
                     fcap - 1.0, fcap)


def channel_bits(w: jax.Array, f: Optional[jax.Array],
                 bits: int = 8) -> jax.Array:
    """Per-output-channel fractional bits for ``bits``-wide packing of
    ``w [..., K, N]``: the channel max of the trained ``f`` (every weight
    in the channel stays exactly representable), capped so the channel
    amax fits +-(2^(b-1)-1) — saturating the big weights corrupts the
    matmul far worse than flooring the small ones.  With ``f=None`` the
    cap itself is the (power-of-two) scale.  Shared by serving/packed.py
    and dist.perf packing."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)
    fgrid = grid_exponent(amax, bits)
    if f is None:
        return fgrid
    fi = jnp.max(jnp.floor(jnp.broadcast_to(
        jnp.asarray(f, jnp.float32), w32.shape) + 0.5), axis=-2)
    # trained bits below the cap never saturate (amax * 2^fi <= qmax/2), so
    # min(trained, capped-grid) preserves the old cap-then-back-off result
    return jnp.minimum(fi, fgrid)


def pack_weights(w: jax.Array, f: jax.Array, bits: int = 8
                 ) -> Tuple[jax.Array, jax.Array]:
    """[K, N] fp weights + fractional bits (scalar | [N] | [K, N]) ->
    (int8-stored mantissas clipped to the ``bits``-wide grid, [N] scale).
    Per-parameter f packs at the per-channel max so every weight in the
    channel is exactly representable."""
    f = jnp.asarray(f, jnp.float32)
    if f.ndim == 0:
        fcol = jnp.full((w.shape[1],), f)
    elif f.ndim == 1:
        fcol = jnp.broadcast_to(f, (w.shape[1],))
    else:
        fcol = jnp.max(jnp.broadcast_to(f, w.shape), axis=0)
    return pack_ref(w, fcol, bits)


def pack_linear(w: jax.Array, f: Optional[jax.Array] = None,
                bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """``w [..., K, N]`` (leading stacked-layer/expert axes allowed) ->
    ``(mantissas [..., K, N], scale [..., N])``: :func:`pack_weights` at
    the capped per-channel bits of :func:`channel_bits` on a ``bits``-wide
    grid.  The single leaf packer behind serving/packed.py and dist.perf
    packing."""
    w32 = jnp.asarray(w, jnp.float32)
    fi = channel_bits(w32, f, bits)
    if w32.ndim == 2:
        return pack_weights(w32, fi, bits)
    lead = w32.shape[:-2]
    m, scale = jax.vmap(lambda wi, fii: pack_weights(wi, fii, bits))(
        w32.reshape((-1,) + w32.shape[-2:]),
        fi.reshape((-1, fi.shape[-1])))
    return m.reshape(w32.shape), scale.reshape(lead + (w32.shape[-1],))


def pack_nibbles(m: jax.Array, axis: int = -1) -> jax.Array:
    """Pack int4-range mantissas two per int8 byte along ``axis`` (odd
    lengths pad one zero nibble).  The storage/wire format of sub-5-bit
    plan layers: halves serving HBM bytes and collective payloads."""
    m = jnp.moveaxis(jnp.asarray(m, jnp.int8), axis, -1)
    if m.shape[-1] % 2:
        m = jnp.pad(m, [(0, 0)] * (m.ndim - 1) + [(0, 1)])
    lo, hi = m[..., 0::2], m[..., 1::2]
    packed = jnp.bitwise_or(jnp.bitwise_and(lo, jnp.int8(0x0F)),
                            jnp.left_shift(hi, 4)).astype(jnp.int8)
    return jnp.moveaxis(packed, -1, axis)


def unpack_nibbles(packed: jax.Array, orig: int,
                   axis: int = -1) -> jax.Array:
    """Inverse of :func:`pack_nibbles`: int8 bytes -> ``orig`` sign-
    extended int4-range mantissas along ``axis`` (arithmetic shifts, so
    negative nibbles come back exact)."""
    p = jnp.moveaxis(jnp.asarray(packed, jnp.int8), axis, -1)
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    m = jnp.stack([lo, hi], axis=-1).reshape(
        p.shape[:-1] + (2 * p.shape[-1],))[..., :orig]
    return jnp.moveaxis(m, -1, axis)


def qmatmul_any(x: jax.Array, w_int: jax.Array, scale: jax.Array, *,
                interpret: Optional[bool] = None, bm: int = 128,
                bn: int = 128, bk: int = 512) -> jax.Array:
    """x [..., K] @ packed w [K, N]: flattens leading dims and pads to the
    (8, 128) tile grid.  ``interpret=None`` selects per backend
    (``kernels.backend.default_interpret``); pass a bool to override."""
    K, N = w_int.shape
    lead = x.shape[:-1]
    M = math.prod(lead) if lead else 1
    x2 = x.reshape(M, K)

    def _round_up(v, base):
        return -(-v // base) * base

    # every dim must be an exact multiple of its tile (partial blocks read
    # out-of-bounds in the k-accumulation grid)
    bm_ = min(bm, _round_up(M, 8))
    bk_ = min(bk, _round_up(K, 128))
    bn_ = min(bn, _round_up(N, 128))
    M3, K3, N3 = _round_up(M, bm_), _round_up(K, bk_), _round_up(N, bn_)
    if M3 > M or K3 > K:
        x2 = jnp.pad(x2, ((0, M3 - M), (0, K3 - K)))
    w2, s2 = w_int, scale
    if K3 > K or N3 > N:
        w2 = jnp.pad(w_int, ((0, K3 - K), (0, N3 - N)))
        s2 = jnp.pad(scale, (0, N3 - N))
    out = qmatmul(x2, w2, s2, bm=bm_, bn=bn_, bk=bk_, interpret=interpret)
    return out[:M, :N].reshape(*lead, N)


def packed_bytes(w: jax.Array, f: jax.Array, vmin, vmax) -> float:
    """Serving weight bytes under {0,4,8}-bit storage classes chosen from the
    calibrated per-channel bitwidths b = max(i' + f, 0).  This is the
    memory-roofline win HGQ buys on TPU decode (DESIGN.md SS2)."""
    from ...core.quantizer import int_bits_from_range
    b = jnp.maximum(int_bits_from_range(vmin, vmax)
                    + jnp.floor(jnp.asarray(f, jnp.float32) + 0.5), 0.0)
    cls = jnp.where(b <= 0, 0.0, jnp.where(b <= 4, 4.0, 8.0))
    n_per_channel = w.shape[0] if w.ndim == 2 else 1
    return float(jnp.sum(cls) / 8.0 * n_per_channel)
