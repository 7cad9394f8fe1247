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


# Stored weight elements one grid step may hold, counted as the fp32
# values it dequantizes to in VMEM (a nibble byte holds two).
BLOCK_ELEMS = 1 << 20
# Rows of x one grid step takes; smaller M runs as one block.
BLOCK_ROWS = 256
# K block of the last-resort path, for a K with no fitting divisor.
PAD_BLOCK_K = 512


def _round_up(v: int, base: int) -> int:
    return -(-v // base) * base


def qmatmul_tiles(M: int, Ks: int, Ns: int, nibbles: bool
                  ) -> Tuple[int, int, Optional[int]]:
    """(bm, bn, bk) for a weight stored with ``Ks`` rows along K and
    ``Ns`` along N (nibble bytes count once).  K is one whole block where
    a 128-wide N block of it fits :data:`BLOCK_ELEMS`, else the largest
    multiple-of-128 divisor of K that does; ``bk`` is None when K has no
    such divisor (the caller pads K).  N takes the widest multiple-of-
    128 block that fits, or all of N; a partial last block only feeds
    output columns that are never written back."""
    budget = BLOCK_ELEMS // (2 if nibbles else 1)
    bm = M if M <= BLOCK_ROWS else BLOCK_ROWS
    if Ks * min(Ns, 128) <= budget:
        bk = Ks
    else:
        bk = max((d for d in range(128, Ks, 128)
                  if Ks % d == 0 and d * 128 <= budget), default=None)
    fit = budget // (bk or PAD_BLOCK_K)
    bn = Ns if Ns <= fit else max(128, fit // 128 * 128)
    return bm, bn, bk


def qmatmul_any(x: jax.Array, w: jax.Array, scale: Optional[jax.Array] = None,
                *, nibbles: bool = False, transposed: bool = False,
                layer: Optional[jax.Array] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """x [..., K] times a packed weight read as stored (``kernel``'s
    module doc): ``[K, N]`` int8 by default, ``[K/2, N]`` with
    ``nibbles``, ``[N, K]`` (a tied table) with ``transposed``, ``[N/2,
    K]`` with both; a leading layer axis with ``layer``.  ``scale`` [N]
    or None (unit).  Leading dims of x flatten into M; tiles come from
    the shapes (:func:`qmatmul_tiles`), and only a K with no fitting
    divisor pads (x and the weight, with zeros).  ``interpret=None``
    selects per backend (``kernels.backend.default_interpret``); pass a
    bool to override."""
    Ns, Ks = w.shape[-2:] if transposed else w.shape[-2:][::-1]
    K = 2 * Ks if nibbles and not transposed else Ks
    N = 2 * Ns if nibbles and transposed else Ns
    lead = x.shape[:-1]
    M = math.prod(lead) if lead else 1
    x2 = x.reshape(M, K)
    bm, bn, bk = qmatmul_tiles(M, Ks, Ns, nibbles)
    if bk is None:
        # last resort: zero rows of K (zero nibble bytes decode to 0)
        bk = PAD_BLOCK_K
        pad = _round_up(Ks, bk) - Ks
        x2 = jnp.pad(x2, ((0, 0), (0, pad * (K // Ks))))
        kax = w.ndim - 1 if transposed else w.ndim - 2
        w = jnp.pad(w, [(0, pad) if a == kax else (0, 0)
                        for a in range(w.ndim)])
    out = qmatmul(x2, w, scale, layer, nibbles=nibbles,
                  transposed=transposed, bm=bm, bn=bn, bk=bk,
                  interpret=interpret)
    if nibbles and transposed:
        out = out.transpose(1, 2, 0).reshape(M, N)
    return out.reshape(*lead, N)


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
