"""Backend dispatch + shape handling for the quantized-KV-cache kernels.

Dispatch follows ``kernels.backend``: on TPU the compiled Pallas kernels
are the fast path; elsewhere the jnp reference is — XLA fuses the
dequant into the attention einsums on CPU/GPU.  ``use_kernel``/
``interpret`` overrides exist so tests can force the kernel route
(interpreted) and pin it against the reference on any backend.

Entry points accept the cache-native layouts of ``serving/kvcache.py``
(``[B, W, KV, hd]`` mantissas, ``[B, W, KV]`` exponents); lane alignment
is handled here by zero padding that provably round-trips — padded head
columns quantize to 0 mantissas and contribute nothing to either dot
product, padded ring slots carry mask 0.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..backend import resolve, use_fused_kernel
from . import kernel, ref
from .kernel import LANE

__all__ = ["kv_attention_decode", "kv_dequant", "kv_pack", "kv_quantize",
           "kv_unpack", "use_fused_kernel"]


def _pad_last(x: jax.Array, mult: int, value=0) -> jax.Array:
    pad = (-x.shape[-1]) % mult
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=value)


def kv_quantize(x: jax.Array, bits: int = 8, *,
                use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """``[..., hd]`` fp k/v rows -> (int8 mantissas ``[..., hd]``, int8
    grid exponents ``[...]``): amax over the head dim, capped 2^-f grid,
    saturating round — the cache-store quantizer."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.kv_quantize_ref(x, bits)
    lead, hd = x.shape[:-1], x.shape[-1]
    rows = _pad_last(jnp.asarray(x, jnp.float32).reshape(-1, hd), LANE)
    q, f = kernel.kv_quantize_rows(rows, bits=bits, interpret=interpret)
    return q[:, :hd].reshape(lead + (hd,)), f.reshape(lead)


def kv_dequant(q: jax.Array, f: jax.Array, *,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """(int8 mantissas ``[..., hd]``, int8 exponents ``[...]``) -> fp32
    ``q * 2^-f``."""
    use_kernel, interpret = resolve(use_kernel, interpret)
    if not use_kernel:
        return ref.kv_dequant_ref(q, f)
    lead, hd = q.shape[:-1], q.shape[-1]
    q2 = _pad_last(jnp.asarray(q, jnp.int8).reshape(-1, hd), LANE)
    out = kernel.kv_dequant_rows(q2, f.reshape(-1), interpret=interpret)
    return out[:, :hd].reshape(lead + (hd,))


def kv_pack(q: jax.Array) -> jax.Array:
    """Nibble-pack int4-range mantissas two per stored byte along the
    head dim (``kv_bits <= 4`` format).  The written rows are tiny next
    to the full-cache read, so the pack stays jnp on every backend."""
    return ref.kv_pack_ref(q)


def kv_unpack(packed: jax.Array, hd: int) -> jax.Array:
    """Inverse of :func:`kv_pack` (plain readers; the fused attention
    read unpacks in VMEM instead)."""
    return ref.kv_unpack_ref(packed, hd)


def kv_attention_decode(qh: jax.Array, km: jax.Array, kf: jax.Array,
                        vm: jax.Array, vf: jax.Array, qpos: jax.Array,
                        tpos: jax.Array, *, window: Optional[int],
                        n_kv: int, probs_f: Optional[jax.Array] = None,
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention over the quantized ring cache, dequant fused.

    ``qh`` [B, S, H, hd] roped queries; ``km``/``vm`` [B, W, KV, hdm]
    int8 mantissas (hdm = hd, or hd // 2 nibble-packed); ``kf``/``vf``
    [B, W, KV] int8 exponents; ``qpos`` [B, S] global query positions;
    ``tpos`` [B, W] global position per ring slot (negative = empty).
    Returns [B, S, H, hd] in ``qh.dtype`` — same contract as
    ``nn.attention._decode_attention`` on a dequantized cache.
    """
    use_kernel, interpret = resolve(use_kernel, interpret)
    B, S, H, hd = qh.shape
    KV = n_kv
    G = H // KV
    qg = qh.reshape(B, S, KV, G, hd)
    if not use_kernel:
        out = ref.kv_attention_ref(qg, km, kf, vm, vf, qpos, tpos,
                                   window=window, probs_f=probs_f)
        return out.reshape(B, S, H, hd)
    W = km.shape[1]
    packed = km.shape[-1] != hd
    # one (b, kv-head) grid cell per call; query rows repeat G-fold so
    # the mask/qpos land row-aligned with the grouped heads
    qg2 = qg.transpose(0, 2, 1, 3, 4).reshape(B, KV, S * G, hd)
    km2 = km.transpose(0, 2, 1, 3)                    # [B, KV, W, hdm]
    vm2 = vm.transpose(0, 2, 1, 3)
    kf2 = kf.transpose(0, 2, 1)[:, :, None, :]        # [B, KV, 1, W]
    vf2 = vf.transpose(0, 2, 1)[:, :, None, :]
    mask = (tpos[:, None, :] <= qpos[:, :, None]) & (tpos[:, None, :] >= 0)
    if window is not None:
        mask &= (qpos[:, :, None] - tpos[:, None, :]) < window
    mask = jnp.repeat(mask.astype(jnp.int8), G, axis=1)  # [B, SG, W]
    km2, vm2 = _pad_last(km2, LANE), _pad_last(vm2, LANE)
    if packed:
        # the kernel unpacks to planar columns (even, then odd): hand it
        # the queries in that order
        hdm = km2.shape[-1]
        qg2 = _pad_last(qg2.astype(jnp.float32), 2 * hdm)
        qg2 = jnp.concatenate([qg2[..., 0::2], qg2[..., 1::2]], axis=-1)
    else:
        qg2 = _pad_last(qg2.astype(jnp.float32), LANE)
    # ring-slot axis: padded slots carry mask 0 and contribute nothing
    Wp = (-(-W // LANE)) * LANE
    if Wp != W:
        km2 = jnp.pad(km2, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))
        vm2 = jnp.pad(vm2, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))
        kf2, vf2 = _pad_last(kf2, LANE), _pad_last(vf2, LANE)
        mask = _pad_last(mask, LANE)
    pf = (jnp.zeros((), jnp.float32) if probs_f is None
          else jnp.asarray(probs_f, jnp.float32))
    out = kernel.kv_attention_rows(
        qg2, km2, kf2, vm2, vf2, mask, pf, scale=float(hd) ** -0.5,
        packed=packed, use_pf=probs_f is not None, interpret=interpret)
    if packed:
        # planar -> interleaved head columns
        out = out.reshape(B, KV, S * G, 2, hdm).swapaxes(-1, -2).reshape(
            B, KV, S * G, 2 * hdm)
    out = out[..., :hd].reshape(B, KV, S, G, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, H, hd).astype(qh.dtype)
