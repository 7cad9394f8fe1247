"""Pallas TPU kernel: fused dequant x matmul for HGQ-packed weights.

Serving-path kernel (DESIGN.md SS2): weights live in HBM as int8 or
nibble mantissas + a per-output-channel power-of-two scale (2^-f with f
the trained HGQ bits).  Decode is HBM-bandwidth-bound, so the kernel
reads each weight in the layout it is stored in, once, and dequantizes
in VMEM — no XLA op rewrites a weight between its leaf and the kernel.

Stored layouts (static ``nibbles`` / ``transposed``):

  * ``[K, N]`` int8 — ``x @ w``.
  * ``[K/2, N]`` nibbles interleaved along K — byte row r holds rows 2r
    (low nibble) and 2r + 1 (high).  ``qmatmul`` hands the kernel x in
    planes ``[2, M, K/2]`` (even columns, then odd) and it accumulates
    ``x_even @ lo + x_odd @ hi``.
  * ``[N, K]`` int8 (a tied ``[vocab, d]`` table) — ``x @ w.T`` as a
    ``dot_general`` contracting the last dims of both blocks.
  * ``[N/2, K]`` nibbles interleaved along N — output planes ``[2, M,
    N/2]`` (even columns, then odd); ops.py interleaves them.

Tiling: grid (M/bm, N/bn, K/bk) in stored units; ops.py picks the tiles
from the shapes so the weight's K axis is whole or a divisor of K.  A
partial last block along M or N only feeds rows or columns that are
never written back.  With one K step the product goes straight to the
output; otherwise an fp32 accumulator lives in VMEM.  The per-channel
scale multiplies once at the end (it is constant along K).  A stacked
``[L, ...]`` weight and a scalar-prefetched ``layer`` index read one
layer's blocks in place, so a ``lax.scan`` over layers needs no slice.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret


def nibble_planes(packed):
    """int8 nibble bytes -> (low, high) sign-extended int32 mantissas of
    the same shape.  The shifts run in int32 (Mosaic has no int8
    shifts); arithmetic shifts of the sign-extended byte give the int4
    values exactly."""
    p = packed.astype(jnp.int32)
    lo = jax.lax.shift_right_arithmetic(jax.lax.shift_left(p, 28), 28)
    hi = jax.lax.shift_right_arithmetic(p, 4)
    return lo, hi


def _qmatmul_kernel(*refs, k_steps: int, nibbles: bool, transposed: bool,
                    scaled: bool, stacked: bool):
    refs = list(refs[1:] if stacked else refs)   # drop the layer index
    x_ref, w_ref = refs.pop(0), refs.pop(0)
    s_ref = refs.pop(0) if scaled else None
    o_ref = refs.pop(0)
    acc_ref = refs.pop(0) if k_steps > 1 else None
    contract = (((1,), (1,)), ((), ())) if transposed \
        else (((1,), (0,)), ((), ()))

    def mm(a, b):
        return jax.lax.dot_general(a.astype(jnp.float32),
                                   b.astype(jnp.float32), contract,
                                   preferred_element_type=jnp.float32)

    w = w_ref[...]
    if not nibbles:
        part = mm(x_ref[...], w)
    elif transposed:
        lo, hi = nibble_planes(w)
        x = x_ref[...]
        part = jnp.stack([mm(x, lo), mm(x, hi)])
    else:
        lo, hi = nibble_planes(w)
        part = mm(x_ref[0], lo) + mm(x_ref[1], hi)

    def emit(acc):
        if scaled:
            acc = acc * s_ref[...]
        o_ref[...] = acc.astype(o_ref.dtype)

    if k_steps == 1:
        emit(part)
        return

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(k > 0)
    def _accumulate():
        acc_ref[...] += part

    @pl.when(k == k_steps - 1)
    def _done():
        emit(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("nibbles", "transposed", "bm",
                                             "bn", "bk", "interpret"))
def qmatmul(x: jax.Array, w: jax.Array, scale: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None, *, nibbles: bool = False,
            transposed: bool = False, bm: int, bn: int, bk: int,
            interpret: Optional[bool] = None) -> jax.Array:
    """x [M, K] fp times the stored weight ``w`` (module doc: ``[K, N]``,
    ``[K/2, N]``, ``[N, K]`` or ``[N/2, K]``, with a leading layer axis
    when ``layer`` is given); ``scale`` [N] or None (unit).  Returns
    [M, N] in x.dtype, or the planes [2, M, N/2] for nibbles along N.

    ``bn``/``bk`` are in stored units (nibble bytes count once); the K
    axis must split into whole ``bk`` blocks.  ``interpret=None``
    compiles on TPU and interprets elsewhere.
    """
    stacked = layer is not None
    ws = w.shape[1:] if stacked else w.shape
    Ns, Ks = ws if transposed else ws[::-1]
    M, K = x.shape
    assert K == (2 * Ks if nibbles and not transposed else Ks), (x.shape,
                                                                 w.shape)
    assert Ks % bk == 0, f"K {Ks} must split into blocks of {bk}"
    assert scale is None or not (nibbles and transposed)
    grid = (pl.cdiv(M, bm), pl.cdiv(Ns, bn), Ks // bk)

    def imap(f, weight=False):
        """Index map over the grid; a stacked weight's leads with the
        prefetched layer index."""
        if not stacked:
            return lambda i, j, k: f(i, j, k)
        return lambda i, j, k, l: ((l[0],) if weight else ()) + f(i, j, k)

    lead = (None,) if stacked else ()
    if nibbles and not transposed:
        # planar columns: even K columns, then odd, one plane each
        x = x.reshape(M, Ks, 2).transpose(2, 0, 1)
        x_spec = pl.BlockSpec((2, bm, bk), imap(lambda i, j, k: (0, i, k)))
    else:
        x_spec = pl.BlockSpec((bm, bk), imap(lambda i, j, k: (i, k)))
    if transposed:
        w_spec = pl.BlockSpec(lead + (bn, bk),
                              imap(lambda i, j, k: (j, k), weight=True))
    else:
        w_spec = pl.BlockSpec(lead + (bk, bn),
                              imap(lambda i, j, k: (k, j), weight=True))
    in_specs, args = [x_spec, w_spec], [x, w]
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, bn), imap(lambda i, j, k: (0, j))))
        args.append(scale.reshape(1, Ns))
    if nibbles and transposed:
        out_block, out_shape = (2, bm, bn), (2, M, Ns)
        o_spec = pl.BlockSpec(out_block, imap(lambda i, j, k: (0, i, j)))
    else:
        out_block, out_shape = (bm, bn), (M, Ns)
        o_spec = pl.BlockSpec(out_block, imap(lambda i, j, k: (i, j)))
    kern = functools.partial(_qmatmul_kernel, k_steps=grid[2],
                             nibbles=nibbles, transposed=transposed,
                             scaled=scale is not None, stacked=stacked)
    scratch = [pltpu.VMEM(out_block, jnp.float32)] if grid[2] > 1 else []
    if stacked:
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=o_spec, scratch_shapes=scratch)
        args = [jnp.asarray(layer, jnp.int32).reshape(1)] + args
    else:
        spec = pl.GridSpec(grid=grid, in_specs=in_specs, out_specs=o_spec,
                           scratch_shapes=scratch)
    return pl.pallas_call(
        kern, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(*args)
