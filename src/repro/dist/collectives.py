"""Compressed data-parallel gradient collectives: the reduction itself
moves int8 (or bf16) bytes, not fp32.

``dist.ef_compress`` quantizes the *synchronized* gradient — it bounds
update noise but every fp32 byte still crosses the wire first.  This
module compresses **inside** the reduction, DeepSpeed/1-bit-Adam style,
with error feedback on both phases:

phase 1 (reduce-scatter as ``all_to_all``)
    Each data shard quantizes its local ``grad + residual`` to int8
    mantissas on a per-layer power-of-two grid ``2^-f`` (the exponent comes
    from :func:`repro.kernels.qmatmul.ops.grid_exponent`, the same grid
    logic the serving weight packer uses; the leaf amax is ``pmax``-shared
    so every shard quantizes on the same grid).  The int8 chunks are
    exchanged with ``lax.all_to_all`` and summed as int32 — exact, since
    ``n * 127`` fits comfortably.

phase 2 (``all_gather``)
    The chunk owner re-quantizes the int32 chunk sum back to int8 by a
    static right-shift of ``ceil(log2 n)`` bits and gathers the int8 sums;
    the shift remainder (phase-2 error) is scattered into the owner's
    residual, so the time-averaged delivered mean gradient telescopes to
    the true mean exactly like single-phase error feedback (see
    ``tests/test_collectives.py``).

Per-device bytes on the wire per gradient element: ``2 * (n-1)/n`` at 1
byte (int8) vs ``2 * (n-1)/n`` at 4 bytes for a ring fp32 all-reduce — a
4x reduction, independent of ``n`` (bf16-wire: 2x).  The per-leaf scale
exponents add one ``pmax`` float per layer, which the byte accounting
includes.

The public entry :func:`ef_wire_pmean` runs under ``shard_map`` over the
mesh's data axes (``model`` stays unmapped: every tensor-parallel shard
carries the replicated gradient, exactly as in the uncompressed step) and
is wrapped in ``jax.custom_vjp`` — the forward is the compressed mean
all-reduce, the backward passes cotangents through like the transpose of
``pmean`` — so it composes under ``jax.value_and_grad`` even though the
quantization ops themselves have no useful derivative.

``simulate_wire_pmean`` is the collective-free reference: identical
per-shard math on a stacked ``[n, ...]`` tree, used by single-device
tests and by the property tests; the 8-device CI job checks the
``shard_map`` path agrees with it bit-for-bit.

Two execution strategies share this math (``fused=True`` default):

fused / pipelined (the wall-clock fast path)
    One amax ``pmax`` for the whole tree, quantize/pack/decode routed
    through the ``kernels.wire_pack`` fused kernels, and the leaves
    exchanged in size-bucketed column-concatenated buffers — bucket k+1
    compresses while bucket k is in ``all_to_all`` (double-buffered
    program order), collapsing ~3 collectives *per leaf* into ~3 per
    bucket.  Bit-for-bit the per-leaf path: ``pmax`` is elementwise, so
    pmax(concat) == concat(pmax); the collectives act on axis 0, so
    column concatenation commutes with them; decode and residual math
    never change.

per-leaf (``fused=False``)
    The original one-collective-set-per-leaf trace, kept as the
    executable reference the fused path is tested against.

:func:`ef_wire_pmean_2d` (below) is the 2D generalization: the exchange
is additionally sliced over the tensor-parallel ``model`` axis, so each
(data, model) device reduces only its 1/(D*M) slice and the model-axis
replication moves int8 instead of fp32 — see the section comment above
it for the full layout.
"""
from __future__ import annotations

from functools import partial
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.plan import NIBBLE_BITS
from .scope import Scoped

WIRE_KINDS = ("int8", "bf16")

# fused-path bucket budget: wire payload bytes per pipelined exchange
# buffer.  Big enough that a smoke-scale tree rides one buffer (minimum
# launch count), small enough that real models get >= 2 buckets and the
# compress/exchange overlap; tests force tiny budgets to exercise the
# multi-bucket pipeline.
_WIRE_BUCKET_BYTES = 1 << 20

# trace-time recorder for bytes-on-wire accounting (collectives_bench):
# shapes are static, so appending (op, per-device bytes) while tracing
# measures exactly what the compiled collectives move.  Scoped, not a
# module global — see dist.scope.
_BYTES_TRACE: Scoped[Optional[List[Tuple[str, float]]]] = Scoped(
    "repro.dist.wire_bytes", None)


class record_wire_bytes:
    """Context manager: collect (op, per-device payload bytes) tuples for
    every collective issued while tracing inside the block."""

    def __init__(self):
        self.records: List[Tuple[str, float]] = []
        self._cm = None

    def __enter__(self):
        self._cm = _BYTES_TRACE.scope(self.records)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        cm, self._cm = self._cm, None
        return cm.__exit__(*exc)

    def total(self) -> float:
        return sum(b for _, b in self.records)


def _record(op: str, nbytes: float) -> None:
    records = _BYTES_TRACE.get()
    if records is not None:
        records.append((op, float(nbytes)))


def _ring_allreduce_bytes(nbytes: float, n: int) -> float:
    return 2.0 * (n - 1) / n * nbytes


def data_axis_names(mesh) -> Tuple[str, ...]:
    """The data-parallel axis names of ``mesh`` (pod is outer DP; the axis
    whitelist lives once, in ``sharding``)."""
    from .sharding import _data_axes
    return _data_axes(mesh)


def data_axis_size(mesh) -> int:
    from .sharding import _data_size
    return _data_size(mesh)


# ---------------------------------------------------------------------------
# per-shard quantization (pure; shared by the shard_map body, the simulator,
# and the tests)
# ---------------------------------------------------------------------------

def _stacked_flags(tree: Any, stacked: Any) -> Tuple[bool, ...]:
    """Per-leaf stacked-layer flags in ``jax.tree.flatten`` order.

    ``stacked`` is an optional matching tree of bools; ``None`` derives
    the flags from the tree paths (``sharding.stacked_tree`` — the same
    explicit rule ``dist.ef_compress`` uses, replacing the old rank
    sniff)."""
    from .sharding import stacked_tree
    marks = stacked_tree(tree) if stacked is None else stacked
    return tuple(bool(m) for m in jax.tree.leaves(marks))


def _width_flags(tree: Any, widths: Any) -> Tuple[int, ...]:
    """Per-leaf wire widths (static python ints) in ``jax.tree.flatten``
    order.  ``widths`` is an optional matching tree of ints — what
    ``core.plan.PrecisionPlan.wire_bits_tree`` produces; ``None`` means
    uniform int8, the exact legacy trace."""
    if widths is None:
        return tuple(8 for _ in jax.tree.leaves(tree))
    vals = tuple(int(w) for w in jax.tree.leaves(widths))
    for w in vals:
        if not 2 <= w <= 8:
            raise ValueError(f"wire width must be in [2, 8], got {w!r}")
    return vals


def _nibble_wire(kind: str, bits: int) -> bool:
    """True when this leaf's payload rides nibble-packed int4 bytes.
    Static (python bool), so bits == 8 traces the identical legacy graph."""
    return kind == "int8" and bits <= NIBBLE_BITS


def _layer_rows(e: jax.Array, stacked: bool) -> jax.Array:
    """Flatten a leaf to [L, P] rows — one quantization grid per leading
    (stacked-layer) axis entry for stacked rank >= 3 leaves, one per
    tensor otherwise (same stacked-leaf rule as ``dist._compress_leaf``;
    ``stacked`` comes from the tree path, not the rank)."""
    L = e.shape[0] if (stacked and e.ndim >= 3) else 1
    return jnp.asarray(e, jnp.float32).reshape(L, -1)


def _phase1_quantize(e: jax.Array, amax_rows: jax.Array, kind: str,
                     stacked: bool, bits: int = 8
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize one leaf for the wire.

    Returns ``(payload_rows, scale_rows, residual)``: the wire payload as
    [L, P] (``bits``-wide mantissas in int8 storage, or bf16 values with a
    dummy unit scale), the per-row grid step, and the local quantization
    error ``e - dequant``.  ``amax_rows`` is the *global* per-row amax
    (``pmax`` over shards), so every shard lands on the same grid and
    int32 chunk sums are exact.  ``bits`` comes from the leaf's
    PrecisionPlan entry (8 = legacy int8 grid; <= 4 rides nibble-packed
    bytes on the wire) and is ignored for bf16.
    """
    rows = _layer_rows(e, stacked)
    if kind == "bf16":
        payload = rows.astype(jnp.bfloat16)
        deq = payload.astype(jnp.float32)
        scale = jnp.ones((rows.shape[0],), jnp.float32)
        residual = (jnp.asarray(e, jnp.float32)
                    - deq.astype(jnp.float32).reshape(e.shape))
        return payload, scale, residual
    from ..kernels import wire_pack
    payload, scale, res_rows = wire_pack.quantize_leaf(rows, amax_rows,
                                                       bits)
    return payload, scale, res_rows.reshape(e.shape)


def _phase2_requantize(chunk_sum: jax.Array, n: int, kind: str
                       ) -> Tuple[jax.Array, jax.Array]:
    """Requantize a chunk of summed phase-1 payloads for the all_gather.

    int8: the int32 mantissa sum (|sum| <= n*127) shifts right by
    ``k = ceil(log2 n)`` so it fits int8 again; the remainder (in mantissa
    units) is the phase-2 error the chunk owner keeps.  bf16: round the
    fp32 sum to bf16, keep the rounding error.
    """
    if kind == "bf16":
        payload = chunk_sum.astype(jnp.bfloat16)
        return payload, chunk_sum - payload.astype(jnp.float32)
    k = _phase2_shift(n)
    m2 = jnp.round(chunk_sum.astype(jnp.float32) / (2 ** k)).astype(jnp.int32)
    err = (chunk_sum - m2 * (2 ** k)).astype(jnp.float32)
    return m2.astype(jnp.int8), err


def _phase2_shift(n: int) -> int:
    """The decode side multiplies by exactly this power of two — keep the
    encode/decode shift one definition.

    Width-independent by construction: with ``k = ceil(log2 n)`` the
    requantized sum satisfies ``|round(sum / 2^k)| <= round(n * qmax /
    2^k) <= qmax`` for ANY phase-1 grid width (``2^k >= n``), so mixed
    int4/int8 leaves share this one shift and phase-2 payloads always fit
    back into their phase-1 width (tests/test_collectives.py pins this
    for w=4)."""
    return max((n - 1).bit_length(), 0)


# ---------------------------------------------------------------------------
# the shard_map body (one leaf at a time)
# ---------------------------------------------------------------------------

def _wire_leaf(e: jax.Array, axes: Tuple[str, ...], n: int, kind: str,
               stacked: bool, bits: int = 8
               ) -> Tuple[jax.Array, jax.Array]:
    """Compressed mean-reduce of one per-shard leaf inside shard_map.

    ``e`` is this shard's ``grad + residual`` (leading shard axis of size 1
    already squeezed).  Returns ``(delivered_mean, new_residual)``.
    ``bits`` is the leaf's plan wire width; <= 4 nibble-packs the payload
    around each collective (chunk length, scales, and residual layout are
    untouched — only the bytes on the wire halve).
    """
    dtype = e.dtype
    rows = _layer_rows(e, stacked)
    L, Pn = rows.shape
    amax = None
    if kind != "bf16":     # bf16 payloads carry their own exponents
        amax = jax.lax.pmax(jnp.max(jnp.abs(rows), axis=1), axes)
        _record("pmax.scale", _ring_allreduce_bytes(L * 4, n))
    payload, scale, residual = _phase1_quantize(e, amax, kind, stacked,
                                                bits)

    flat = payload.reshape(-1)
    T = flat.shape[0]
    C = -(-T // n)
    flat = jnp.pad(flat, (0, n * C - T))
    # per-position grid steps, padded the same way (bf16 rows share scale 1)
    s_flat = jnp.pad(jnp.broadcast_to(scale[:, None], (L, Pn)).reshape(-1),
                     (0, n * C - T), constant_values=1.0)

    nib = _nibble_wire(kind, bits)
    wtag = "int4" if nib else kind

    # phase 1: reduce-scatter as all_to_all of the compressed chunks
    # (nibble wires pack two mantissas per byte around the collective;
    # each chunk packs independently so nibbles never straddle chunks)
    if nib:
        from ..kernels.qmatmul.ops import pack_nibbles, unpack_nibbles
        pk = pack_nibbles(flat.reshape(n, C), axis=-1)
        _record(f"all_to_all.{wtag}",
                (n - 1) / n * (n * pk.shape[-1]) * pk.dtype.itemsize)
        ex = unpack_nibbles(
            jax.lax.all_to_all(pk, axes, 0, 0, tiled=False), C, axis=-1)
    else:
        _record(f"all_to_all.{wtag}",
                (n - 1) / n * (n * C) * flat.dtype.itemsize)
        ex = jax.lax.all_to_all(flat.reshape(n, C), axes, 0, 0, tiled=False)
    chunk_sum = jnp.sum(ex.astype(jnp.float32 if kind == "bf16"
                                  else jnp.int32), axis=0)

    # phase 2: requantize the sum, gather, decode once (the shift keeps
    # phase-2 mantissas inside the phase-1 width — see _phase2_shift)
    q2, err2 = _phase2_requantize(chunk_sum, n, kind)
    if nib:
        q2p = pack_nibbles(q2, axis=-1)
        _record(f"all_gather.{wtag}",
                (n - 1) * q2p.shape[0] * q2p.dtype.itemsize)
        full = unpack_nibbles(
            jax.lax.all_gather(q2p, axes, axis=0, tiled=False),
            C, axis=-1).reshape(-1)
    else:
        _record(f"all_gather.{wtag}", (n - 1) * C * q2.dtype.itemsize)
        full = jax.lax.all_gather(q2, axes, axis=0, tiled=False).reshape(-1)
    if kind == "bf16":
        delivered_flat = full.astype(jnp.float32) / n
        err2_val = err2  # value domain; carried in full so delivery /n
        #                  next step recovers exactly what was withheld
    else:
        delivered_flat = (full.astype(jnp.float32) * (2 ** _phase2_shift(n))
                          * s_flat / n)
        err2_val = err2  # mantissa units; scaled to values below
    delivered = delivered_flat[:T].reshape(e.shape).astype(dtype)

    # error feedback for phase 2: the owner of chunk i carries the shift
    # remainder forward — next step it is re-quantized and delivered,
    # so the time-averaged delivered mean telescopes exactly
    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    if kind != "bf16":
        own_scale = jax.lax.dynamic_slice(s_flat, (idx * C,), (C,))
        err2_val = err2_val * own_scale
    scatter = jax.lax.dynamic_update_slice(
        jnp.zeros((n * C,), jnp.float32), err2_val, (idx * C,))[:T]
    new_residual = (residual + scatter.reshape(e.shape)).astype(dtype)
    return delivered, new_residual


# ---------------------------------------------------------------------------
# fused / pipelined tree-level exchange
# ---------------------------------------------------------------------------

def _bucket_leaves(byte_sizes, bucket_bytes: int):
    """Greedy size-bucketed partition of leaf indices, largest first:
    each bucket's wire payload stays under ``bucket_bytes`` (a single
    oversized leaf gets its own bucket).  Deterministic in the leaf
    order, so the fused trace is stable across runs."""
    order = sorted(range(len(byte_sizes)),
                   key=lambda i: (-byte_sizes[i], i))
    buckets, cur, acc = [], [], 0.0
    for i in order:
        if cur and acc + byte_sizes[i] > bucket_bytes:
            buckets.append(cur)
            cur, acc = [], 0.0
        cur.append(i)
        acc += byte_sizes[i]
    if cur:
        buckets.append(cur)
    return buckets


def _pipelined_collective(buckets, build, collective):
    """Double-buffered bucket pipeline: bucket k's collective is issued
    BEFORE bucket k+1's payload is built, so program order lets an async
    backend overlap compression with the bytes in flight (and even a
    synchronous backend pays ~#buckets collective launches instead of
    one per leaf)."""
    if not buckets:
        return []
    outs = [None] * len(buckets)
    pending = build(0)
    for b in range(len(buckets)):
        inflight = collective(pending)
        if b + 1 < len(buckets):
            pending = build(b + 1)
        outs[b] = inflight
    return outs


def _split_cols(buf, idxs, cols, axis):
    """Undo a column concatenation: static per-leaf slices of ``buf``."""
    out = {}
    off = 0
    for i in idxs:
        out[i] = jax.lax.slice_in_dim(buf, off, off + cols[i], axis=axis)
        off += cols[i]
    return out


def _wire_tree_fused(flat: List[jax.Array], axes: Tuple[str, ...], n: int,
                     kind: str, flags: Tuple[bool, ...],
                     widths: Tuple[int, ...], bucket_bytes: int
                     ) -> List[Tuple[jax.Array, jax.Array]]:
    """Fused/pipelined twin of mapping :func:`_wire_leaf` over a tree.

    One amax ``pmax`` covers every leaf (pmax is elementwise, so the
    pmax of the concatenated amax rows equals the concatenation of the
    per-leaf pmaxes), quantize/pack/decode run through the
    ``kernels.wire_pack`` fused kernels, and both exchange phases move
    size-bucketed buffers of column-concatenated leaf chunks — the
    collectives act on axis 0, so splitting columns after the exchange
    reproduces every per-leaf result exactly.  Byte records keep the
    per-leaf legacy tags and values: their totals ARE the fused
    buffers' bytes (tests pin both the equality with the per-leaf path
    and the recorded totals).
    """
    from ..kernels import wire_pack as wp
    from ..kernels.qmatmul.ops import unpack_nibbles
    N = len(flat)
    f32 = [jnp.asarray(e, jnp.float32) for e in flat]
    rows = [_layer_rows(e, st) for e, st in zip(f32, flags)]
    dims = []
    for r in rows:
        L, Pn = r.shape
        T = L * Pn
        dims.append((L, Pn, T, -(-T // n)))
    nibs = [_nibble_wire(kind, b) for b in widths]
    # nibble leaves pre-pad their chunk columns to EVEN with a zero
    # mantissa on scale 1 — the very zero nibble pack_nibbles would add —
    # so packing the column-concatenated bucket equals concatenating the
    # per-leaf packs (no pair straddles a leaf boundary)
    ceven = [(-(-C // 2) * 2 if nib else C)
             for (_, _, _, C), nib in zip(dims, nibs)]
    cols = [(ce // 2 if nib else ce) for ce, nib in zip(ceven, nibs)]
    item = 2 if kind == "bf16" else 1
    # width-homogeneous buckets: one saturating clip bound (and one
    # nibble flag) per bucket, so each bucket quantizes, requantizes and
    # decodes in a SINGLE fused elementwise chain over its concatenated
    # buffer — per-leaf work shrinks to pad/reshape/slice
    classes: dict = {}
    for i in range(N):
        classes.setdefault(widths[i] if kind != "bf16" else 0,
                           []).append(i)
    buckets = []
    for key in sorted(classes):
        idxs = classes[key]
        for b in _bucket_leaves([n * cols[i] * item for i in idxs],
                                bucket_bytes):
            buckets.append([idxs[j] for j in b])

    amaxes: List[Optional[jax.Array]] = [None] * N
    if kind != "bf16":
        gmax = jax.lax.pmax(
            jnp.concatenate([jnp.max(jnp.abs(r), axis=1) for r in rows]),
            axes)
        off = 0
        for i, (L, _, _, _) in enumerate(dims):
            amaxes[i] = jax.lax.slice_in_dim(gmax, off, off + L)
            off += L
            _record("pmax.scale", _ring_allreduce_bytes(L * 4, n))

    def chunked(i):
        """One leaf's (values, scales) in padded chunk layout [n, ceven]
        — positionwise identical to the rows layout, chunk row d = the
        slice shard d will own."""
        L, Pn, T, C = dims[i]
        e = jnp.pad(rows[i].reshape(-1), (0, n * C - T)).reshape(n, C)
        if ceven[i] != C:
            e = jnp.pad(e, ((0, 0), (0, ceven[i] - C)))
        if kind == "bf16":
            return e, None
        s = jnp.pad(
            jnp.broadcast_to(wp.grid_scale(amaxes[i], widths[i])[:, None],
                             (L, Pn)).reshape(-1),
            (0, n * C - T), constant_values=1.0).reshape(n, C)
        if ceven[i] != C:
            s = jnp.pad(s, ((0, 0), (0, ceven[i] - C)),
                        constant_values=1.0)
        return e, s

    bstate: List[Any] = [None] * len(buckets)

    def compress(b):
        idxs = buckets[b]
        pieces = [chunked(i) for i in idxs]
        E = jnp.concatenate([p[0] for p in pieces], axis=1)
        if kind == "bf16":
            payload = E.astype(jnp.bfloat16)
            S, R = None, E - payload.astype(jnp.float32)
        else:
            S = jnp.concatenate([p[1] for p in pieces], axis=1)
            payload, R = wp.quantize_chunks(E, S, widths[idxs[0]])
        bstate[b] = (S, R)
        for i in idxs:
            _record(f"all_to_all.{'int4' if nibs[i] else kind}",
                    (n - 1) / n * (n * cols[i]) * item)
        if nibs[idxs[0]]:
            payload = wp.pack_chunks(payload)
        return payload

    a2a = _pipelined_collective(
        buckets, compress,
        lambda x: jax.lax.all_to_all(x, axes, 0, 0, tiled=False))

    err2c: List[Any] = [None] * len(buckets)

    def requant(b):
        idxs = buckets[b]
        x = a2a[b]
        if nibs[idxs[0]]:
            x = unpack_nibbles(x, sum(ceven[i] for i in idxs), axis=-1)
        chunk_sum = jnp.sum(x.astype(jnp.float32 if kind == "bf16"
                                     else jnp.int32), axis=0)
        q2, err2c[b] = _phase2_requantize(chunk_sum, n, kind)
        if nibs[idxs[0]]:
            q2 = wp.pack_chunks(q2)
        for i in idxs:
            _record(f"all_gather.{'int4' if nibs[i] else kind}",
                    (n - 1) * cols[i] * q2.dtype.itemsize)
        return q2

    gath = _pipelined_collective(
        buckets, requant,
        lambda x: jax.lax.all_gather(x, axes, axis=0, tiled=False))

    idx = jnp.int32(0)
    for ax in axes:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)

    out: List[Any] = [None] * N
    for b, idxs in enumerate(buckets):
        f = gath[b]
        if nibs[idxs[0]]:
            f = unpack_nibbles(f, sum(ceven[i] for i in idxs), axis=-1)
        S, R = bstate[b]
        if kind == "bf16":
            dcat = f.astype(jnp.float32) / n
            ecat = err2c[b]
        else:
            dcat = wp.dequant_sum(f, S, _phase2_shift(n), n)
            ecat = err2c[b] * jax.lax.dynamic_slice_in_dim(
                S, idx, 1, axis=0)[0]
        off = 0
        for i in idxs:
            _, _, T, C = dims[i]
            e = flat[i]
            ce = ceven[i]
            d = jax.lax.slice_in_dim(dcat, off, off + ce, axis=1)[:, :C]
            delivered = d.reshape(-1)[:T].reshape(e.shape).astype(e.dtype)
            residual = jax.lax.slice_in_dim(
                R, off, off + ce, axis=1)[:, :C].reshape(-1)[:T] \
                .reshape(e.shape)
            ev = jax.lax.slice_in_dim(ecat, off, off + ce, axis=0)[:C]
            scatter = jax.lax.dynamic_update_slice(
                jnp.zeros((n * C,), jnp.float32), ev, (idx * C,))[:T]
            out[i] = (delivered,
                      (residual + scatter.reshape(e.shape)).astype(e.dtype))
            off += ce
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def ef_wire_init(grads: Any, n_data: int) -> Any:
    """Zero per-shard residual tree: each leaf gains a leading ``[n_data]``
    shard axis (sharded over the data axes by
    ``sharding.ef_residual_sharding``)."""
    return jax.tree.map(
        lambda g: jnp.zeros((n_data,) + tuple(g.shape), g.dtype), grads)


def _check_kind(kind: str) -> None:
    if kind not in WIRE_KINDS:
        raise ValueError(f"unsupported wire compression kind {kind!r}; "
                         f"supported: {WIRE_KINDS}")


def _wire_pmean_impl(e_stacked: Any, mesh, kind: str,
                     flags: Tuple[bool, ...],
                     widths: Tuple[int, ...], fused: bool = True,
                     bucket_bytes: int = _WIRE_BUCKET_BYTES
                     ) -> Tuple[Any, Any]:
    axes = data_axis_names(mesh)
    n = data_axis_size(mesh)

    def body(tree):
        flat, treedef = jax.tree.flatten(tree)
        squeezed = [leaf[0] for leaf in flat]
        if fused:
            pairs = _wire_tree_fused(squeezed, axes, n, kind, flags,
                                     widths, bucket_bytes)
        else:
            pairs = [_wire_leaf(leaf, axes, n, kind, st, b)
                     for leaf, st, b in zip(squeezed, flags, widths)]
        delivered = jax.tree.unflatten(treedef, [d for d, _ in pairs])
        residual = jax.tree.unflatten(treedef, [r[None] for _, r in pairs])
        return delivered, residual

    stack_spec = jax.tree.map(
        lambda leaf: P(axes, *([None] * (leaf.ndim - 1))), e_stacked)
    plain_spec = jax.tree.map(
        lambda leaf: P(*([None] * (leaf.ndim - 1))), e_stacked)
    return jax.shard_map(body, mesh=mesh, in_specs=(stack_spec,),
                     out_specs=(plain_spec, stack_spec),
                     check_vma=False)(e_stacked)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _ef_wire_pmean_cv(e_stacked: Any, mesh, kind: str,
                      flags: Tuple[bool, ...],
                      widths: Tuple[int, ...], fused: bool,
                      bucket_bytes: int) -> Tuple[Any, Any]:
    return _wire_pmean_impl(e_stacked, mesh, kind, flags, widths, fused,
                            bucket_bytes)


def _ef_wire_fwd(e_stacked, mesh, kind, flags, widths, fused,
                 bucket_bytes):
    return _ef_wire_pmean_cv(e_stacked, mesh, kind, flags, widths, fused,
                             bucket_bytes), None


def _ef_wire_bwd(mesh, kind, flags, widths, fused, bucket_bytes, _res,
                 cts):
    ct_delivered, _ct_residual = cts
    n = data_axis_size(mesh)
    ct_e = jax.tree.map(
        lambda ct: jnp.broadcast_to(ct[None] / n, (n,) + tuple(ct.shape)),
        ct_delivered)
    return (ct_e,)


_ef_wire_pmean_cv.defvjp(_ef_wire_fwd, _ef_wire_bwd)


def ef_wire_pmean(e_stacked: Any, mesh, kind: str = "int8",
                  stacked: Any = None, widths: Any = None,
                  fused: bool = True,
                  bucket_bytes: Optional[int] = None) -> Tuple[Any, Any]:
    """Compressed mean all-reduce with error feedback, inside the wire.

    ``e_stacked`` is a pytree whose leaves carry a leading ``[n_data]``
    shard axis holding each data shard's ``local_grad + residual``
    (sharded over the data axes).  Returns ``(delivered, new_residual)``:
    the int8/bf16-wire mean gradient, replicated, plus the per-shard
    residual to thread into the next step.

    ``stacked`` optionally marks stacked-layer leaves (a matching bool
    tree) for per-layer quantization grids; default derives it from the
    tree paths, like ``dist.ef_compress``.  ``widths`` optionally carries
    per-leaf wire widths (a matching int tree, e.g. from
    ``core.plan.PrecisionPlan.wire_bits_tree``); ``None`` is uniform int8
    — the exact legacy trace.  Widths <= 4 ride nibble-packed int4 bytes.

    ``fused`` (default) runs the pipelined tree-level exchange —
    bit-for-bit the per-leaf trace, with quantize/pack fused into the
    ``kernels.wire_pack`` kernels and the leaves bucketed so compression
    of bucket k+1 overlaps bucket k's collective; ``fused=False`` keeps
    the original one-collective-set-per-leaf reference.  ``bucket_bytes``
    overrides the pipeline bucket budget (mainly for tests).

    The custom VJP passes the ``delivered`` cotangent through as the
    transpose of an uncompressed shard mean, so the backward of a loss
    containing this collective is unchanged and ``jax.value_and_grad``
    composes; residual cotangents are dropped (state, not value).
    """
    _check_kind(kind)
    bb = _WIRE_BUCKET_BYTES if bucket_bytes is None else int(bucket_bytes)
    return _ef_wire_pmean_cv(e_stacked, mesh, kind,
                             _stacked_flags(e_stacked, stacked),
                             _width_flags(e_stacked, widths),
                             bool(fused), bb)


# ---------------------------------------------------------------------------
# 2D (data x model) sliced wire collective
# ---------------------------------------------------------------------------
#
# The 1D collective above replicates over the model axis: every TP shard
# exchanges and reduces the FULL gradient (and, under TP, first pays an
# fp32 all_gather over `model` to rematerialize it, since gradients of
# model-sharded parameters arrive model-sharded).  The 2D path slices the
# exchange over `model` too:
#
#   * gradients ENTER model-sharded (per-leaf in_specs reuse the exact
#     `sharding.model_axis_for` placement rule, so no model-axis gather is
#     emitted at all); leaves that do not shard over `model` are flat-chunk
#     sliced by model index instead — either way device (d, m) quantizes
#     only its 1/M slice;
#   * the two-phase int8 all_to_all + all_gather reduce runs over the data
#     axes on that slice only (1/M the bytes), with the same globally
#     pmax-shared per-row 2^-f grids — the pmax now spans BOTH axes;
#   * one int8 all_gather over `model` rematerializes each TP shard's full
#     delivered gradient (int8 sums decode once, after the gather), so the
#     model-axis replication that used to move fp32 now moves int8;
#   * error-feedback residuals live in the sliced layout: a stacked
#     [n_data, n_model, C] flat tree (`ef_wire2d_init`), sharded so device
#     (d, m) keeps exactly its own slice (`sharding.ef_residual_sharding`
#     with layout="2d").  Both phase errors stay within the slice, so the
#     time-averaged delivered mean telescopes exactly as in 1D.
#
# Per-device payload bytes per gradient element (D data x M model):
#   1D:  (M-1)/M * 4 (fp32 model ag)  +  2 (D-1)/D * 1   (int8 data phases)
#   2D:  2 (D-1)/(D*M) * 1            +  (M-1)/M * 1     (int8 model ag)
# e.g. on a 2x4 mesh: 4.0 B/elt -> 1.0 B/elt.


def _wire2d_model_axes(mesh) -> Tuple[str, ...]:
    return ("model",) if "model" in mesh.axis_names else ()


def model_axis_size(mesh) -> int:
    """Size of the mesh's tensor-parallel ``model`` axis (1 if absent)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("model", 1))


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def wire2d_slice_len(shape, n_data: int, n_model: int) -> int:
    """Padded flat slice length ``C`` each ``(data, model)`` device owns
    for a leaf of ``shape``: the model block (when the leaf shards over
    ``model`` per :func:`repro.dist.sharding.model_axis_for`) or the
    ceil-div flat slice, padded up to a multiple of ``n_data`` so the data
    all_to_all chunks evenly."""
    from .sharding import model_axis_for
    T = _prod(shape)
    if model_axis_for(shape, n_model) is not None:
        Tb = T // n_model
    else:
        Tb = -(-T // n_model)
    return n_data * (-(-Tb // n_data))


def ef_wire2d_init(grads: Any, n_data: int, n_model: int) -> Any:
    """Zero residual tree in the 2D sliced layout: each leaf becomes a
    flat ``[n_data, n_model, C]`` stack (``C`` from
    :func:`wire2d_slice_len`) addressable by ``(data, model)`` index —
    shard with ``sharding.ef_residual_sharding(..., layout='2d')``.  A
    mesh rescale changes ``C`` (or the leading axes), so a checkpointed
    residual from another mesh fails template restore loudly — callers
    warn and restart it at zero."""
    return jax.tree.map(
        lambda g: jnp.zeros(
            (n_data, n_model,
             wire2d_slice_len(g.shape, n_data, n_model)), g.dtype), grads)


def _wire2d_rows(shape, stacked: bool) -> Tuple[int, int]:
    """(L, row_len) of a leaf: one quantization row per leading
    (stacked-layer) axis entry for stacked rank >= 3 leaves, one per
    tensor otherwise — the same rule as :func:`_layer_rows`."""
    L = int(shape[0]) if (stacked and len(shape) >= 3) else 1
    return L, _prod(shape) // max(L, 1)


def _wire2d_leaf(g: jax.Array, r: jax.Array, S: Tuple[int, ...],
                 k: Optional[int], daxes: Tuple[str, ...], maxes:
                 Tuple[str, ...], D: int, M: int, kind: str, stacked: bool,
                 bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Sliced compressed mean-reduce of one leaf inside shard_map.

    ``g`` is this device's gradient block (data axis squeezed; the model
    block when ``k`` names the model-sharded tensor axis, else the full
    leaf), ``r`` its ``[C]`` flat residual slice.  Returns
    ``(delivered_full, new_residual_slice)``.  ``bits`` is the leaf's
    plan wire width; <= 4 nibble-packs every payload (all three
    collectives) while slice/residual layouts stay unchanged.
    """
    dtype = g.dtype
    axes2d = tuple(daxes) + tuple(maxes)
    g32 = jnp.asarray(g, jnp.float32)
    L, Prow_full = _wire2d_rows(S, stacked)
    if k is not None:
        B = g.shape                      # model block; block rows keep L
        Tb = g32.size
        C = -(-Tb // D)
        Cp = D * C
        Prow = Tb // L
        sl = jnp.pad(g32.reshape(-1), (0, Cp - Tb))
        row_of = jnp.minimum(jnp.arange(Cp) // Prow, L - 1)
    else:
        T = g32.size                     # full leaf; slice by model index
        Tb = -(-T // M)
        C = -(-Tb // D)
        Cp = D * C
        flat_full = jnp.pad(g32.reshape(-1), (0, M * Cp - T))
        midx = (jax.lax.axis_index(maxes[0]) if maxes else jnp.int32(0))
        sl = jax.lax.dynamic_slice(flat_full, (midx * Cp,), (Cp,))
        pos = midx * Cp + jnp.arange(Cp)
        row_of = jnp.minimum(pos // Prow_full, L - 1)
    e = sl + jnp.asarray(r, jnp.float32)

    if kind == "bf16":
        s_sl = jnp.ones((Cp,), jnp.float32)
        payload = e.astype(jnp.bfloat16)
        deq = payload.astype(jnp.float32)
    else:
        # per-row amax of |grad + residual| over every (data, model)
        # slice: the 2D pmax makes the 2^-f grid global, so int32 chunk
        # sums stay exact and every device decodes on the same scales
        local_amax = jnp.zeros((L,), jnp.float32).at[row_of].max(jnp.abs(e))
        amax = jax.lax.pmax(local_amax, axes2d)
        _record("pmax.scale", _ring_allreduce_bytes(L * 4, D * M))
        from ..core.quantizer import _exp2i
        from ..kernels.qmatmul.ops import grid_exponent
        scale = _exp2i(-grid_exponent(amax, bits))      # [L]
        s_sl = scale[row_of]
        qmax = 2 ** (bits - 1) - 1
        payload = jnp.clip(jnp.round(e / s_sl), -qmax,
                           qmax).astype(jnp.int8)
        deq = payload.astype(jnp.float32) * s_sl
    res1 = e - deq

    nib = _nibble_wire(kind, bits)
    wtag = "int4" if nib else kind
    if nib:
        from ..kernels.qmatmul.ops import pack_nibbles, unpack_nibbles

    # phase 1: reduce-scatter the slice over data as all_to_all
    acc_t = jnp.float32 if kind == "bf16" else jnp.int32
    if D > 1:
        if nib:
            pk = pack_nibbles(payload.reshape(D, C), axis=-1)
            _record(f"all_to_all.{wtag}",
                    (D - 1) / D * (D * pk.shape[-1]) * pk.dtype.itemsize)
            ex = unpack_nibbles(
                jax.lax.all_to_all(pk, daxes, 0, 0, tiled=False),
                C, axis=-1)
        else:
            _record(f"all_to_all.{wtag}",
                    (D - 1) / D * Cp * payload.dtype.itemsize)
            ex = jax.lax.all_to_all(payload.reshape(D, C), daxes, 0, 0,
                                    tiled=False)
        chunk_sum = jnp.sum(ex.astype(acc_t), axis=0)
    else:
        chunk_sum = payload.astype(acc_t)

    # phase 2: requantize the owned chunk, gather the slice over data
    q2, err2 = _phase2_requantize(chunk_sum, D, kind)
    if D > 1:
        if nib:
            q2p = pack_nibbles(q2, axis=-1)
            _record(f"all_gather.{wtag}",
                    (D - 1) * q2p.shape[0] * q2p.dtype.itemsize)
            sl_q = unpack_nibbles(
                jax.lax.all_gather(q2p, daxes, axis=0, tiled=False),
                C, axis=-1).reshape(Cp)
        else:
            _record(f"all_gather.{wtag}", (D - 1) * C * q2.dtype.itemsize)
            sl_q = jax.lax.all_gather(q2, daxes, axis=0, tiled=False
                                      ).reshape(Cp)
    else:
        sl_q = q2.reshape(Cp)

    # phase 3: rematerialize over model — the quantized sums cross the
    # model axis, not fp32; decode once after the gather
    if maxes and M > 1:
        if nib:
            slp = pack_nibbles(sl_q, axis=-1)
            _record(f"all_gather.{wtag}.model",
                    (M - 1) * slp.shape[0] * slp.dtype.itemsize)
            gath = unpack_nibbles(
                jax.lax.all_gather(slp, maxes, axis=0, tiled=False),
                Cp, axis=-1)
        else:
            _record(f"all_gather.{wtag}.model",
                    (M - 1) * Cp * sl_q.dtype.itemsize)
            gath = jax.lax.all_gather(sl_q, maxes, axis=0, tiled=False)
    else:
        gath = sl_q[None]

    shift = 2 ** _phase2_shift(D)
    if k is not None:
        if kind == "bf16":
            dec = gath.astype(jnp.float32) / D
        else:
            dec = gath.astype(jnp.float32) * shift * s_sl[None] / D
        blocks = dec[:, :Tb].reshape((gath.shape[0],) + tuple(B))
        delivered = jnp.concatenate(
            [blocks[m] for m in range(blocks.shape[0])], axis=k)
    else:
        flat_q = gath.reshape(-1)                       # [M * Cp]
        if kind == "bf16":
            dec = flat_q.astype(jnp.float32) / D
        else:
            row_full = jnp.minimum(jnp.arange(flat_q.shape[0]) // Prow_full,
                                   L - 1)
            dec = flat_q.astype(jnp.float32) * shift * scale[row_full] / D
        delivered = dec[:_prod(S)].reshape(S)

    # phase-2 error feedback: the chunk owner keeps the shift remainder
    # inside its own slice, exactly like the 1D path
    didx = jnp.int32(0)
    for ax in daxes:
        didx = didx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    if kind != "bf16":
        err2_val = err2 * jax.lax.dynamic_slice(s_sl, (didx * C,), (C,))
    else:
        err2_val = err2
    new_r = res1 + jax.lax.dynamic_update_slice(
        jnp.zeros((Cp,), jnp.float32), err2_val, (didx * C,))
    return delivered.astype(dtype), new_r.astype(r.dtype)


def _wire2d_tree_fused(gflat: List[jax.Array], rflat: List[jax.Array],
                       shapes, ks, daxes: Tuple[str, ...],
                       maxes: Tuple[str, ...], D: int, M: int, kind: str,
                       flags: Tuple[bool, ...], widths: Tuple[int, ...],
                       bucket_bytes: int
                       ) -> List[Tuple[jax.Array, jax.Array]]:
    """Fused/pipelined twin of mapping :func:`_wire2d_leaf` over a tree:
    one 2D amax ``pmax`` for every leaf, wire_pack kernels for the
    elementwise stages, and all three exchanges (data all_to_all, data
    all_gather, model all_gather) pipelined over size-bucketed
    column-concatenated buffers.  Bit-for-bit the per-leaf path, by the
    same commutation arguments as :func:`_wire_tree_fused`; byte records
    keep the per-leaf legacy tags and values, including the pure-TP op
    set (no data-exchange records when D == 1)."""
    from ..kernels import wire_pack as wp
    from ..kernels.qmatmul.ops import unpack_nibbles
    axes2d = tuple(daxes) + tuple(maxes)
    N = len(gflat)
    midx = (jax.lax.axis_index(maxes[0]) if maxes else jnp.int32(0))

    info = []
    for g, r, S, k, st in zip(gflat, rflat, shapes, ks, flags):
        g32 = jnp.asarray(g, jnp.float32)
        L, Prow_full = _wire2d_rows(S, st)
        if k is not None:
            Tb = g32.size
            C = -(-Tb // D)
            Cp = D * C
            sl = jnp.pad(g32.reshape(-1), (0, Cp - Tb))
            row_of = jnp.minimum(jnp.arange(Cp) // (Tb // L), L - 1)
        else:
            T = g32.size
            Tb = -(-T // M)
            C = -(-Tb // D)
            Cp = D * C
            flat_full = jnp.pad(g32.reshape(-1), (0, M * Cp - T))
            sl = jax.lax.dynamic_slice(flat_full, (midx * Cp,), (Cp,))
            pos = midx * Cp + jnp.arange(Cp)
            row_of = jnp.minimum(pos // Prow_full, L - 1)
        info.append(dict(e=sl + jnp.asarray(r, jnp.float32), row_of=row_of,
                         L=L, Prow_full=Prow_full, C=C, Cp=Cp, Tb=Tb,
                         B=tuple(g.shape)))

    scales: List[Optional[jax.Array]] = [None] * N
    if kind != "bf16":
        gmax = jax.lax.pmax(jnp.concatenate(
            [jnp.zeros((inf["L"],), jnp.float32).at[inf["row_of"]].max(
                jnp.abs(inf["e"])) for inf in info]), axes2d)
        off = 0
        for i, inf in enumerate(info):
            L = inf["L"]
            amax = jax.lax.slice_in_dim(gmax, off, off + L)
            off += L
            _record("pmax.scale", _ring_allreduce_bytes(L * 4, D * M))
            scales[i] = wp.grid_scale(amax, widths[i])

    nibs = [_nibble_wire(kind, w) for w in widths]
    item = 2 if kind == "bf16" else 1
    cols = [(-(-inf["C"] // 2) if nib else inf["C"])
            for inf, nib in zip(info, nibs)]
    buckets = _bucket_leaves([D * c * item for c in cols], bucket_bytes)

    state: List[Any] = [None] * N
    acc_t = jnp.float32 if kind == "bf16" else jnp.int32

    def compress(i):
        """Quantize leaf i's slice -> payload [D, C] in the wire dtype."""
        inf = info[i]
        C = inf["C"]
        if kind == "bf16":
            s_sl = jnp.ones((inf["Cp"],), jnp.float32)
            payload = inf["e"].astype(jnp.bfloat16)
            res1 = inf["e"] - payload.astype(jnp.float32)
            payload = payload.reshape(D, C)
        else:
            s_sl = scales[i][inf["row_of"]]
            payload, res = wp.quantize_chunks(
                inf["e"].reshape(D, C), s_sl.reshape(D, C), widths[i])
            res1 = res.reshape(-1)
        state[i] = (s_sl, res1)
        return payload

    err2s: List[Any] = [None] * N
    slq: List[Any] = [None] * N
    if D > 1:
        def build1(b):
            parts = []
            for i in buckets[b]:
                p = compress(i)
                wtag = "int4" if nibs[i] else kind
                if nibs[i]:
                    p = wp.pack_chunks(p)
                    _record(f"all_to_all.{wtag}", (D - 1) / D
                            * (D * p.shape[-1]) * p.dtype.itemsize)
                else:
                    _record(f"all_to_all.{wtag}",
                            (D - 1) / D * info[i]["Cp"] * p.dtype.itemsize)
                parts.append(p)
            return jnp.concatenate(parts, axis=1)

        a2a = _pipelined_collective(
            buckets, build1,
            lambda x: jax.lax.all_to_all(x, daxes, 0, 0, tiled=False))
        ex: dict = {}
        for b, bucket in enumerate(buckets):
            ex.update(_split_cols(a2a[b], bucket, cols, axis=1))

        def build2(b):
            parts = []
            for i in buckets[b]:
                C = info[i]["C"]
                x = ex[i]
                if nibs[i]:
                    x = unpack_nibbles(x, C, axis=-1)
                q2, err2s[i] = _phase2_requantize(
                    jnp.sum(x.astype(acc_t), axis=0), D, kind)
                wtag = "int4" if nibs[i] else kind
                if nibs[i]:
                    q2 = wp.pack_chunks(q2)
                _record(f"all_gather.{wtag}",
                        (D - 1) * q2.shape[0] * q2.dtype.itemsize)
                parts.append(q2)
            return jnp.concatenate(parts)

        gath2 = _pipelined_collective(
            buckets, build2,
            lambda x: jax.lax.all_gather(x, daxes, axis=0, tiled=False))
        for b, bucket in enumerate(buckets):
            got = _split_cols(gath2[b], bucket, cols, axis=1)
            for i in bucket:
                f = got[i]
                if nibs[i]:
                    f = unpack_nibbles(f, info[i]["C"], axis=-1)
                slq[i] = f.reshape(info[i]["Cp"])
    else:
        for i in range(N):
            payload = compress(i)
            q2, err2s[i] = _phase2_requantize(
                payload.reshape(-1).astype(acc_t), D, kind)
            slq[i] = q2.reshape(info[i]["Cp"])

    gth: List[Any] = [None] * N
    if maxes and M > 1:
        mcols = [(-(-inf["Cp"] // 2) if nib else inf["Cp"])
                 for inf, nib in zip(info, nibs)]

        def build3(b):
            parts = []
            for i in buckets[b]:
                mg = slq[i]
                wtag = "int4" if nibs[i] else kind
                if nibs[i]:
                    mg = wp.pack_chunks(mg)
                _record(f"all_gather.{wtag}.model",
                        (M - 1) * mg.shape[0] * mg.dtype.itemsize)
                parts.append(mg)
            return jnp.concatenate(parts)

        gath3 = _pipelined_collective(
            buckets, build3,
            lambda x: jax.lax.all_gather(x, maxes, axis=0, tiled=False))
        for b, bucket in enumerate(buckets):
            got = _split_cols(gath3[b], bucket, mcols, axis=1)
            for i in bucket:
                f = got[i]
                if nibs[i]:
                    f = unpack_nibbles(f, info[i]["Cp"], axis=-1)
                gth[i] = f
    else:
        for i in range(N):
            gth[i] = slq[i][None]

    didx = jnp.int32(0)
    for ax in daxes:
        didx = didx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)

    out = []
    shift_k = _phase2_shift(D)
    for i, (g, r, S, k) in enumerate(zip(gflat, rflat, shapes, ks)):
        inf = info[i]
        s_sl, res1 = state[i]
        gath = gth[i]
        C, Cp = inf["C"], inf["Cp"]
        if k is not None:
            if kind == "bf16":
                dec = gath.astype(jnp.float32) / D
            else:
                dec = wp.dequant_sum(gath, s_sl[None], shift_k, D)
            blocks = dec[:, :inf["Tb"]].reshape(
                (gath.shape[0],) + inf["B"])
            delivered = jnp.concatenate(
                [blocks[m] for m in range(blocks.shape[0])], axis=k)
        else:
            flat_q = gath.reshape(-1)
            if kind == "bf16":
                dec = flat_q.astype(jnp.float32) / D
            else:
                row_full = jnp.minimum(
                    jnp.arange(flat_q.shape[0]) // inf["Prow_full"],
                    inf["L"] - 1)
                dec = wp.dequant_sum(flat_q, scales[i][row_full],
                                     shift_k, D)
            delivered = dec[:_prod(S)].reshape(S)
        if kind != "bf16":
            err2_val = err2s[i] * jax.lax.dynamic_slice(
                s_sl, (didx * C,), (C,))
        else:
            err2_val = err2s[i]
        new_r = res1 + jax.lax.dynamic_update_slice(
            jnp.zeros((Cp,), jnp.float32), err2_val, (didx * C,))
        out.append((delivered.astype(g.dtype), new_r.astype(r.dtype)))
    return out


def _wire2d_specs(grads_stacked: Any, mesh):
    """(grad in_specs, residual spec tree, delivered out_specs) for the 2D
    collective: gradients enter stacked ``[n_data]`` over the data axes
    AND model-sharded on their natural tensor axis, residuals in the
    ``[n_data, n_model, C]`` sliced layout, delivered replicated."""
    from .sharding import model_axis_for
    daxes = data_axis_names(mesh)
    maxes = _wire2d_model_axes(mesh)
    M = model_axis_size(mesh)
    d_entry = daxes if len(daxes) > 1 else daxes[0]

    def gspec(leaf):
        entries: list = [None] * leaf.ndim
        entries[0] = d_entry
        k = model_axis_for(leaf.shape[1:], M)
        if k is not None and maxes:
            entries[k + 1] = "model"
        return P(*entries)

    gin = jax.tree.map(gspec, grads_stacked)
    rspec = jax.tree.map(
        lambda leaf: P(d_entry, "model" if maxes else None, None),
        grads_stacked)
    dout = jax.tree.map(lambda leaf: P(*([None] * (leaf.ndim - 1))),
                        grads_stacked)
    return gin, rspec, dout


def _wire2d_impl(grads_stacked: Any, residual: Any, mesh, kind: str,
                 flags: Tuple[bool, ...],
                 widths: Tuple[int, ...], fused: bool = True,
                 bucket_bytes: int = _WIRE_BUCKET_BYTES
                 ) -> Tuple[Any, Any]:
    from .sharding import model_axis_for
    daxes = data_axis_names(mesh)
    maxes = _wire2d_model_axes(mesh)
    D = data_axis_size(mesh)
    M = model_axis_size(mesh)
    shapes = [tuple(leaf.shape[1:])
              for leaf in jax.tree.leaves(grads_stacked)]
    ks = [model_axis_for(S, M) for S in shapes]

    def body(gtree, rtree):
        gflat, treedef = jax.tree.flatten(gtree)
        rflat, _ = jax.tree.flatten(rtree)
        if fused:
            pairs = _wire2d_tree_fused(
                [g[0] for g in gflat], [r[0, 0] for r in rflat], shapes,
                ks, daxes, maxes, D, M, kind, flags, widths, bucket_bytes)
        else:
            pairs = [
                _wire2d_leaf(g[0], r[0, 0], S, kk, daxes, maxes, D, M,
                             kind, st, b)
                for g, r, S, kk, st, b in zip(gflat, rflat, shapes, ks,
                                              flags, widths)]
        delivered = jax.tree.unflatten(treedef, [d for d, _ in pairs])
        new_res = jax.tree.unflatten(treedef,
                                     [nr[None, None] for _, nr in pairs])
        return delivered, new_res

    gin, rspec, dout = _wire2d_specs(grads_stacked, mesh)
    return jax.shard_map(body, mesh=mesh, in_specs=(gin, rspec),
                     out_specs=(dout, rspec), check_vma=False)(
                         grads_stacked, residual)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _wire2d_cv(grads_stacked: Any, residual: Any, mesh, kind: str,
               flags: Tuple[bool, ...],
               widths: Tuple[int, ...], fused: bool,
               bucket_bytes: int) -> Tuple[Any, Any]:
    return _wire2d_impl(grads_stacked, residual, mesh, kind, flags,
                        widths, fused, bucket_bytes)


def _wire2d_fwd(grads_stacked, residual, mesh, kind, flags, widths, fused,
                bucket_bytes):
    return _wire2d_cv(grads_stacked, residual, mesh, kind, flags,
                      widths, fused, bucket_bytes), None


def _wire2d_bwd(mesh, kind, flags, widths, fused, bucket_bytes, _res,
                cts):
    ct_delivered, ct_residual = cts
    n = data_axis_size(mesh)
    ct_g = jax.tree.map(
        lambda ct: jnp.broadcast_to(ct[None] / n, (n,) + tuple(ct.shape)),
        ct_delivered)
    ct_r = jax.tree.map(jnp.zeros_like, ct_residual)
    return (ct_g, ct_r)


_wire2d_cv.defvjp(_wire2d_fwd, _wire2d_bwd)


def ef_wire_pmean_2d(grads_stacked: Any, residual: Any, mesh,
                     kind: str = "int8", stacked: Any = None,
                     widths: Any = None, fused: bool = True,
                     bucket_bytes: Optional[int] = None
                     ) -> Tuple[Any, Any]:
    """2D-sliced compressed mean all-reduce with error feedback.

    ``grads_stacked`` is a pytree whose leaves carry a leading
    ``[n_data]`` shard axis (each data shard's local gradient — NOT
    pre-added with the residual: the add happens on the slice, inside the
    collective); ``residual`` the matching ``[n_data, n_model, C]`` tree
    from :func:`ef_wire2d_init`.  Returns ``(delivered, new_residual)``:
    the int8/bf16-wire mean gradient, replicated, plus the sliced residual
    for the next step.  ``stacked`` optionally marks stacked-layer leaves
    (default: derived from the tree paths, like ``dist.ef_compress``).
    ``widths`` optionally carries per-leaf wire widths (matching int
    tree); ``None`` is uniform int8 — the exact legacy trace.

    ``fused``/``bucket_bytes`` select the pipelined tree-level exchange
    exactly as in :func:`ef_wire_pmean` (default on; bit-for-bit the
    per-leaf trace).

    The custom VJP passes the ``delivered`` cotangent through as the
    transpose of an uncompressed shard mean (``ct / n_data`` per shard);
    residual cotangents are dropped (state, not value).
    """
    _check_kind(kind)
    bb = _WIRE_BUCKET_BYTES if bucket_bytes is None else int(bucket_bytes)
    return _wire2d_cv(grads_stacked, residual, mesh, kind,
                      _stacked_flags(grads_stacked, stacked),
                      _width_flags(grads_stacked, widths),
                      bool(fused), bb)


def simulate_wire_pmean_2d(grads_stacked: Any, residual: Any, n_model: int,
                           kind: str = "int8", stacked: Any = None,
                           widths: Any = None) -> Tuple[Any, Any]:
    """Collective-free reference of :func:`ef_wire_pmean_2d` on a stacked
    ``[n_data, ...]`` gradient tree plus its ``[n_data, n_model, C]``
    residual: same slicing, same grids, same chunking, same two-phase
    errors — usable on one device.  The 8-device CI job asserts the
    shard_map path matches this bit-for-bit on 2x4 and 4x2 meshes (mixed
    widths included: nibble pack/unpack is the identity on in-range
    mantissas, so the simulator never needs to model the packing)."""
    _check_kind(kind)
    from .sharding import model_axis_for
    flags = _stacked_flags(grads_stacked, stacked)
    wflags = _width_flags(grads_stacked, widths)

    def leaf(es, res, stk, bits):
        D = es.shape[0]
        M = n_model
        S = tuple(es.shape[1:])
        dtype = es.dtype
        T = _prod(S)
        L, Prow_full = _wire2d_rows(S, stk)
        k = model_axis_for(S, M)
        Cp = res.shape[-1]
        C = Cp // D
        shift = 2 ** _phase2_shift(D)

        # per-(d, m) flat slices + row ids (identical to the shard_map body)
        slices = [[None] * M for _ in range(D)]
        rows = [None] * M
        for d in range(D):
            g32 = jnp.asarray(es[d], jnp.float32).reshape(-1)
            for m in range(M):
                if k is not None:
                    Bk = S[k] // M
                    blk = jax.lax.slice_in_dim(
                        jnp.asarray(es[d], jnp.float32), m * Bk,
                        (m + 1) * Bk, axis=k)
                    Tb = blk.size
                    slices[d][m] = jnp.pad(blk.reshape(-1), (0, Cp - Tb))
                    rows[m] = jnp.minimum(
                        jnp.arange(Cp) // (Tb // L), L - 1)
                else:
                    flat = jnp.pad(g32, (0, M * Cp - T))
                    slices[d][m] = jax.lax.dynamic_slice(
                        flat, (m * Cp,), (Cp,))
                    pos = m * Cp + jnp.arange(Cp)
                    rows[m] = jnp.minimum(pos // Prow_full, L - 1)
        es_sl = [[slices[d][m] + jnp.asarray(res[d, m], jnp.float32)
                  for m in range(M)] for d in range(D)]

        if kind != "bf16":
            local = [jnp.zeros((L,), jnp.float32).at[rows[m]].max(
                jnp.abs(es_sl[d][m])) for d in range(D) for m in range(M)]
            amax = jnp.max(jnp.stack(local), axis=0)
            from ..core.quantizer import _exp2i
            from ..kernels.qmatmul.ops import grid_exponent
            scale = _exp2i(-grid_exponent(amax, bits))
            qmax = 2 ** (bits - 1) - 1

        delivered_slices = [None] * M
        new_res = [[None] * M for _ in range(D)]
        for m in range(M):
            if kind == "bf16":
                s_sl = jnp.ones((Cp,), jnp.float32)
                payloads = [es_sl[d][m].astype(jnp.bfloat16)
                            for d in range(D)]
                deqs = [p.astype(jnp.float32) for p in payloads]
            else:
                s_sl = scale[rows[m]]
                payloads = [jnp.clip(jnp.round(es_sl[d][m] / s_sl), -qmax,
                                     qmax).astype(jnp.int8)
                            for d in range(D)]
                deqs = [p.astype(jnp.float32) * s_sl for p in payloads]
            res1 = [es_sl[d][m] - deqs[d] for d in range(D)]
            acc_t = jnp.float32 if kind == "bf16" else jnp.int32
            stacked = jnp.stack([p.reshape(D, C) for p in payloads])
            sums = jnp.sum(stacked.astype(acc_t), axis=0)     # [D, C]
            q2, err2 = _phase2_requantize(sums, D, kind)
            sl_q = q2.reshape(Cp)
            if kind == "bf16":
                delivered_slices[m] = sl_q.astype(jnp.float32) / D
            else:
                delivered_slices[m] = (sl_q.astype(jnp.float32) * shift
                                       * s_sl / D)
            for d in range(D):
                if kind != "bf16":
                    err_val = err2[d] * jax.lax.dynamic_slice(
                        s_sl, (d * C,), (C,))
                else:
                    err_val = err2[d]
                new_res[d][m] = (res1[d] + jax.lax.dynamic_update_slice(
                    jnp.zeros((Cp,), jnp.float32), err_val, (d * C,))
                ).astype(res.dtype)

        if k is not None:
            Bk = S[k] // M
            B = S[:k] + (Bk,) + S[k + 1:]
            Tb = _prod(B)
            blocks = [delivered_slices[m][:Tb].reshape(B) for m in range(M)]
            delivered = jnp.concatenate(blocks, axis=k)
        else:
            delivered = jnp.concatenate(delivered_slices)[:T].reshape(S)
        nr = jnp.stack([jnp.stack([new_res[d][m] for m in range(M)])
                        for d in range(D)])
        return delivered.astype(dtype), nr

    gflat, treedef = jax.tree.flatten(grads_stacked)
    rflat, _ = jax.tree.flatten(residual)
    pairs = [leaf(g, r, st, b)
             for g, r, st, b in zip(gflat, rflat, flags, wflags)]
    return (jax.tree.unflatten(treedef, [d for d, _ in pairs]),
            jax.tree.unflatten(treedef, [r for _, r in pairs]))


def wire2d_leaf_bytes(shape, n_data: int, n_model: int, kind: str,
                      stacked: bool = False, bits: int = 8) -> float:
    """Analytic per-device wire bytes of one 2D-sliced mean-reduce of a
    leaf (matches :class:`record_wire_bytes` on the traced ops, at the
    leaf's ACTUAL wire width): data all_to_all + all_gather on the 1/M
    slice, the quantized model-axis all_gather, and the per-row scale
    pmax over all D*M devices.  ``stacked`` marks a stacked-layer leaf
    (per-layer scale rows); ``bits`` <= 4 counts nibble-packed chunk
    bytes.  tests/test_wire2d.py pins this against measured trace bytes
    per leaf for int8, bf16, and mixed widths."""
    _check_kind(kind)
    item = 1 if kind == "int8" else 2
    Cp = wire2d_slice_len(shape, n_data, n_model)
    C = Cp // n_data
    if _nibble_wire(kind, bits):
        chunk_b, slice_b = float(-(-C // 2)), float(-(-Cp // 2))
    else:
        chunk_b, slice_b = C * item, Cp * item
    a2a = (n_data - 1) * chunk_b if n_data > 1 else 0.0
    ag = (n_data - 1) * chunk_b if n_data > 1 else 0.0
    ag_model = (n_model - 1) * slice_b if n_model > 1 else 0.0
    L, _ = _wire2d_rows(shape, stacked)
    scales = (_ring_allreduce_bytes(L * 4, n_data * n_model)
              if kind == "int8" else 0.0)
    return a2a + ag + ag_model + scales


def tp_replication_bytes(shape, n_model: int) -> float:
    """Per-device fp32 bytes the 1D wire path pays to rematerialize a
    model-sharded gradient leaf before its model-replicated shard_map (an
    all_gather over ``model`` GSPMD inserts implicitly): zero when the
    leaf does not shard over ``model`` — and zero for the 2D path, whose
    in_specs consume the sharded gradient directly."""
    from .sharding import model_axis_for
    if n_model <= 1 or model_axis_for(shape, n_model) is None:
        return 0.0
    return (n_model - 1) * (_prod(shape) / n_model) * 4.0


def simulate_wire_pmean(e_stacked: Any, kind: str = "int8",
                        stacked: Any = None,
                        widths: Any = None) -> Tuple[Any, Any]:
    """Collective-free reference of :func:`ef_wire_pmean` on a stacked
    ``[n, ...]`` tree: same grids, same chunking, same two-phase errors —
    usable on one device (tests, notebooks).  The 8-device CI job asserts
    the shard_map path matches this bit-for-bit (mixed widths included —
    nibble pack/unpack is the identity on in-range mantissas, so the
    simulator never models the packing).  ``stacked`` optionally marks
    stacked-layer leaves (default: derived from the tree paths);
    ``widths`` optionally carries per-leaf wire widths."""
    _check_kind(kind)
    flags = _stacked_flags(e_stacked, stacked)
    wflags = _width_flags(e_stacked, widths)

    def leaf(es, stk, bits):
        n = es.shape[0]
        dtype = es.dtype
        shape = es.shape[1:]
        rows0 = _layer_rows(es[0], stk)
        L, Pn = rows0.shape
        amax = jnp.max(jnp.abs(jnp.asarray(es, jnp.float32)
                               .reshape(n, L, -1)), axis=(0, 2))
        payloads, residuals, scale = [], [], None
        for i in range(n):
            p, scale, r = _phase1_quantize(es[i], amax, kind, stk, bits)
            payloads.append(p.reshape(-1))
            residuals.append(r)
        T = payloads[0].shape[0]
        C = -(-T // n)
        pad = n * C - T
        stacked = jnp.stack([jnp.pad(p, (0, pad)) for p in payloads])
        s_flat = jnp.pad(jnp.broadcast_to(scale[:, None], (L, Pn))
                         .reshape(-1), (0, pad), constant_values=1.0)
        sums = jnp.sum(stacked.astype(jnp.float32 if kind == "bf16"
                                      else jnp.int32), axis=0)
        q2, err2 = _phase2_requantize(sums.reshape(n, C), n, kind)
        q2 = q2.reshape(-1)
        if kind == "bf16":
            delivered_flat = q2.astype(jnp.float32) / n
            err2_val = err2
        else:
            delivered_flat = (q2.astype(jnp.float32)
                              * (2 ** _phase2_shift(n)) * s_flat / n)
            err2_val = err2 * s_flat.reshape(n, C)
        delivered = delivered_flat[:T].reshape(shape).astype(dtype)
        scatter = jnp.zeros((n, n * C), jnp.float32)
        for i in range(n):
            scatter = scatter.at[i, i * C:(i + 1) * C].set(err2_val[i])
        new_res = jnp.stack([
            (residuals[i] + scatter[i, :T].reshape(shape)).astype(dtype)
            for i in range(n)])
        return delivered, new_res

    flat, treedef = jax.tree.flatten(e_stacked)
    pairs = [leaf(x, st, b) for x, st, b in zip(flat, flags, wflags)]
    return (jax.tree.unflatten(treedef, [d for d, _ in pairs]),
            jax.tree.unflatten(treedef, [r for _, r in pairs]))


def wire_bytes_model(n_elements: int, n: int, kind: str,
                     n_scale_rows: int = 1, bits: int = 8) -> float:
    """Analytic per-device bytes-on-wire of one compressed mean-reduce
    (matches what :class:`record_wire_bytes` measures on the traced ops):
    all_to_all + all_gather of 1-byte (int8) / 2-byte (bf16) / half-byte
    (nibble-packed, ``bits <= 4``) payloads plus the per-row fp32 scale
    pmax."""
    _check_kind(kind)
    item = 1 if kind == "int8" else 2
    C = -(-n_elements // n)
    chunk_b = float(-(-C // 2)) if _nibble_wire(kind, bits) else C * item
    a2a = (n - 1) / n * (n * chunk_b)
    ag = (n - 1) * chunk_b
    # bf16 payloads carry their own exponents — no scale pmax on that path
    scales = (_ring_allreduce_bytes(n_scale_rows * 4, n)
              if kind == "int8" else 0.0)
    return a2a + ag + scales


def fp32_allreduce_bytes(n_elements: int, n: int) -> float:
    """Per-device bytes of the ring fp32 all-reduce the wire path replaces."""
    return _ring_allreduce_bytes(n_elements * 4, n)
