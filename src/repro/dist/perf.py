"""Serving/throughput knobs: compute-dtype casting and HGQ int8 packing.

Compute dtype: layers call :func:`cast_for_matmul` on matmul operands so
fp32-master FSDP gathers and TP partial-sum all-reduces move bf16 bytes
(half the collective volume) when a run opts in.  The dtype a trace sees
is *scoped*: ``repro.api.RunContext`` activates its ``PrecisionSpec``
around every trace (:func:`compute_dtype_scope`), so two contexts with
different precisions coexist in one process.  The unscoped default is
``None`` (no cast).

Packing: :func:`pack_params_for_serving` rewrites every matmul weight dict
``{'w', 'f'}`` into ``{'w_int8', 'scale', 'f'}`` — int8 mantissas plus a
per-output-channel ``2^-f`` scale, the deployable representation the HGQ
paper's heterogeneous-bitwidth training produces.  ``nn.common.get_qw``
dequantizes at use (``unpack_weight``) and XLA fuses the dequant into the
consuming matmul, mirroring ``kernels/qmatmul``.  Halves decode HBM
traffic vs bf16.  The transform is shape-preserving and traceable, so the
dry-run can ``jax.eval_shape`` it over abstract params.

All knobs here are read at *trace* time: a jitted executable keeps
whatever was in scope when it traced, and later scope changes do not
retrace it.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .scope import Scoped

_COMPUTE: Scoped[Optional[Any]] = Scoped("repro.dist.compute_dtype", None)
_PACKED: Scoped[bool] = Scoped("repro.dist.packed_matmul", False)


def compute_dtype_scope(dtype):
    """Context manager: trace the enclosed computation with ``dtype`` as
    the matmul compute dtype (``None`` = no cast); restores on exit."""
    return _COMPUTE.scope(dtype)


def reset_precision() -> None:
    """Back to the no-cast / unpacked defaults (tests)."""
    _COMPUTE.reset_default()
    _PACKED.reset_default()


def get_compute_dtype():
    return _COMPUTE.get()


def cast_for_matmul(x: jax.Array) -> jax.Array:
    """Cast a floating matmul operand to the compute dtype, if one is set."""
    dtype = _COMPUTE.get()
    if dtype is None:
        return x
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# packed-matmul routing (serving/packed.py)
# ---------------------------------------------------------------------------

def get_packed_matmul() -> bool:
    return _PACKED.get()


class packed_matmul:
    """Context manager: trace/run the enclosed computation with the packed
    qmatmul routing set to ``on`` (restores the previous value on exit).

    On: dense projections over int8-packed kernels route onto the Pallas
    ``kernels.qmatmul.qmatmul_any`` path (read at trace time, like the
    compute dtype).  Off: packed kernels dequantize and use ``jnp.matmul``
    (XLA fuses the dequant)."""

    def __init__(self, on: bool = True):
        self.on = bool(on)
        self._cm = None

    def __enter__(self):
        self._cm = _PACKED.scope(self.on)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        cm, self._cm = self._cm, None
        return cm.__exit__(*exc)


# ---------------------------------------------------------------------------
# HGQ int8 serving-weight packing
# ---------------------------------------------------------------------------

def _packable(name: str, w) -> bool:
    if not hasattr(w, "ndim") or w.ndim < 2:
        return False          # biases, norm gains, scalars
    if not jnp.issubdtype(w.dtype, jnp.floating):
        return False
    if name == "bias":
        return False          # stacked biases are [L, d] but not matmuls
    if name == "kernel" and w.ndim >= 4:
        return False          # conv kernels: HConv2D reads 'w' directly
    return True


def _pack_one(p: Dict[str, Any], bits: int = 8) -> Dict[str, Any]:
    """One matmul-weight dict {'w', 'f'?} -> {'w_int8', 'scale', 'f'?}
    (or {'w_nib', ...} for sub-5-bit plan layers).

    The per-output-channel power-of-two grid (2^-f at the trained bits,
    capped so the channel amax fits the ``bits``-wide mantissa; with no
    'f' the cap alone) and the mantissas come from the single shared leaf
    packer ``kernels.qmatmul.pack_linear`` — the same representation the
    fused dequant-matmul kernel consumes.  Scale keeps a broadcastable
    ``[..., 1, N]`` shape for ``unpack_weight``.  ``bits <= 4`` with an
    even K axis nibble-packs two mantissas per stored byte along K
    (``w_nib [..., K/2, N]``; K recovers as ``2 * w_nib.shape[-2]``, no
    side metadata); odd-K layers keep int8 storage on the narrow grid."""
    from ..core.plan import NIBBLE_BITS
    from ..kernels.qmatmul.ops import pack_linear, pack_nibbles
    m, scale = pack_linear(p["w"], p.get("f"), bits)
    out: Dict[str, Any] = {
        "scale": scale[..., None, :].astype(jnp.float32)}
    if bits <= NIBBLE_BITS and m.shape[-2] % 2 == 0:
        out["w_nib"] = pack_nibbles(m, axis=-2)
    else:
        out["w_int8"] = m
    if p.get("f") is not None:
        out["f"] = p["f"]
    return out


def pack_params_for_serving(params: Any, plan=None) -> Any:
    """Rewrite matmul weights to quantized mantissas + per-channel scale
    (see module doc), at each layer's ``plan`` pack width (uniform int8
    when ``plan`` is ``None``).

    Structure-preserving everywhere else (including list-of-layer nodes,
    e.g. Griffin remainder blocks); safe to call on abstract
    (``ShapeDtypeStruct``) trees under ``jax.eval_shape``.  Layer keys
    are the ``/``-joined tree paths ``core.plan.iter_packable`` yields,
    so a plan derived from this params tree addresses exactly these
    weights.
    """
    def walk(obj, name="", prefix=()):
        if isinstance(obj, dict):
            if "w" in obj and _packable(name, obj["w"]):
                bits = 8 if plan is None else \
                    plan.entry_for("/".join(prefix)).pack_bits
                return _pack_one(obj, bits)
            return {k: walk(v, k, prefix + (str(k),))
                    for k, v in obj.items()}
        if isinstance(obj, list):
            return [walk(v, name, prefix + (str(i),))
                    for i, v in enumerate(obj)]
        return obj
    return walk(params)


def stored_weight(p: Dict[str, Any]
                  ) -> Tuple[jax.Array, bool, Optional[jax.Array]]:
    """(stored mantissa array, nibble-stored?, layer index or None) of a
    packed weight dict.  A packed dict under ``p['layers']`` may carry a
    ``layer`` index into a stacked ``[L, ...]`` array
    (:func:`layer_scan_split`); this is the one reader of that key."""
    layer = p.get("layer")
    if "w_nib" in p:
        return p["w_nib"], True, layer
    return p["w_int8"], False, layer


def packed_mantissas(p: Dict[str, Any]) -> jax.Array:
    """Full-width int8 mantissas ``[..., K, N]`` of a packed weight dict,
    whichever storage it uses (``w_int8`` as-is; ``w_nib`` sign-extend
    unpacked along K; a ``layer`` index picks that layer of a stacked
    weight, :func:`layer_scan_split`).  The accessor for every reader
    that dequantizes in XLA; the packed kernel reads the stored array."""
    w, nib, layer = stored_weight(p)
    if layer is not None:
        w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    if nib:
        from ..kernels.qmatmul.ops import unpack_nibbles
        return unpack_nibbles(w, 2 * w.shape[-2], axis=-2)
    return w


def is_packed(p: Any) -> bool:
    """True for a serving-packed weight dict (either storage format)."""
    return isinstance(p, dict) and ("w_int8" in p or "w_nib" in p)


def layer_scan_split(layers: Any) -> Tuple[Any, Any]:
    """(xs, whole) for a ``lax.scan`` over a stacked layer tree.  Under
    packed routing each packed ``[L, K, N]`` weight array leaves ``xs``
    for ``whole``, and its dict gains ``layer`` (0..L-1) in ``xs`` in its
    place; :func:`layer_scan_join` puts the array back whole into each
    layer's slice, and the packed kernel reads that layer's blocks in
    place — sliced by the scan, each layer's weight would be copied out
    of the stack first.  Off, ``xs`` is ``layers`` and ``whole`` None."""
    if not get_packed_matmul():
        return layers, None

    def split(t):
        if is_packed(t) and stored_weight(t)[0].ndim == 3:
            key = "w_nib" if "w_nib" in t else "w_int8"
            rest = {k: v for k, v in t.items() if k != key}
            rest["layer"] = jnp.arange(t[key].shape[0], dtype=jnp.int32)
            return rest, {key: t[key]}
        if isinstance(t, dict):
            pairs = {k: split(v) for k, v in t.items()}
            return ({k: a for k, (a, _) in pairs.items()},
                    {k: b for k, (_, b) in pairs.items() if b})
        return t, None

    return split(layers)


def layer_scan_join(lp: Any, whole: Any) -> Any:
    """One layer's params from its scanned slice and the ``whole``
    arrays :func:`layer_scan_split` held back."""
    if not whole:
        return lp
    out = dict(lp)
    for k, v in whole.items():
        out[k] = v if k in ("w_int8", "w_nib") else layer_scan_join(lp[k], v)
    return out


def unpack_weight(p: Dict[str, Any]) -> jax.Array:
    """Dequantize a packed weight dict; fuses into the consuming matmul."""
    w = (packed_mantissas(p).astype(jnp.float32)
         * p["scale"].astype(jnp.float32))
    dtype = _COMPUTE.get()
    if dtype is not None:
        w = w.astype(dtype)
    return w
