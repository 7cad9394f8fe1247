"""Basic quantized layers: dense, conv2d, embedding, norms, activations.

Static layer attributes (activation name, conv stride/padding) are passed at
apply time, NOT stored in params — params must stay a pure-array pytree so
layer stacks can be vmap-initialized and lax.scan'ed.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import ebops as ebops_lib
from ..core import hgq
from ..core.hgq import Aux, QTensor
from ..core.quantizer import f_shape_for
from .common import HGQConfig, act_q_init, apply_act_q, get_qw, qweight_init


def activation(name: str, x: jax.Array) -> jax.Array:
    if not name or name == "linear":
        return x
    return {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid,
            "softmax": lambda v: jax.nn.softmax(v, axis=-1)}[name](x)


class HDense:
    """The paper's HDense: quantized kernel (+bias), EBOPs on x@W, optional
    fused activation + output activation quantizer."""

    @staticmethod
    def init(key, d_in: int, d_out: int, cfg: HGQConfig, *, bias: bool = True,
             act: Optional[str] = None, out_q: bool = True,
             dtype=jnp.float32):
        del act  # static; passed at apply time
        kk, _ = jax.random.split(key)
        p: Dict[str, Any] = {"kernel": qweight_init(kk, (d_in, d_out), cfg,
                                                    dtype=dtype)}
        q: Dict[str, Any] = {}
        if bias:
            p["bias"] = {"w": jnp.zeros((d_out,), dtype)}
            if cfg.enabled:
                p["bias"]["f"] = jnp.full(
                    f_shape_for((d_out,), cfg.weight_gran),
                    cfg.init_weight_f, jnp.float32)
        if out_q:
            f, st = act_q_init(cfg)
            if f is not None:
                p["out_f"] = f
                q["out"] = st
        return p, q

    @staticmethod
    def apply(p, q, x: QTensor, *, mode: str, aux: Aux, act: str = ""
              ) -> Tuple[QTensor, Dict[str, Any]]:
        from ..dist.perf import (cast_for_matmul, get_compute_dtype,
                                 get_packed_matmul, is_packed, stored_weight)
        if is_packed(p["kernel"]) and get_packed_matmul():
            # serving hot path (serving/packed.py): the packed mantissas
            # stream as stored (int8 or nibbles, one layer of a stack)
            # into the fused dequant-matmul Pallas kernel — the weight
            # bytes moved from HBM are the packed ones, once
            from ..kernels.qmatmul.ops import qmatmul_any
            k = p["kernel"]
            w, nib, layer = stored_weight(k)
            y = qmatmul_any(x.q.astype(jnp.float32), w,
                            k["scale"].reshape(w.shape[-1]), nibbles=nib,
                            layer=layer).astype(x.q.dtype)
        else:
            wq = get_qw(p["kernel"], mode)
            d_in, d_out = wq.q.shape
            xq = cast_for_matmul(x.q).astype(wq.q.dtype)
            # under bf16-compute the cross-shard partial-sum all-reduce runs
            # on the bf16 output (Megatron convention) — halves the TP
            # collective; otherwise accumulate/reduce in f32
            pet = jnp.float32 if get_compute_dtype() is None else None
            y = jnp.matmul(xq, wq.q,
                           preferred_element_type=pet).astype(x.q.dtype)
            hgq.matmul_ebops(aux, x.bits, wq.bits, d_in, d_out)
        if "bias" in p:
            y = y + get_qw(p["bias"], mode).q
        y = activation(act, y)
        newq = dict(q) if q else {}
        if "out_f" in p:
            yq, st = apply_act_q(y, p["out_f"], q.get("out"), mode, aux)
            if st is not None:
                newq["out"] = st
            return yq, newq
        return QTensor(y, None), newq


class HConv2D:
    """SAME/VALID conv with stream-IO EBOPs counting (DESIGN.md SS2)."""

    @staticmethod
    def init(key, kh: int, kw: int, cin: int, cout: int, cfg: HGQConfig, *,
             act: Optional[str] = None, bias: bool = True, out_q: bool = True,
             dtype=jnp.float32):
        del act
        kk, _ = jax.random.split(key)
        p = {"kernel": qweight_init(kk, (kh, kw, cin, cout), cfg,
                                    dtype=dtype)}
        q: Dict[str, Any] = {}
        if bias:
            p["bias"] = {"w": jnp.zeros((cout,), dtype)}
            if cfg.enabled:
                p["bias"]["f"] = jnp.full(f_shape_for((cout,),
                                                      cfg.weight_gran),
                                          cfg.init_weight_f, jnp.float32)
        if out_q:
            f, st = act_q_init(cfg)
            if f is not None:
                p["out_f"] = f
                q["out"] = st
        return p, q

    @staticmethod
    def apply(p, q, x: QTensor, *, mode: str, aux: Aux, act: str = "",
              stride: int = 1, padding: str = "VALID"):
        wq = get_qw(p["kernel"], mode)
        w_shape = p["kernel"]["w"].shape
        y = jax.lax.conv_general_dilated(
            x.q, wq.q, window_strides=(stride, stride), padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if x.bits is not None and wq.bits is not None:
            aux.add(ebops=ebops_lib.ebops_conv2d(
                _chan_bits(x.bits, w_shape[2]), wq.bits, w_shape))
        if "bias" in p:
            y = y + get_qw(p["bias"], mode).q
        y = activation(act, y)
        newq = dict(q) if q else {}
        if "out_f" in p:
            yq, st = apply_act_q(y, p["out_f"], q.get("out"), mode, aux)
            if st is not None:
                newq["out"] = st
            return yq, newq
        return QTensor(y, None), newq


def _chan_bits(bits: jax.Array, cin: int) -> jax.Array:
    """Collapse activation bits to per-input-channel for conv EBOPs."""
    b = jnp.asarray(bits, jnp.float32)
    if b.ndim == 0:
        return b
    return jnp.max(b.reshape(-1, b.shape[-1]), axis=0) if b.shape[-1] == cin \
        else jnp.max(b) * jnp.ones((1,), jnp.float32)


class HEmbedding:
    """Lookup = no multipliers => no EBOPs; the table is still quantized (its
    bits feed the packed-bytes TPU cost and the L1 term)."""

    @staticmethod
    def init(key, vocab: int, d: int, cfg: HGQConfig, dtype=jnp.float32):
        p = {"table": qweight_init(key, (vocab, d), cfg, channel_axis=-1,
                                   scale=0.02, dtype=dtype)}
        return p, {}

    @staticmethod
    def apply(p, q, ids: jax.Array, *, mode: str, aux: Aux):
        wq = get_qw(p["table"], mode)
        y = jnp.take(wq.q, ids, axis=0)
        return QTensor(y, None), (dict(q) if q else {})


class RMSNorm:
    @staticmethod
    def init(key, d: int, cfg: HGQConfig, *, out_q: bool = True,
             dtype=jnp.float32):
        p = {"scale": jnp.ones((d,), dtype)}
        q: Dict[str, Any] = {}
        if out_q:
            f, st = act_q_init(cfg)
            if f is not None:
                p["out_f"] = f
                q["out"] = st
        return p, q

    @staticmethod
    def apply(p, q, x: jax.Array, *, mode: str, aux: Aux, eps: float = 1e-6):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        y = (y * p["scale"]).astype(x.dtype)
        newq = dict(q) if q else {}
        if "out_f" in p:
            yq, st = apply_act_q(y, p["out_f"], q.get("out"), mode, aux)
            if st is not None:
                newq["out"] = st
            return yq, newq
        return QTensor(y, None), newq


class LayerNorm:
    @staticmethod
    def init(key, d: int, cfg: HGQConfig, *, out_q: bool = True,
             dtype=jnp.float32):
        p = {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
        q: Dict[str, Any] = {}
        if out_q:
            f, st = act_q_init(cfg)
            if f is not None:
                p["out_f"] = f
                q["out"] = st
        return p, q

    @staticmethod
    def apply(p, q, x: jax.Array, *, mode: str, aux: Aux, eps: float = 1e-5):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = (y * p["scale"] + p["bias"]).astype(x.dtype)
        newq = dict(q) if q else {}
        if "out_f" in p:
            yq, st = apply_act_q(y, p["out_f"], q.get("out"), mode, aux)
            if st is not None:
                newq["out"] = st
            return yq, newq
        return QTensor(y, None), newq
