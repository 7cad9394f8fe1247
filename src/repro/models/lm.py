"""Decoder-only transformer LM (dense / MoE / VLM-backbone variants).

Layers are homogeneous and stacked; the forward pass is a single
``jax.lax.scan`` over the layer axis (small HLO, fast multi-arch compiles,
remat-friendly) — mandatory for the 95-layer deepseek-67b cell.

The residual stream is kept unquantized (it is an *accumulator*, paper
SSec. III.C folds accumulation EBOPs into the feeding multiplications);
activation quantizers sit at the norm outputs and projection outputs, so
every matmul sees quantized operands.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..core import hgq
from ..core.hgq import Aux, QTensor
from ..dist.axes import constrain
from ..nn.attention import (AttnConfig, GQAAttention, KVCache,
                            decode_positions)
from ..nn.basic import HDense, HEmbedding, LayerNorm, RMSNorm
from ..nn.mlp import GLUMLP
from ..nn.moe import MoE, MoEConfig
from .config import ModelConfig


def _norm_cls(cfg: ModelConfig):
    return RMSNorm if cfg.norm == "rms" else LayerNorm


def _attn_cfg(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                      n_kv=cfg.n_kv, head_dim=cfg.hd, qkv_bias=cfg.qkv_bias,
                      rope_theta=cfg.rope_theta, window=cfg.window,
                      causal=True, q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)


def _moe_cfg(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                     n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                     act=cfg.act)


class TransformerLM:
    # ---------------------------- init ----------------------------------
    @staticmethod
    def init(key, cfg: ModelConfig):
        dtype = cfg.np_dtype
        ke, kl, kf, kh = jax.random.split(key, 4)
        Norm = _norm_cls(cfg)
        p: Dict[str, Any] = {}
        q: Dict[str, Any] = {}
        p["embed"], q["embed"] = HEmbedding.init(ke, cfg.vocab, cfg.d_model,
                                                 cfg.hgq, dtype)

        def layer_init(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            lp: Dict[str, Any] = {}
            lq: Dict[str, Any] = {}
            lp["ln1"], lq["ln1"] = Norm.init(k1, cfg.d_model, cfg.hgq,
                                             dtype=dtype)
            lp["attn"], lq["attn"] = GQAAttention.init(k2, _attn_cfg(cfg),
                                                       cfg.hgq, dtype)
            lp["ln2"], lq["ln2"] = Norm.init(k3, cfg.d_model, cfg.hgq,
                                             dtype=dtype)
            if cfg.moe_experts:
                lp["moe"], lq["moe"] = MoE.init(k4, _moe_cfg(cfg), cfg.hgq,
                                                dtype)
            else:
                lp["mlp"], lq["mlp"] = GLUMLP.init(k4, cfg.d_model, cfg.d_ff,
                                                   cfg.hgq, act=cfg.act,
                                                   dtype=dtype)
            return lp, lq

        lkeys = jax.random.split(kl, cfg.n_layers)
        p["layers"], q["layers"] = jax.vmap(layer_init)(lkeys)
        p["final_norm"], q["final_norm"] = Norm.init(kf, cfg.d_model, cfg.hgq,
                                                     dtype=dtype)
        if not cfg.tie_embeddings:
            p["lm_head"], q["lm_head"] = HDense.init(
                kh, cfg.d_model, cfg.vocab, cfg.hgq, bias=False, out_q=False,
                dtype=dtype)
        return p, q

    # -------------------------- layer body ------------------------------
    @staticmethod
    def _layer(lp, lq, x, positions, cache, cache_pos, cfg: ModelConfig,
               mode: str, kv_bits: Optional[int] = None):
        Norm = _norm_cls(cfg)
        aux = Aux.zero()
        newq: Dict[str, Any] = {}
        h, newq["ln1"] = Norm.apply(lp["ln1"], lq["ln1"], x, mode=mode,
                                    aux=aux)
        a, newq["attn"], new_cache = GQAAttention.apply(
            lp["attn"], lq["attn"], h, cfg=_attn_cfg(cfg), mode=mode, aux=aux,
            positions=positions, cache=cache, cache_pos=cache_pos,
            kv_bits=kv_bits)
        x = constrain(x + a.q, "b..")
        h, newq["ln2"] = Norm.apply(lp["ln2"], lq["ln2"], x, mode=mode,
                                    aux=aux)
        if cfg.moe_experts:
            m, newq["moe"] = MoE.apply(lp["moe"], lq["moe"], h,
                                       cfg=_moe_cfg(cfg), mode=mode, aux=aux)
        else:
            m, newq["mlp"] = GLUMLP.apply(lp["mlp"], lq["mlp"], h, mode=mode,
                                          aux=aux, act=cfg.act)
        x = constrain(x + m.q, "b..")
        return x, newq, new_cache, aux.as_tuple()

    # ------------------------- scan driver ------------------------------
    @staticmethod
    def _stack_forward(p, q, x, positions, cfg: ModelConfig, mode: str,
                       caches: Optional[KVCache] = None,
                       cache_pos=None, kv_bits: Optional[int] = None):
        def body(carry, xs):
            h, ebops, l1 = carry
            if caches is not None:
                lp, lq, cache_l = xs
            else:
                lp, lq = xs
                cache_l = None
            lp = layer_scan_join(lp, whole)
            h2, newlq, new_cache, (e, l) = TransformerLM._layer(
                lp, lq, h, positions, cache_l, cache_pos, cfg, mode,
                kv_bits=kv_bits)
            out = (newlq, new_cache) if caches is not None else newlq
            return (h2.astype(h.dtype), ebops + e, l1 + l), out

        if cfg.remat:
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        from ..dist.perf import layer_scan_join, layer_scan_split
        layers, whole = layer_scan_split(p["layers"])
        xs = (layers, q["layers"]) if caches is None \
            else (layers, q["layers"], caches)
        (x, ebops, l1), out = jax.lax.scan(body, (x, jnp.float32(0.0),
                                                  jnp.float32(0.0)), xs)
        if caches is None:
            return x, out, None, (ebops, l1)
        return x, out[0], out[1], (ebops, l1)

    # --------------------------- forward --------------------------------
    @staticmethod
    def forward(p, q, batch: Dict[str, jax.Array], cfg: ModelConfig,
                mode: str = hgq.TRAIN):
        """Training / prefill forward.  batch: tokens [B,S]
        (+ patch_embeds [B,P,d] for vlm).  Returns (logits, new_qstate, aux).
        """
        tokens = batch["tokens"]
        B, S = tokens.shape
        aux = Aux.zero()
        newq: Dict[str, Any] = {}
        e, newq["embed"] = HEmbedding.apply(p["embed"], q["embed"], tokens,
                                            mode=mode, aux=aux)
        from ..dist.perf import cast_for_matmul
        x = constrain(cast_for_matmul(e.q), "b..")
        if cfg.n_patches and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(x.dtype)
            x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
        positions = jnp.arange(S)
        x, newq["layers"], _, (ebops, l1) = TransformerLM._stack_forward(
            p, q, x, positions, cfg, mode)
        aux.add(ebops=ebops, l1=l1)
        Norm = _norm_cls(cfg)
        h, newq["final_norm"] = Norm.apply(p["final_norm"], q["final_norm"],
                                           x, mode=mode, aux=aux)
        logits = TransformerLM._logits(p, q, newq, h, cfg, mode, aux)
        return logits, newq, aux

    @staticmethod
    def _logits(p, q, newq, h: QTensor, cfg: ModelConfig, mode, aux):
        if cfg.tie_embeddings:
            from ..dist.perf import (get_packed_matmul, is_packed,
                                     stored_weight)
            tbl = p["embed"]["table"]
            if is_packed(tbl) and get_packed_matmul():
                # tied head with a packed table: scales are per-embedding-
                # column (axis d), so they fold into the activation —
                # h @ (m * s[None]).T == (h * s) @ m.T — and the kernel
                # reads the [vocab, d] table as stored, unit-scaled
                from ..kernels.qmatmul.ops import qmatmul_any
                s_d = tbl["scale"].reshape(cfg.d_model)
                w, nib, _ = stored_weight(tbl)
                logits = qmatmul_any(h.q.astype(jnp.float32) * s_d, w,
                                     nibbles=nib, transposed=True)
                return constrain(logits, "b.m")
            from ..nn.common import get_qw
            wq = get_qw(tbl, mode)
            logits = jnp.matmul(h.q.astype(wq.q.dtype), wq.q.T)
            hgq.matmul_ebops(aux, h.bits,
                             None if wq.bits is None else wq.bits.T,
                             cfg.d_model, cfg.vocab)
            return constrain(logits, "b.m")
        lt, newq["lm_head"] = HDense.apply(p["lm_head"], q["lm_head"], h,
                                           mode=mode, aux=aux)
        return constrain(lt.q, "b.m")

    # ---------------------------- decode --------------------------------
    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, ring_slack: int = 0,
                   kv_bits: Optional[int] = None) -> KVCache:
        """``ring_slack``: extra ring-buffer slots beyond the attention
        window — writing a decode/prefill chunk of S <= ring_slack + 1
        tokens then never evicts history still inside the oldest chunk
        query's window, keeping multi-token decode_step calls exact.
        ``kv_bits``: plan-width quantized storage (``serving/kvcache.py``);
        None keeps the exact legacy fp cache."""
        kv_len = min(max_len, cfg.window + ring_slack) if cfg.window \
            else max_len
        shape = (cfg.n_layers, batch, kv_len, cfg.n_kv, cfg.hd)
        if kv_bits is not None:
            from ..serving.kvcache import quantized_cache
            return quantized_cache(shape, kv_bits)
        return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))

    @staticmethod
    def decode_step(p, q, caches: KVCache, tokens: jax.Array,
                    cache_pos: jax.Array, cfg: ModelConfig,
                    mode: str = hgq.EVAL, kv_bits: Optional[int] = None):
        """One decode step. tokens [B, S_new]; cache_pos scalar or per-slot
        [B] (ragged continuous batching). Returns (logits, new_caches)."""
        B, S = tokens.shape
        aux = Aux.zero()
        newq: Dict[str, Any] = {}
        e, newq["embed"] = HEmbedding.apply(p["embed"], q["embed"], tokens,
                                            mode=mode, aux=aux)
        positions = decode_positions(cache_pos, S)
        x, newq["layers"], new_caches, (ebops, l1) = \
            TransformerLM._stack_forward(p, q, e.q, positions, cfg, mode,
                                         caches=caches, cache_pos=cache_pos,
                                         kv_bits=kv_bits)
        aux.add(ebops=ebops, l1=l1)
        Norm = _norm_cls(cfg)
        h, newq["final_norm"] = Norm.apply(p["final_norm"], q["final_norm"],
                                           x, mode=mode, aux=aux)
        logits = TransformerLM._logits(p, q, newq, h, cfg, mode, aux)
        return logits, new_caches
