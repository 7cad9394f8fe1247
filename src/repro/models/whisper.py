"""Whisper-large-v3 backbone: encoder-decoder transformer.

The conv/mel frontend is a STUB per the assignment brief — ``input_specs``
provides precomputed frame embeddings [B, enc_seq, d] (enc_seq = 1500).
Full MHA (n_kv == n_heads), LayerNorm + biases, gelu MLP, learned positions.

Serving shape: the decoder runs through the Engine's ragged decode path
(``decode_step`` with per-slot ``cache_pos``), and the encoder memory is
*streamable* — ``append_cross`` encodes one audio chunk block-locally at
the cache's absolute frame offset and appends its cross-attention K/V
rows, advancing the per-slot fill level ``mem_len``.  Decode-path
cross-attention reads through the same ``tpos``-masked kernels the ring
caches use (``nn.attention.memory_tpos``), so partially-streamed memory
is masked exactly and rows with ``mem_len == 0`` (LM traffic sharing the
batch) get a zero attention read.  Under a quantized KV plan the cross
rows are stored on the same int8 2^-f grids as the self-attention ring
(``cross_kf``/``cross_vf``), read through ``kernels.kv_dequant``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import hgq
from ..core.hgq import Aux, QTensor
from ..dist.axes import constrain
from ..nn.attention import (AttnConfig, GQAAttention, KVCache, QKVCache,
                            _decode_attention, decode_positions, memory_tpos)
from ..nn.basic import HDense, HEmbedding, LayerNorm
from ..nn.mlp import MLP
from .config import ModelConfig


class WhisperCaches(NamedTuple):
    self_k: jax.Array    # [L, B, S_max, H, hd] (int8 mantissas quantized)
    self_v: jax.Array
    cross_k: jax.Array   # [L, B, enc_seq, H, hd] (int8 mantissas quantized)
    cross_v: jax.Array
    mem_len: jax.Array   # [1, B] int32 — encoder frames written per slot
    self_kf: Optional[jax.Array] = None  # [L, B, S_max, H] grid exponents
    self_vf: Optional[jax.Array] = None  # (None = legacy fp self cache)
    cross_kf: Optional[jax.Array] = None  # [L, B, enc_seq, H] exponents
    cross_vf: Optional[jax.Array] = None  # (None = fp cross memory)


def _attn_cfg(cfg: ModelConfig, causal: bool) -> AttnConfig:
    return AttnConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                      n_kv=cfg.n_kv, head_dim=cfg.hd, qkv_bias=True,
                      causal=causal, q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)


class CrossAttention:
    """q from decoder stream, k/v from (fixed) encoder memory."""

    @staticmethod
    def init(key, cfg: ModelConfig, dtype=jnp.float32):
        ks = jax.random.split(key, 4)
        d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
        p, q = {}, {}
        p["wq"], q["wq"] = HDense.init(ks[0], d, H * hd, cfg.hgq, bias=True,
                                       dtype=dtype)
        p["wk"], q["wk"] = HDense.init(ks[1], d, H * hd, cfg.hgq, bias=False,
                                       dtype=dtype)
        p["wv"], q["wv"] = HDense.init(ks[2], d, H * hd, cfg.hgq, bias=True,
                                       dtype=dtype)
        p["wo"], q["wo"] = HDense.init(ks[3], H * hd, d, cfg.hgq, bias=True,
                                       out_q=False, dtype=dtype)
        if cfg.hgq.enabled:
            p["probs_f"] = jnp.full((), cfg.hgq.init_act_f, jnp.float32)
        return p, q

    @staticmethod
    def kv(p, q, memory: QTensor, cfg: ModelConfig, mode, aux):
        B, T, _ = memory.q.shape
        kt, nk = HDense.apply(p["wk"], q["wk"], memory, mode=mode, aux=aux)
        vt, nv = HDense.apply(p["wv"], q["wv"], memory, mode=mode, aux=aux)
        H, hd = cfg.n_heads, cfg.hd
        return (kt.q.reshape(B, T, H, hd), vt.q.reshape(B, T, H, hd),
                {"wk": nk, "wv": nv})

    @staticmethod
    def apply(p, q, x: QTensor, kh, vh, cfg: ModelConfig, mode, aux):
        B, S, _ = x.q.shape
        H, hd = cfg.n_heads, cfg.hd
        newq: Dict[str, Any] = {}
        qt, newq["wq"] = HDense.apply(p["wq"], q["wq"], x, mode=mode, aux=aux)
        qh = qt.q.reshape(B, S, H, hd)
        T = kh.shape[1]
        scale = hd ** -0.5
        cq = min(cfg.q_chunk, S)
        nq = -(-S // cq)
        pad = nq * cq - S
        qp = jnp.pad(qh, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else qh
        qs = qp.reshape(B, nq, cq, H, hd).transpose(1, 0, 3, 2, 4)

        def q_step(_, qc):
            s = constrain(jnp.einsum("bhqd,bthd->bhqt", qc, kh,
                                     preferred_element_type=jnp.float32),
                          "bm..") * scale
            pt = jax.nn.softmax(s, axis=-1)
            if p.get("probs_f") is not None:
                fn = (hgq.quantize if mode == hgq.TRAIN
                      else hgq.quantize_inference)
                pt = fn(pt, p["probs_f"])
            o = jnp.einsum("bhqt,bthd->bhqd", pt, vh,
                           preferred_element_type=jnp.float32)
            return None, o

        _, outs = jax.lax.scan(q_step, None, qs)
        o = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * cq, H * hd)[:, :S]
        o = o.astype(x.q.dtype)
        yo, newq["wo"] = HDense.apply(p["wo"], q["wo"], QTensor(o, None),
                                      mode=mode, aux=aux)
        if p.get("probs_f") is not None:
            aux.add(l1=jax.nn.relu(p["probs_f"]))
        return yo, newq

    @staticmethod
    def decode(p, q, x: QTensor, ck, cv, mem, cfg: ModelConfig, mode, aux,
               ckf=None, cvf=None):
        """Decode-path cross read over the (possibly partially-streamed,
        possibly quantized) memory cache: only the ``mem[b]`` written
        rows are visible — empty slots are masked via ``memory_tpos``,
        and a row with ``mem == 0`` gets an exactly-zero attention read
        (how LM slots ride a shared batch without touching the memory
        buffer).  ``ckf``/``cvf`` select the fused dequant-attention
        kernel path over int8 2^-f mantissas (``kernels.kv_dequant``)."""
        B, S, _ = x.q.shape
        H, hd = cfg.n_heads, cfg.hd
        newq: Dict[str, Any] = {}
        qt, newq["wq"] = HDense.apply(p["wq"], q["wq"], x, mode=mode, aux=aux)
        qh = qt.q.reshape(B, S, H, hd)
        T = ck.shape[1]
        tpos = memory_tpos(mem, T)
        # every valid memory row is visible to every query position
        qpos = jnp.full((B, S), T, jnp.int32)
        probs_f = p.get("probs_f")
        if ckf is not None:
            from ..kernels.kv_dequant.ops import kv_attention_decode
            out = kv_attention_decode(qh, ck, ckf, cv, cvf, qpos, tpos,
                                      window=None, n_kv=H, probs_f=probs_f)
        else:
            acfg = dataclasses.replace(_attn_cfg(cfg, causal=False), n_kv=H)
            out = _decode_attention(qh, ck, cv, qpos, acfg, probs_f, mode,
                                    tpos=tpos)
        o = out.reshape(B, S, H * hd).astype(x.q.dtype)
        yo, newq["wo"] = HDense.apply(p["wo"], q["wo"], QTensor(o, None),
                                      mode=mode, aux=aux)
        if probs_f is not None:
            aux.add(l1=jax.nn.relu(p["probs_f"]))
        return yo, newq


class WhisperModel:
    @staticmethod
    def init(key, cfg: ModelConfig):
        dtype = cfg.np_dtype
        ks = jax.random.split(key, 8)
        p: Dict[str, Any] = {}
        q: Dict[str, Any] = {}
        d = cfg.d_model
        # encoder (frame embeddings come precomputed — frontend stub)
        p["enc_pos"] = 0.02 * jax.random.normal(ks[0], (cfg.enc_seq, d),
                                                dtype)

        def enc_layer(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            lp, lq = {}, {}
            lp["ln1"], lq["ln1"] = LayerNorm.init(k1, d, cfg.hgq, dtype=dtype)
            lp["attn"], lq["attn"] = GQAAttention.init(
                k2, _attn_cfg(cfg, causal=False), cfg.hgq, dtype)
            lp["ln2"], lq["ln2"] = LayerNorm.init(k3, d, cfg.hgq, dtype=dtype)
            lp["mlp"], lq["mlp"] = MLP.init(k4, d, cfg.d_ff, cfg.hgq,
                                            act="gelu", dtype=dtype)
            return lp, lq

        p["enc_layers"], q["enc_layers"] = jax.vmap(enc_layer)(
            jax.random.split(ks[1], cfg.enc_layers))
        p["enc_norm"], q["enc_norm"] = LayerNorm.init(ks[2], d, cfg.hgq,
                                                      dtype=dtype)
        # decoder
        p["embed"], q["embed"] = HEmbedding.init(ks[3], cfg.vocab, d,
                                                 cfg.hgq, dtype)
        p["dec_pos"] = 0.02 * jax.random.normal(ks[4], (4096, d), dtype)

        def dec_layer(k):
            k1, k2, k3, k4, k5, k6 = jax.random.split(k, 6)
            lp, lq = {}, {}
            lp["ln1"], lq["ln1"] = LayerNorm.init(k1, d, cfg.hgq, dtype=dtype)
            lp["attn"], lq["attn"] = GQAAttention.init(
                k2, _attn_cfg(cfg, causal=True), cfg.hgq, dtype)
            lp["ln_x"], lq["ln_x"] = LayerNorm.init(k3, d, cfg.hgq,
                                                    dtype=dtype)
            lp["xattn"], lq["xattn"] = CrossAttention.init(k4, cfg, dtype)
            lp["ln2"], lq["ln2"] = LayerNorm.init(k5, d, cfg.hgq, dtype=dtype)
            lp["mlp"], lq["mlp"] = MLP.init(k6, d, cfg.d_ff, cfg.hgq,
                                            act="gelu", dtype=dtype)
            return lp, lq

        p["dec_layers"], q["dec_layers"] = jax.vmap(dec_layer)(
            jax.random.split(ks[5], cfg.n_layers))
        p["dec_norm"], q["dec_norm"] = LayerNorm.init(ks[6], d, cfg.hgq,
                                                      dtype=dtype)
        return p, q

    # ------------------------------------------------------------------
    @staticmethod
    def encode(p, q, frame_embeds: jax.Array, cfg: ModelConfig, mode, aux,
               offset=0):
        """Encode a block of frames at absolute frame position
        ``offset``: learned positions are sliced there and RoPE phases
        start there, so streaming (one call per arriving chunk,
        block-local self-attention) and whole-audio encoding agree on
        any block they both encode.  ``offset=0`` with the full audio is
        the classic offline encoder."""
        T = frame_embeds.shape[1]
        if isinstance(offset, int) and offset == 0:
            pe = p["enc_pos"][None, :T]
            positions = jnp.arange(T)
        else:
            off = jnp.asarray(offset, jnp.int32)
            pe = jax.lax.dynamic_slice_in_dim(p["enc_pos"], off, T,
                                              axis=0)[None]
            positions = off + jnp.arange(T)
        x = constrain(frame_embeds + pe, "b..")

        def body(carry, xs):
            h, eb, l1 = carry
            lp, lq = xs
            a = Aux.zero()
            nq = {}
            n1, nq["ln1"] = LayerNorm.apply(lp["ln1"], lq["ln1"], h,
                                            mode=mode, aux=a)
            at, nq["attn"], _ = GQAAttention.apply(
                lp["attn"], lq["attn"], n1, cfg=_attn_cfg(cfg, causal=False),
                mode=mode, aux=a, positions=positions)
            h = h + at.q
            n2, nq["ln2"] = LayerNorm.apply(lp["ln2"], lq["ln2"], h,
                                            mode=mode, aux=a)
            mt, nq["mlp"] = MLP.apply(lp["mlp"], lq["mlp"], n2, mode=mode,
                                      aux=a)
            e, l = a.as_tuple()
            return ((h + mt.q).astype(carry[0].dtype), eb + e, l1 + l), nq

        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        (x, eb, l1), newq = jax.lax.scan(
            body, (x, jnp.float32(0.0), jnp.float32(0.0)),
            (p["enc_layers"], q["enc_layers"]))
        aux.add(ebops=eb, l1=l1)
        n, nq_n = LayerNorm.apply(p["enc_norm"], q["enc_norm"], x, mode=mode,
                                  aux=aux)
        return n, {"enc_layers": newq, "enc_norm": nq_n}

    @staticmethod
    def _decode_stack(p, q, x, memory: Optional[QTensor], positions, cfg,
                      mode, aux, caches=None, cache_pos=None, kv_bits=None):
        decode = caches is not None
        quant = decode and caches.self_kf is not None
        # per-slot memory fill level [B]: not scanned over layers
        mem = caches.mem_len[0] if decode else None

        def body(carry, xs):
            h, eb, l1 = carry
            ckf = cvf = None
            if quant:
                lp, lq, (sk, sv, skf, svf, ck, cv, ckf, cvf) = xs
                kvc = QKVCache(sk, sv, skf, svf)
            elif decode:
                lp, lq, (sk, sv, ck, cv) = xs
                kvc = KVCache(sk, sv)
            else:
                lp, lq = xs
                kvc = None
            a = Aux.zero()
            nq = {}
            n1, nq["ln1"] = LayerNorm.apply(lp["ln1"], lq["ln1"], h,
                                            mode=mode, aux=a)
            at, nq["attn"], nkv = GQAAttention.apply(
                lp["attn"], lq["attn"], n1, cfg=_attn_cfg(cfg, causal=True),
                mode=mode, aux=a, positions=positions, cache=kvc,
                cache_pos=cache_pos, kv_bits=kv_bits)
            h = h + at.q
            nx, nq["ln_x"] = LayerNorm.apply(lp["ln_x"], lq["ln_x"], h,
                                             mode=mode, aux=a)
            if decode:
                xt, nq["xattn"] = CrossAttention.decode(
                    lp["xattn"], lq["xattn"], nx, ck, cv, mem, cfg, mode,
                    a, ckf=ckf, cvf=cvf)
            else:
                kh, vh, kvq = CrossAttention.kv(
                    lp["xattn"], lq["xattn"], memory, cfg, mode, a)
                xt, xq = CrossAttention.apply(
                    lp["xattn"], lq["xattn"], nx, kh, vh, cfg, mode, a)
                # the step hands back qstate in the tree it took
                nq["xattn"] = {**xq, **kvq}
            h = h + xt.q
            n2, nq["ln2"] = LayerNorm.apply(lp["ln2"], lq["ln2"], h,
                                            mode=mode, aux=a)
            mt, nq["mlp"] = MLP.apply(lp["mlp"], lq["mlp"], n2, mode=mode,
                                      aux=a)
            e, l = a.as_tuple()
            if quant:
                out = (nq, (nkv.k, nkv.v, nkv.kf, nkv.vf))
            elif decode:
                out = (nq, (nkv.k, nkv.v))
            else:
                out = nq
            return ((h + mt.q).astype(carry[0].dtype), eb + e, l1 + l), out

        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        if quant:
            xs = (p["dec_layers"], q["dec_layers"],
                  (caches.self_k, caches.self_v, caches.self_kf,
                   caches.self_vf, caches.cross_k, caches.cross_v,
                   caches.cross_kf, caches.cross_vf))
        elif decode:
            xs = (p["dec_layers"], q["dec_layers"],
                  (caches.self_k, caches.self_v, caches.cross_k,
                   caches.cross_v))
        else:
            xs = (p["dec_layers"], q["dec_layers"])
        (x, eb, l1), out = jax.lax.scan(
            body, (x, jnp.float32(0.0), jnp.float32(0.0)), xs)
        aux.add(ebops=eb, l1=l1)
        if decode:
            return x, out[0], out[1]
        return x, out, None

    # ------------------------------------------------------------------
    @staticmethod
    def forward(p, q, batch, cfg: ModelConfig, mode: str = hgq.TRAIN):
        """batch: frame_embeds [B, enc_seq, d], tokens [B, S_dec]."""
        aux = Aux.zero()
        newq: Dict[str, Any] = {}
        mem, nq_enc = WhisperModel.encode(p, q, batch["frame_embeds"], cfg,
                                          mode, aux)
        newq.update(nq_enc)
        tokens = batch["tokens"]
        B, S = tokens.shape
        e, newq["embed"] = HEmbedding.apply(p["embed"], q["embed"], tokens,
                                            mode=mode, aux=aux)
        pos_table = p["dec_pos"]
        x = e.q + jnp.take(pos_table, jnp.arange(S) % pos_table.shape[0],
                           axis=0)[None]
        x, newq["dec_layers"], _ = WhisperModel._decode_stack(
            p, q, x, mem, jnp.arange(S), cfg, mode, aux)
        h, newq["dec_norm"] = LayerNorm.apply(p["dec_norm"], q["dec_norm"],
                                              x, mode=mode, aux=aux)
        # whisper ties decoder embedding for logits
        from ..nn.common import get_qw
        wq = get_qw(p["embed"]["table"], mode)
        logits = constrain(jnp.matmul(h.q.astype(wq.q.dtype), wq.q.T), "b.m")
        hgq.matmul_ebops(aux, h.bits,
                         None if wq.bits is None else wq.bits.T,
                         cfg.d_model, cfg.vocab)
        return logits, newq, aux

    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, ring_slack: int = 0,
                   kv_bits=None) -> WhisperCaches:
        del ring_slack  # decoder self-attn cache is not windowed
        L, H, hd = cfg.n_layers, cfg.n_heads, cfg.hd
        self_shape = (L, batch, max_len, H, hd)
        cross_shape = (L, batch, cfg.enc_seq, H, hd)
        if kv_bits is not None:
            # the cross memory rides the same quantized-cache machinery
            # as the self-attention ring: int8 mantissas on per-row 2^-f
            # grids (nibble-packed at kv_bits <= 4), exponents alongside
            from ..serving.kvcache import quantized_cache
            qkv = quantized_cache(self_shape, kv_bits)
            selfkv = dict(self_k=qkv.k, self_v=qkv.v,
                          self_kf=qkv.kf, self_vf=qkv.vf)
            qx = quantized_cache(cross_shape, kv_bits)
            cross = dict(cross_k=qx.k, cross_v=qx.v,
                         cross_kf=qx.kf, cross_vf=qx.vf)
        else:
            selfkv = dict(self_k=jnp.zeros(self_shape, dtype),
                          self_v=jnp.zeros(self_shape, dtype))
            cross = dict(cross_k=jnp.zeros(cross_shape, dtype),
                         cross_v=jnp.zeros(cross_shape, dtype))
        return WhisperCaches(
            mem_len=jnp.zeros((1, batch), jnp.int32), **selfkv, **cross)

    @staticmethod
    def append_cross(p, q, caches: WhisperCaches, frame_chunk, cfg,
                     mode: str = hgq.EVAL, kv_bits=None) -> WhisperCaches:
        """Encode one audio chunk block-locally at the cache's current
        memory offset and append its cross-attention K/V rows,
        advancing ``mem_len``.

        Streaming contract: chunks are self-attended only within their
        own block (at absolute positions — ``encode(offset=...)``), so
        feeding N chunks one call at a time writes bit-for-bit the rows
        that one call per chunk over the whole audio would — the
        chunk *decomposition* is the semantic unit, not the arrival
        schedule.  All batch rows advance together (the Engine appends
        on single-slot cache slices; ``serving.streaming.generate_asr``
        is the B=1 offline reference)."""
        aux = Aux.zero()
        off = caches.mem_len[0, 0]
        mem, _ = WhisperModel.encode(p, q, frame_chunk, cfg, mode, aux,
                                     offset=off)

        def one_layer(lp, lq):
            kh, vh, _ = CrossAttention.kv(lp["xattn"], lq["xattn"], mem, cfg,
                                          mode, Aux.zero())
            return kh, vh

        ck, cv = jax.vmap(one_layer)(p["dec_layers"], q["dec_layers"])

        def upd(a, u):
            return jax.lax.dynamic_update_slice_in_dim(a, u, off, axis=2)

        if caches.cross_kf is not None:
            from ..kernels.kv_dequant.ops import kv_pack, kv_quantize
            km, kf = kv_quantize(ck, kv_bits or 8)
            vm, vf = kv_quantize(cv, kv_bits or 8)
            if caches.cross_k.shape[-1] != ck.shape[-1]:
                km, vm = kv_pack(km), kv_pack(vm)
            new = dict(cross_k=upd(caches.cross_k, km),
                       cross_v=upd(caches.cross_v, vm),
                       cross_kf=upd(caches.cross_kf, kf),
                       cross_vf=upd(caches.cross_vf, vf))
        else:
            new = dict(
                cross_k=upd(caches.cross_k,
                            ck.astype(caches.cross_k.dtype)),
                cross_v=upd(caches.cross_v,
                            cv.astype(caches.cross_v.dtype)))
        n = jnp.int32(frame_chunk.shape[1])
        return caches._replace(mem_len=caches.mem_len + n, **new)

    @staticmethod
    def prefill_cross(p, q, caches: WhisperCaches, frame_embeds, cfg,
                      mode: str = hgq.EVAL, kv_bits=None) -> WhisperCaches:
        """Whole-audio memory prefill: one block-local ``append_cross``
        covering the full audio on a fresh cache — the offline encoder."""
        return WhisperModel.append_cross(p, q, caches, frame_embeds, cfg,
                                         mode=mode, kv_bits=kv_bits)

    @staticmethod
    def decode_step(p, q, caches: WhisperCaches, tokens, cache_pos,
                    cfg: ModelConfig, mode: str = hgq.EVAL, kv_bits=None):
        aux = Aux.zero()
        newq: Dict[str, Any] = {}
        B, S = tokens.shape
        e, newq["embed"] = HEmbedding.apply(p["embed"], q["embed"], tokens,
                                            mode=mode, aux=aux)
        pos_table = p["dec_pos"]
        positions = decode_positions(cache_pos, S)
        pe = jnp.take(pos_table, positions % pos_table.shape[0], axis=0)
        x = e.q + (pe if positions.ndim == 2 else pe[None])
        x, _, new_kv = WhisperModel._decode_stack(
            p, q, x, None, positions, cfg, mode, aux, caches=caches,
            cache_pos=cache_pos, kv_bits=kv_bits)
        h, _ = LayerNorm.apply(p["dec_norm"], q["dec_norm"], x, mode=mode,
                               aux=aux)
        from ..nn.common import get_qw
        wq = get_qw(p["embed"]["table"], mode)
        logits = constrain(jnp.matmul(h.q.astype(wq.q.dtype), wq.q.T), "b.m")
        if caches.self_kf is not None:
            nk, nv, nkf, nvf = new_kv
            return logits, caches._replace(self_k=nk, self_v=nv,
                                           self_kf=nkf, self_vf=nvf)
        nk, nv = new_kv
        return logits, caches._replace(self_k=nk, self_v=nv)
