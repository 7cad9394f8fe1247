"""Continuous-batching serving engine: ragged per-slot decode over one
jitted step, chunked prefill, HGQ int8-packed decode weights.

Architecture (one ``Engine`` = one model replica):

* **Slots.** The KV/state cache holds ``batch_slots`` independent rows.  A
  request occupies one slot from admission to completion; finished slots
  are recycled immediately (continuous batching — requests join and leave
  mid-run, no barrier).
* **Per-slot positions.** Every decode tick runs ONE jitted
  ``model.decode_step`` over the whole batch with a position *vector*
  ``cache_pos [B]`` — RoPE phases, ring-buffer writes, and causal/window
  masks are all per-batch-row (``nn.attention``), so slots with different
  prompt lengths decode correctly together.
* **Chunked prefill.** ``submit`` runs the prompt through the same stack
  forward in fixed-size chunks against a fresh single-slot cache slice,
  then splices the slice into the batch cache at the slot's offset —
  no token-by-token prefill, and one compile per chunk shape.
* **Sampling.** Greedy / temperature / top-k, per request, fused into the
  jitted step (Gumbel-max over rank-filtered logits).
* **Packed weights.** ``packed=True`` converts params to the HGQ int8 +
  per-channel 2^-f serving tree (``serving/packed.py``) and routes decode
  projections onto the fused dequant-matmul ``kernels.qmatmul.qmatmul_any``.
* **Quantized KV.** ``kv_bits=b`` stores the ring buffer as int8
  mantissas on per-row 2^-f grids (nibble-packed at b <= 4) with the
  grid exponents riding alongside through the slot scheduler; decode
  reads through the fused dequant-attention kernel
  (``kernels.kv_dequant``).  ``None`` keeps the legacy fp cache,
  byte-identical HLO.
* **Handles.** ``submit(req)`` returns a :class:`RequestHandle`;
  ``tokens(handle)`` reads its output incrementally while ticking the
  engine; ``run(requests)`` is the thin serve-to-completion wrapper.

``generate`` remains the single-batch greedy reference the engine is
tested token-for-token against.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..dist.axes import axis_scope, get_axes
from ..dist.perf import (compute_dtype_scope, get_compute_dtype,
                         packed_matmul)
from ..models.config import ModelConfig
from ..nn.attention import NEG_INF

# cache donation below is a TPU/GPU aliasing win; CPU ignores it (noisily)
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")


@dataclasses.dataclass
class SamplingConfig:
    temperature: float = 0.0      # <= 0: greedy
    top_k: int = 0                # 0: no top-k filter


GREEDY = SamplingConfig()


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new: int
    sampling: Optional[SamplingConfig] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class RequestHandle:
    """Admission receipt for one submitted request: what ``submit``
    returns and ``Engine.tokens`` reads from.  Truthy (so legacy
    ``if eng.submit(req):`` call sites keep working — a full engine
    returns ``None``); carries the request, the engine's request number
    ``rid`` (the ``rid`` of its ``engine.submit`` span) and the
    incremental-read cursor."""
    request: Request
    rid: int = 0
    _cursor: int = 0

    @property
    def done(self) -> bool:
        return self.request.done

    @property
    def out(self) -> List[int]:
        return self.request.out


def _sample(logits: jax.Array, key: jax.Array, temp: jax.Array,
            topk: jax.Array, enable: bool = True) -> jax.Array:
    """Per-row sampling: logits [B, V]; temp [B] (<=0 greedy); topk [B]
    (0 = off).  ``enable`` is static: an all-greedy tick compiles to a bare
    argmax — no vocab sort, no gumbel draw on the decode hot path."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not enable:
        return greedy
    V = logits.shape[-1]
    # top-k via the per-row k-th value threshold (one sort; threshold ties
    # all pass, the standard top-k-filter convention)
    k = jnp.clip(jnp.where(topk > 0, topk, V), 1, V)
    srt = jnp.sort(logits, axis=-1)[:, ::-1]
    thresh = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    filt = jnp.where(logits >= thresh, logits, NEG_INF)
    t = jnp.maximum(temp, 1e-6)[:, None]
    g = jax.random.gumbel(key, logits.shape)
    sampled = jnp.argmax(filt / t + g, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


class Engine:
    """Continuous-batching engine over a model's KV-cache decode path."""

    def __init__(self, model, params, qstate, cfg: ModelConfig, *,
                 batch_slots: int = 8, max_len: int = 512,
                 eos_id: Optional[int] = None, packed: bool = False,
                 plan=None, prefill_chunk: int = 16, seed: int = 0,
                 kv_bits: Optional[int] = None,
                 ring_slack: Optional[int] = None,
                 prefix_reuse: bool = False):
        self.model = model
        self.cfg = cfg
        self.packed = packed
        self.plan = plan       # PrecisionPlan: per-layer pack widths
        # kv_bits: plan-width quantized KV ring storage (serving/kvcache);
        # None keeps the exact legacy fp cache and its byte-identical HLO
        self.kv_bits = kv_bits
        # snapshot the trace-time configuration in scope at construction
        # (a RunContext's activate(), or the process defaults): every
        # trace this engine owns re-binds exactly this snapshot, so
        # engines built under different contexts — two precisions, two
        # meshes, one process — never read each other's flags
        self._axes = get_axes()
        self._compute_dtype = get_compute_dtype()
        if packed:
            from .packed import pack_for_serving
            params, qstate = pack_for_serving(params, qstate, plan)
        self.p = params
        self.q = qstate
        self.slots = batch_slots
        self.max_len = max_len
        self.eos = eos_id
        W = min(max_len, cfg.window) if cfg.window else max_len
        self.prefill_chunk = max(1, min(prefill_chunk, W))
        # ring_slack: a windowed ring buffer gets prefill_chunk extra slots
        # so writing a whole chunk never evicts history still inside the
        # chunk's oldest query window — chunked prefill stays exact.  An
        # explicit ring_slack only widens that floor (shrinking below the
        # chunk would break prefill exactness).
        self.ring_slack = (self.prefill_chunk if not ring_slack
                           else max(ring_slack, self.prefill_chunk))
        self.caches = model.init_cache(cfg, batch_slots, max_len,
                                       ring_slack=self.ring_slack,
                                       kv_bits=kv_bits)
        self.prefix_reuse = prefix_reuse
        # prompt tuple -> (prefilled slot slice, last-position logits);
        # bounded LRU so long-lived engines don't hoard cache slices
        self._prefix_cache: "dict" = {}
        self._prefix_cap = 32
        # a zeroed single-slot cache slice: prefill always starts from a
        # clean slot (also resets recurrent state left by the previous
        # occupant — KV junk is masked by positions, recurrent state isn't)
        self._fresh_slot = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype),
            self.caches)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)   # cache fill level
        self._next_tok = np.zeros(batch_slots, np.int32)  # next decode input
        self._key = jax.random.PRNGKey(seed)
        self._rid = 0                                     # requests admitted
        self._build()

    # ------------------------------------------------------------------
    def _build(self) -> None:
        model, cfg, kv_bits = self.model, self.cfg, self.kv_bits

        # each program's name (``jit_engine_decode`` ...) is how a
        # profile's module list, and the benchmark reading it, finds it
        def engine_decode(p, q, c, tok, pos, key, temp, topk, enable):
            if kv_bits is None:
                logits, c = model.decode_step(p, q, c, tok, pos, cfg)
            else:
                logits, c = model.decode_step(p, q, c, tok, pos, cfg,
                                              kv_bits=kv_bits)
            return _sample(logits[:, -1], key, temp, topk, enable), c

        def engine_prefill(p, q, cs, tok, pos):
            if kv_bits is None:
                return model.decode_step(p, q, cs, tok, pos, cfg)
            return model.decode_step(p, q, cs, tok, pos, cfg,
                                     kv_bits=kv_bits)

        # donate the cache through the per-token tick and the slot splice so
        # XLA aliases it in place instead of copying the full KV/state tree
        # every decoded token (self.caches is reassigned from the result).
        # _prefill must NOT donate: its first cs is the reused _fresh_slot.
        def engine_splice(c, cs, s):
            return jax.tree_util.tree_map(
                lambda a, u: jax.lax.dynamic_update_slice_in_dim(
                    a, u, s, axis=1), c, cs)

        def engine_first_token(logits, key, temp, topk, enable):
            return _sample(logits, key, temp, topk, enable)

        self._decode = jax.jit(engine_decode, static_argnums=(8,),
                               donate_argnums=(2,))
        self._prefill = jax.jit(engine_prefill)
        self._write_slot = jax.jit(engine_splice, donate_argnums=(0,))
        self._sample1 = jax.jit(engine_first_token, static_argnums=(4,))

    def _run(self, fn, *args):
        """Call a jitted function under this engine's trace-time snapshot
        (axis registry + compute dtype captured at construction) plus its
        packed-matmul routing (all read at trace time; jit caches per
        engine tree)."""
        with axis_scope(self._axes), \
                compute_dtype_scope(self._compute_dtype), \
                packed_matmul(self.packed):
            return fn(*args)

    def decode_program(self):
        """(jaxpr, compiled HLO text) of the engine's decode tick, traced
        on representative full-batch args — the program ``step()`` runs.
        This is what ``repro.analysis`` lints: packed-weight dtypes,
        cache donation and upcasts are judged on this artifact."""
        B = self.slots
        tok = jnp.zeros((B, 1), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        temp = jnp.zeros((B,), jnp.float32)
        topk = jnp.zeros((B,), jnp.int32)
        traced = self._run(self._decode.trace, self.p, self.q, self.caches,
                           tok, pos, self._key, temp, topk, True)
        return traced.jaxpr, traced.lower().compile().as_text()

    # ------------------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _sampling(self, req: Request) -> SamplingConfig:
        return req.sampling or GREEDY

    def _split_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _prefill_prompt(self, prompt: List[int], cs=None):
        """Chunked prefill of one prompt into a single-slot slice at
        offset 0: (slice, last-position logits).  ``cs`` starts from a
        caller-held slice instead of the fresh one (streaming ASR: the
        decoder prompt prefills into the slice whose encoder memory was
        already streamed in)."""
        plen = len(prompt)
        C = self.prefill_chunk
        if cs is None:
            cs = self._fresh_slot
        last_logits = None
        start = 0
        # pad-free chunking: full chunks, then power-of-two tail chunks.
        # Padding would be masked fine by the per-position attention masks,
        # but it would advance recurrent (RG-LRU/RWKV) state — so chunks are
        # exact and compile count stays O(log C), not O(prompt lengths).
        while start < plen:
            n = C if plen - start >= C else \
                1 << ((plen - start).bit_length() - 1)
            with obs.span("engine.prefill_chunk", start=start, n=n):
                tok = jnp.asarray([prompt[start:start + n]], jnp.int32)
                logits, cs = self._run(self._prefill, self.p, self.q, cs,
                                       tok, jnp.int32(start))
            obs.count("engine.prefill_chunks")
            obs.count("engine.prefill_tokens", n)
            start += n
            if start >= plen:
                last_logits = logits[:, -1]
        return cs, last_logits

    def submit(self, req: Request) -> Optional[RequestHandle]:
        """Admit one request: chunked prefill into a fresh slot slice at
        offset 0, splice it into the batch cache, sample the first token.
        Returns a truthy :class:`RequestHandle`, or None when no slot is
        free."""
        slot = self._free_slot()
        if slot is None:
            return None
        plen = len(req.prompt)
        if plen < 1 or req.max_new < 1 or \
                plen + req.max_new > self.max_len:
            raise ValueError(
                f"need prompt >= 1 ({plen}), max_new >= 1 ({req.max_new}), "
                f"and prompt + max_new <= max_len ({self.max_len})")
        self._rid += 1
        with obs.span("engine.submit", rid=self._rid, prompt=plen,
                      slot=slot):
            key = tuple(req.prompt) if self.prefix_reuse else None
            if key is not None and key in self._prefix_cache:
                # exact-prompt reuse: the cached slice is an immutable jax
                # value (prefill never donates), so splicing it again is
                # safe
                cs, last_logits = self._prefix_cache.pop(key)
                self._prefix_cache[key] = (cs, last_logits)  # LRU refresh
                obs.count("engine.prefix_hits")
            else:
                cs, last_logits = self._prefill_prompt(req.prompt)
                if key is not None:
                    self._prefix_cache[key] = (cs, last_logits)
                    while len(self._prefix_cache) > self._prefix_cap:
                        self._prefix_cache.pop(
                            next(iter(self._prefix_cache)))
            with obs.span("engine.splice"):
                self.caches = self._write_slot(self.caches, cs,
                                               jnp.int32(slot))
            sc = self._sampling(req)
            with obs.span("engine.first_token"):
                first = self._run(
                    self._sample1, last_logits, self._split_key(),
                    jnp.asarray([sc.temperature], jnp.float32),
                    jnp.asarray([sc.top_k], jnp.int32), sc.temperature > 0)
                tok = int(first[0])
            self.slot_req[slot] = req
            self.slot_pos[slot] = plen
            self._next_tok[slot] = tok
            self._record(slot, tok)
        obs.count("engine.admitted")
        return RequestHandle(req, rid=self._rid)

    def _record(self, slot: int, token: int) -> None:
        """Append a sampled token; finish + recycle the slot on EOS/len."""
        req = self.slot_req[slot]
        req.out.append(token)
        if (self.eos is not None and token == self.eos) or \
                len(req.out) >= req.max_new:
            req.done = True
            self.slot_req[slot] = None
            obs.count("engine.finished")

    def step(self) -> None:
        """One engine tick: a single jitted ragged decode step advancing
        every active slot by one token (inactive slots ride along masked
        by their own positions)."""
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return
        with obs.span("engine.step", active=len(active)):
            with obs.span("engine.step.inputs"):
                tok = jnp.asarray(self._next_tok, jnp.int32)[:, None]
                pos = jnp.asarray(self.slot_pos, jnp.int32)
                temp = jnp.asarray(
                    [self._sampling(r).temperature if r else 0.0
                     for r in self.slot_req], jnp.float32)
                topk = jnp.asarray(
                    [self._sampling(r).top_k if r else 0
                     for r in self.slot_req], jnp.int32)
                enable = any(
                    self._sampling(self.slot_req[i]).temperature > 0
                    for i in active)
                key = self._split_key()
            with obs.span("engine.step.dispatch"):
                nxt, self.caches = self._run(
                    self._decode, self.p, self.q, self.caches, tok, pos,
                    key, temp, topk, enable)
            with obs.span("engine.step.sync"):
                nxt = np.asarray(nxt)
            with obs.span("engine.step.record"):
                for i in active:
                    self.slot_pos[i] += 1
                    self._next_tok[i] = nxt[i]
                    self._record(i, int(nxt[i]))
        obs.count("engine.ticks")
        obs.count("engine.slot_ticks", len(active))

    def tokens(self, handle: RequestHandle):
        """Incremental token reader for one admitted request: yields each
        sampled token as it lands, ticking the engine (``step()``) when
        the request has produced nothing new yet.  Other active slots
        advance on the same ticks — interleaving readers IS continuous
        batching."""
        req = handle.request
        while True:
            while handle._cursor < len(req.out):
                tok = req.out[handle._cursor]
                handle._cursor += 1
                yield tok
            if req.done:
                return
            self.step()

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve a workload to completion with continuous batching: the
        thin batch wrapper over ``submit``/``step``."""
        pending = list(requests)
        while pending or any(r is not None for r in self.slot_req):
            while pending and self._free_slot() is not None:
                self.submit(pending.pop(0))
            self.step()
        return requests


@functools.lru_cache(maxsize=None)
def _generate_decode_fn(model, cfg: ModelConfig):
    """One jitted decode_step per (model, cfg): repeated generate() calls
    (the per-request test reference) reuse the compiled [B, 1] decode
    instead of re-tracing a fresh lambda each call."""
    return jax.jit(lambda p, q, c, t, pos:
                   model.decode_step(p, q, c, t, pos, cfg))


def generate(model, params, qstate, cfg: ModelConfig, prompt: jax.Array,
             max_new: int, *, cache_len: Optional[int] = None,
             packed: bool = False, plan=None) -> jax.Array:
    """Single-batch greedy generation — the per-request reference the
    engine is tested against.  ``cache_len`` pins the cache width (so
    engine/reference runs share identical masked-attention shapes);
    ``packed=True`` serves from the quantized-packed tree like the
    engine (``plan`` selects per-layer pack widths, ``None`` = int8)."""
    B, S = prompt.shape
    if packed:
        from .packed import pack_for_serving
        params, qstate = pack_for_serving(params, qstate, plan)
    if cache_len is not None and cfg.window is None \
            and cache_len < S + max_new:
        # a windowed ring wraps; a full cache does not — writes past
        # cache_len would be silently dropped and outputs quietly wrong
        raise ValueError(f"cache_len ({cache_len}) < prompt + max_new "
                         f"({S + max_new}) on an unwindowed model")
    # ring_slack=S: the whole-prompt prefill writes S tokens in one chunk
    caches = model.init_cache(cfg, B, cache_len or (S + max_new),
                              ring_slack=S)
    decode = _generate_decode_fn(model, cfg)

    def call(*args):
        with packed_matmul(packed):
            return decode(*args)

    logits, caches = call(params, qstate, caches, prompt, jnp.int32(0))
    pos = S
    last = jnp.argmax(logits[:, -1:], axis=-1)
    outs = [last]
    for _ in range(max_new - 1):
        logits, caches = call(params, qstate, caches, last, jnp.int32(pos))
        last = jnp.argmax(logits[:, -1:], axis=-1)
        outs.append(last)
        pos += 1
    return jnp.concatenate(outs, axis=1)
