"""Continuous-batching serving benchmark: decode tokens/sec across
weight (fp vs HGQ int8-packed) and KV-cache (fp vs plan-width quantized)
modes.

Serves an identical ragged workload through ``repro.serving.Engine``
once per ``RunSpec`` mode — bf16/fp weights, the HGQ int8-packed tree
(``packed=True``, decode projections on ``kernels.qmatmul.qmatmul_any``),
and the plan-width quantized KV ring buffer
(``ServingSpec(kv_cache="plan")``, decode reads through
``kernels.kv_dequant``) — and reports two numbers per mode (compile
excluded via a warmup run): ``decode_tokens_per_sec``, pure jitted
decode ticks on a saturated batch (prefill untimed — the steady-state
hot-path number), and ``mixed_tokens_per_sec``, a full continuous-
batching run including chunked prefill and slot churn.  KV rows
additionally report ``kv_bytes_per_token`` and the cache-bandwidth
speedup ``decode_kv_speedup_x`` (decode is KV-bound, so stored cache
bytes per token are the structural decode-throughput model — the
number that holds on TPU where wall time on this container does not).
Writes a JSON artifact so CI accumulates the perf trajectory.

A fourth row (``mode="asr_stream"``) serves the shipped
``examples/specs/serving_asr_stream.json`` streaming-ASR spec through
``serving.StreamingEngine``: audio-chunk requests stream beside LM
traffic in the shared slot scheduler, and the row reports the
bounded-latency SLO metrics — ``ttft_ms`` (last chunk -> first token),
``chunk_latency_p50_ms`` / ``chunk_latency_p90_ms`` (per-chunk encode +
append wall), ``mixed_tokens_per_sec`` over the mixed workload, and the
structural ``cross_kv_bytes_per_request`` the quantized cross-attention
memory pins.

    PYTHONPATH=src python benchmarks/serving_bench.py --smoke
    PYTHONPATH=src python benchmarks/serving_bench.py \
        --arch qwen2-0.5b --requests 16 --max-new 32 --out BENCH_serving.json

On this CPU container the Pallas kernels run in interpret/reference
mode, so the packed and quantized-KV *wall times* are not the TPU story
(the structural bytes-moved numbers in the JSON are); on TPU the same
flags compile the kernels.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax


def ragged_requests(vocab: int, n: int, max_new: int, seed: int = 7):
    from repro.serving import Request
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        plen = 2 + (i * 5) % 13          # ragged prompt lengths 2..14
        toks = jax.random.randint(jax.random.fold_in(key, i), (plen,), 1,
                                  vocab)
        reqs.append(Request(prompt=[int(t) for t in toks], max_new=max_new))
    return reqs


def bench_engine(ctx, params, qstate, *, mode: str, n_requests: int,
                 max_new: int, max_len: int) -> dict:
    from repro.serving import kv_bytes_per_token
    cfg = ctx.cfg
    slots = ctx.spec.serving.slots
    eng = ctx.make_engine(params, qstate, max_len=max_len, prefill_chunk=8)
    # warmup: compile decode/prefill/sample once
    eng.run(ragged_requests(cfg.vocab, slots, 4))
    # decode-only: saturate every slot (prefill + first token untimed),
    # then time nothing but jitted ragged decode ticks
    dec_reqs = ragged_requests(cfg.vocab, slots, max_new, seed=11)
    for r in dec_reqs:
        if eng.submit(r) is None:
            raise RuntimeError("engine rejected a warm decode request")
    t0 = time.perf_counter()
    while any(s is not None for s in eng.slot_req):
        eng.step()
    dt_dec = time.perf_counter() - t0
    dec_tokens = sum(len(r.out) for r in dec_reqs) - len(dec_reqs)
    # mixed: full continuous-batching run (chunked prefill + slot churn)
    reqs = ragged_requests(cfg.vocab, n_requests, max_new)
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    new_tokens = sum(len(r.out) for r in reqs)
    # attention layers only: griffin/whisper mix in non-KV blocks, but
    # the archs this bench serves are all-attention stacks
    kv_fp = kv_bytes_per_token(cfg.n_kv, cfg.hd, cfg.n_layers, None)
    kv_now = kv_bytes_per_token(cfg.n_kv, cfg.hd, cfg.n_layers,
                                eng.kv_bits)
    return {"mode": mode,
            "spec": ctx.spec.to_dict(),
            "requests": n_requests,
            "kv_bits": eng.kv_bits,
            "kv_bytes_per_token": kv_now,
            # decode is KV-bandwidth-bound: stored cache bytes per token
            # are the structural decode-throughput model (TPU story)
            "decode_kv_speedup_x": round(kv_fp / kv_now, 2),
            "decode_tokens": dec_tokens, "decode_wall_s": round(dt_dec, 4),
            "decode_tokens_per_sec": round(dec_tokens / dt_dec, 2),
            "mixed_tokens": new_tokens, "mixed_wall_s": round(dt, 4),
            "mixed_tokens_per_sec": round(new_tokens / dt, 2)}


def bench_streaming(ctx, params, qstate, *, n_streams: int, n_lm: int,
                    max_new: int, max_len: int) -> dict:
    """Streaming-ASR SLO metrics: chunked audio through the continuous-
    batching slot scheduler with concurrent LM traffic, timed after a
    compile warmup."""
    from repro.serving import (AudioRequest, kv_bytes_per_token,
                               kv_cross_bytes_per_request)
    cfg = ctx.cfg

    def audio_reqs(seed):
        key = jax.random.PRNGKey(seed)
        return [AudioRequest(
            frames=jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.enc_seq, cfg.d_model)) * 0.3,
            prompt=[1, 2 + i % 7], max_new=max_new)
            for i in range(n_streams)]

    eng = ctx.make_engine(params, qstate, max_len=max_len,
                          prefill_chunk=8)
    # warmup: compile append_cross per block shape + prefill + decode
    eng.run(audio_reqs(3) + ragged_requests(cfg.vocab, n_lm, 4))
    streams = audio_reqs(11)
    reqs = streams + ragged_requests(cfg.vocab, n_lm, max_new, seed=13)
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    chunks = sorted(t for r in streams for t in r.t_chunks)
    ttfts = [r.ttft_s for r in streams]
    pct = lambda v, p: v[min(len(v) - 1, round(p * (len(v) - 1)))]
    tokens = sum(len(r.out) for r in reqs)
    return {"mode": "asr_stream",
            "spec": ctx.spec.to_dict(),
            "streams": n_streams, "lm_requests": n_lm,
            "chunk_frames": ctx.spec.serving.audio.chunk_frames,
            "chunks_per_stream": len(streams[0].t_chunks),
            "kv_bits": eng.kv_bits,
            "kv_bytes_per_token": kv_bytes_per_token(
                cfg.n_kv, cfg.hd, cfg.n_layers, eng.kv_bits),
            # static per-request cross-attention memory footprint (the
            # admission-control number; see serving/kvcache.py)
            "cross_kv_bytes_per_request": kv_cross_bytes_per_request(
                cfg.n_kv, cfg.hd, cfg.n_layers, cfg.enc_seq, eng.kv_bits),
            # SLO latencies: ttft = last chunk appended -> first token
            # sampled; chunk latency = one encode+quantize+append event
            "ttft_ms": round(1e3 * sum(ttfts) / len(ttfts), 2),
            "chunk_latency_p50_ms": round(1e3 * pct(chunks, 0.5), 2),
            "chunk_latency_p90_ms": round(1e3 * pct(chunks, 0.9), 2),
            "mixed_tokens": tokens, "mixed_wall_s": round(dt, 4),
            "mixed_tokens_per_sec": round(tokens / dt, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--full", action="store_true",
                    help="use the full (published) config, not smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny workload, smoke config")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the timed serving "
                         "runs to DIR (view with tensorboard or xprof)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.max_new = 6, 6

    import dataclasses

    from repro.api import PrecisionSpec, RunSpec, ServingSpec, build
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.launch.cache import enable_compile_cache
    from repro.serving.packed import pack_tree, packed_nbytes

    enable_compile_cache()

    # the bench measures exactly the declarative config the launcher and
    # the serving example run: one RunSpec per mode, coexisting contexts
    # (one engine's traces never touch another's).  kv_plan carries a
    # nibble-width KV plan (wire/pack stay uniform int8, so weights and
    # every other trace are the exact fp-row programs).
    base = RunSpec(arch=args.arch, full=args.full,
                   serving=ServingSpec(slots=args.batch_slots))
    kv_plan = PrecisionPlan(default=LayerPlan(kv_bits=4))
    modes = [
        ("fp", base),
        ("packed", dataclasses.replace(
            base, precision=PrecisionSpec(packed_serving=True))),
        ("kv_plan", dataclasses.replace(
            base, plan=kv_plan,
            serving=dataclasses.replace(base.serving, kv_cache="plan"))),
    ]
    ctxs = [(m, build(spec)) for m, spec in modes]
    params, qstate = ctxs[0][1].init_state()

    if args.profile:
        jax.profiler.start_trace(args.profile)
    rows = []
    for mode, ctx in ctxs:
        row = bench_engine(ctx, params, qstate, mode=mode,
                           n_requests=args.requests, max_new=args.max_new,
                           max_len=args.max_len)
        rows.append(row)
        print(f"serving.{row['mode']}: decode "
              f"{row['decode_tokens_per_sec']} tok/s, mixed "
              f"{row['mixed_tokens_per_sec']} tok/s "
              f"({row['mixed_tokens']} tokens / {row['mixed_wall_s']}s), "
              f"kv {row['kv_bytes_per_token']} B/tok "
              f"({row['decode_kv_speedup_x']}x)")
    # streaming ASR: serve the shipped golden spec (whisper enc-dec,
    # quantized cross+self KV, mixed lm+asr admission) — its own context
    # and params, coexisting with the LM contexts above
    asr_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "examples", "specs",
                            "serving_asr_stream.json")
    spec_asr = RunSpec.from_file(asr_path)
    if args.full:
        spec_asr = dataclasses.replace(spec_asr, full=True)
    ctx_asr = build(spec_asr)
    p_asr, q_asr = ctx_asr.init_state()
    row = bench_streaming(ctx_asr, p_asr, q_asr,
                          n_streams=3 if args.smoke else 4,
                          n_lm=3 if args.smoke else args.requests,
                          max_new=args.max_new, max_len=args.max_len)
    rows.append(row)
    print(f"serving.{row['mode']}: ttft {row['ttft_ms']}ms, chunk p50 "
          f"{row['chunk_latency_p50_ms']}ms p90 "
          f"{row['chunk_latency_p90_ms']}ms, mixed "
          f"{row['mixed_tokens_per_sec']} tok/s, cross-kv "
          f"{row['cross_kv_bytes_per_request']} B/req")

    if args.profile:
        jax.profiler.stop_trace()
        print(f"profiler trace written to {args.profile}")

    fp_b, q_b = packed_nbytes(params), packed_nbytes(pack_tree(params))
    result = {
        "bench": "serving", "arch": ctxs[0][1].cfg.name,
        "backend": jax.default_backend(),
        "batch_slots": args.batch_slots, "max_len": args.max_len,
        "weight_bytes_fp": fp_b, "weight_bytes_packed": q_b,
        "hbm_saving_x": round(fp_b / q_b, 2),
        "runs": rows,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
