"""Smoke run of the repo's two main paths on a TPU, at qwen2-0.5b's
published widths (24 layers, d_model 896, 14 heads, 2 KV heads, d_ff
4864, vocab 151,936), with random weights from ``--seed``.

    python chip_smoke.py [--seed N]     # one chip: train + serve phases
    python chip_smoke.py --four-chips   # 2x2 chips: int8-wire-2d vs fp32

One chip:

* train — ``RunSpec`` -> ``api.build`` -> ``init_training`` -> 3 HGQ
  steps at batch 8 x seq 512 (the compiled step needs ~10 GiB of the
  chip's 16: ``memory_analysis`` of the step compiled for a v5e).  The
  loss must be finite and EBOPs positive.
* serve — three continuous-batching engines (fp, ``packed`` weights on
  ``kernels/qmatmul``, and the 4-bit ``kv_cache="plan"`` of
  ``examples/specs/serving_kv_plan.json`` on ``kernels/kv_dequant``)
  each serve 6 greedy requests with 32-256 token prompts.  The fp
  engine is held to the model's one-shot forward (``_check_fp``) and
  compared with ``serving.generate()``; the packed and plan decode
  programs must hold their Pallas kernels (``tpu_custom_call``), so a
  silent fallback to the jnp reference fails.

Four chips: the compressed gradient exchange over the data axis (the 2D
int8 wire) against the fp32 all-reduce, 3 steps each on a 2x2 mesh and
one repeated batch; after steps 1 and 2 the two losses must agree within
``WIRE_BAND`` of the fp32 loss's drop from step 0.

Every phase asserts its outcome and any failure exits non-zero.  On a
machine whose JAX sees no TPU the script refuses before doing any work.
The last line printed is the JSON device record; the timings printed
before it are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2-0.5b"
FULL = True
BATCH, SEQ = 8, 512            # fits one v5e: see the module docstring
STEPS = 3
PROMPT_LENS = (32, 77, 128, 190, 256, 45)
MAX_NEW = 16
SLOTS = 4                      # fewer slots than requests: slots recycle
MAX_LEN = 512
# --four-chips trains on one repeated batch, so the loss moves by learning
# alone; after steps 1 and 2, |wire - fp32| <= WIRE_BAND x fp32's drop
# from step 0.  At the published vocab the int8 wire itself trails fp32:
# 0.036 and 0.324 on a v5e 2x2 at full widths, 0.113 and 0.176 on 4 host
# CPU devices at d_model 56, vocab 151,936, seq 64.  A wire that delivers
# zeros reads 1.0 there.  One that delivers half reads as the real wire
# (Adam is nearly scale-blind), which no loss band can catch.
WIRE_BAND = 0.5
# The fp engine's tokens against the model's one-shot forward, teacher-
# forced on the engine's own output.  On the CPU the two agree bit for bit
# (tests/test_serving_engine.py).  On a v5e they need not: the engine's
# chunked prefill and batched decode reduce in other orders than the
# one-shot forward, an HGQ quantizer can turn a last-bit difference into a
# whole grid step, and a near tie among 151,936 logits then flips.  So
# every served token must be within TIE_BAND x the row's logit std of the
# reference's top logit, and at most TIE_SHARE of them may sit below it.
# One v5e, seed 0: 4 of 96 tokens below it, by at most 0.0074 std; with
# the decode position planted one late, gaps of up to 1.66 std (CPU,
# smoke config).
TIE_BAND = 0.1
TIE_SHARE = 0.1


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    """A phase outcome: raises (and so exits non-zero) when not met."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def device_bytes_limit() -> int:
    import jax
    return jax.devices()[0].memory_stats()["bytes_limit"]


def require_tpu(count: int):
    """The devices to run on; exits non-zero unless JAX sees ``count``
    or more TPU devices."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        print(f"chip_smoke: needs {count} TPU device(s), JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(1)
    return devices


def _spec(seed: int, **kw):
    """The smoke's RunSpec: weights and data both drawn from ``seed``."""
    from repro.api import RunSpec
    from repro.data.synthetic import DataSpec
    base = RunSpec(arch=ARCH, full=FULL, seed=seed)
    return dataclasses.replace(
        base, data=DataSpec(kind="lm", batch=BATCH, seq=SEQ, vocab=0,
                            seed=seed),
        train=dataclasses.replace(base.train, steps=STEPS), **kw)


def _train(spec, tag: str, one_batch: bool = False):
    """Build, compile and run STEPS train steps of ``spec``: (losses,
    compiled HLO text).  ``one_batch`` feeds batch 0 at every step."""
    import jax.numpy as jnp
    from repro.api import build

    setup = build(spec).init_training()
    if one_batch:
        first = setup.pipeline(0)
        setup.pipeline = lambda step: first
    args = [setup.params, setup.qstate, setup.opt, setup.pipeline(0),
            jnp.int32(0)]
    if setup.ef_state is not None:
        args.append(setup.ef_state)
    t0 = time.perf_counter()
    compiled = setup.jitted.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    limit = device_bytes_limit()
    log(f"{tag}: batch {BATCH} x seq {SEQ}; the step needs "
        f"{need / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB per device")
    check(need <= limit, f"{tag}: step needs {need} B > {limit} B")
    log(f"{tag}: compile {compile_s:.1f} s (smoke timing)")
    losses, times = [], []
    for step in range(STEPS):
        t0 = time.perf_counter()
        m = setup.step(step)
        loss, ebops = float(m["loss"]), float(m["ebops"])
        times.append(time.perf_counter() - t0)
        log(f"{tag}: step {step} loss {loss:.6f} ebops {ebops:.6g}")
        check(math.isfinite(loss), f"{tag}: loss {loss} at step {step}")
        check(ebops > 0, f"{tag}: ebops {ebops} at step {step}")
        losses.append(loss)
    steady = sum(times[1:]) / len(times[1:])
    log(f"{tag}: steady step {steady:.3f} s (mean of steps 1-{STEPS - 1}; "
        f"smoke timing, not a benchmark number)")
    return losses, compiled.as_text()


def train_phase(seed: int) -> None:
    from repro.api import MeshSpec
    _train(_spec(seed, mesh=MeshSpec.host(1, 1)), "train")


def _requests(vocab: int, seed: int):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab, n).tolist(),
                    max_new=MAX_NEW) for n in PROMPT_LENS]


def kernels_in(hlo: str) -> set:
    """Names of the jitted Pallas kernels compiled into ``hlo`` (the
    ``tpu_custom_call`` instructions)."""
    return {name for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for name in ("qmatmul", "kv_quantize_rows", "kv_attention_rows")
            if f"jit({name})" in line}


def _serve(ctx, params, qstate, tag: str):
    eng = ctx.make_engine(params, qstate, max_len=MAX_LEN)
    reqs = _requests(ctx.cfg.vocab, ctx.spec.seed)
    t0 = time.perf_counter()
    eng.run(reqs)
    log(f"{tag}: {len(reqs)} requests, {SLOTS} slots, "
        f"{sum(len(r.out) for r in reqs)} tokens in "
        f"{time.perf_counter() - t0:.1f} s (compiles included; smoke "
        f"timing)")
    for r in reqs:
        check(r.done and len(r.out) == MAX_NEW
              and all(0 <= t < ctx.cfg.vocab for t in r.out),
              f"{tag}: request output {r.out}")
    return eng, reqs


def _reference_logits(ctx, params, qstate, reqs):
    """Per request, the reference logits [MAX_NEW, V] that predict each
    served token: the model's one-shot forward at batch 1 over prompt +
    out[:-1] (no slots, chunks or ragged positions), padded to one length
    so that it compiles once."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model, cfg = ctx.model, ctx.cfg
    T = max(len(r.prompt) for r in reqs) + MAX_NEW
    fwd = jax.jit(lambda p, q, c, t: model.decode_step(
        p, q, c, t, jnp.int32(0), cfg)[0])
    out = []
    for r in reqs:
        toks = r.prompt + r.out[:-1]
        cache = model.init_cache(cfg, 1, T, ring_slack=T)
        logits = fwd(params, qstate, cache,
                     jnp.asarray([toks + [0] * (T - len(toks))], jnp.int32))
        P = len(r.prompt)
        out.append(np.asarray(logits[0, P - 1:P - 1 + MAX_NEW], np.float32))
    return out


def _check_fp(ctx, params, qstate) -> None:
    """Serve the requests on the fp engine and hold every token to the
    one-shot reference within TIE_BAND; where the engine and
    ``generate()`` part, both tokens must be near-ties there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import generate

    # float32 matmul passes: the TPU's default single bf16 pass would add
    # its own rounding, which differs with the operands' shapes
    with jax.default_matmul_precision("float32"):
        _, reqs = _serve(ctx, params, qstate, "serve fp")
        refs = _reference_logits(ctx, params, qstate, reqs)
        gens = [[int(t) for t in generate(
            ctx.model, params, qstate, ctx.cfg,
            jnp.asarray([r.prompt], jnp.int32), r.max_new,
            cache_len=MAX_LEN)[0]] for r in reqs]
    below = 0
    for i, (r, L, gen) in enumerate(zip(reqs, refs, gens)):
        top, std = L.max(-1), L.std(-1)
        gap = (top - L[np.arange(MAX_NEW), r.out]) / std
        agree = next((t for t in range(MAX_NEW) if r.out[t] != gen[t]),
                     MAX_NEW)
        log(f"serve fp: request {i}: engine == generate() for the first "
            f"{agree} of {MAX_NEW} tokens; {int((gap > 0).sum())} engine "
            f"tokens below the reference's top logit, by at most "
            f"{float(gap.max())!r} logit std (band {TIE_BAND})")
        check(gap.max() <= TIE_BAND,
              f"serve fp: request {i}: engine {r.out} is off the reference "
              f"(gaps {gap.tolist()})")
        if agree < MAX_NEW:
            g = (top[agree] - L[agree, gen[agree]]) / std[agree]
            check(g <= TIE_BAND,
                  f"serve fp: request {i}: generate() {gen} is off the "
                  f"reference at token {agree} by {g} logit std")
        below += int((gap > 0).sum())
    n = len(reqs) * MAX_NEW
    check(below <= TIE_SHARE * n,
          f"serve fp: {below} of {n} tokens below the reference's top "
          f"logit (at most {TIE_SHARE} allowed)")
    log(f"serve fp: every token within {TIE_BAND} logit std of the "
        f"reference; {below} of {n} below its top logit")


def serve_phase(seed: int) -> None:
    from repro.api import RunSpec, ServingSpec, build

    fp = build(_spec(seed, serving=ServingSpec(slots=SLOTS)))
    params, qstate = fp.init_state()
    _check_fp(fp, params, qstate)

    packed = build(_spec(seed, serving=ServingSpec(slots=SLOTS,
                                                   packed=True)))
    plan_file = RunSpec.from_file(
        os.path.join(ROOT, "examples", "specs", "serving_kv_plan.json"))
    plan = build(dataclasses.replace(
        plan_file, full=FULL, seed=seed,
        serving=dataclasses.replace(plan_file.serving, slots=SLOTS)))
    for ctx, tag, want in ((packed, "serve packed", {"qmatmul"}),
                           (plan, "serve plan", {"kv_quantize_rows",
                                                 "kv_attention_rows"})):
        eng, _ = _serve(ctx, params, qstate, tag)
        if tag == "serve plan":
            check(eng.kv_bits == 4, f"{tag}: kv_bits {eng.kv_bits}, not 4")
        found = kernels_in(eng.decode_program()[1])
        log(f"{tag}: decode program kernels {sorted(found)}")
        check(want <= found, f"{tag}: decode program lacks {want - found}")
        del eng


def four_chip_phase(seed: int) -> None:
    from repro.analysis import parse_collectives
    from repro.api import CompressionSpec, MeshSpec

    losses, hlo = {}, {}
    for kind in ("none", "int8-wire-2d"):
        losses[kind], hlo[kind] = _train(
            _spec(seed, mesh=MeshSpec.host(2, 2),
                  compression=CompressionSpec(kind=kind)), f"2x2 {kind}",
            one_batch=True)
    wire = {(c.kind, c.dtype)
            for c in parse_collectives(hlo["int8-wire-2d"])}
    log(f"2x2 int8-wire-2d: s8 collectives "
        f"{sorted(k for k, d in wire if d == 's8')}")
    check({("all-to-all", "s8"), ("all-gather", "s8")} <= wire,
          f"2x2 int8-wire-2d: no s8 all-to-all/all-gather in {wire}")
    ref = losses["none"]
    for step in range(1, STEPS):
        a, b = losses["int8-wire-2d"][step], ref[step]
        drop = ref[0] - b
        check(drop > 0, f"2x2 step {step}: fp32 loss did not drop "
                        f"({ref[0]} -> {b})")
        log(f"2x2 step {step}: int8-wire-2d {a!r} fp32 {b!r}; gap "
            f"{abs(a - b) / drop!r} of fp32's drop {drop!r} "
            f"(band {WIRE_BAND})")
        check(abs(a - b) <= WIRE_BAND * drop,
              f"2x2 step {step}: losses {a} and {b} outside the band")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 int8-wire-2d vs fp32 training "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the random weights and data")
    args = ap.parse_args()
    devices = require_tpu(4 if args.four_chips else 1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        train_phase(args.seed)
        serve_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
