"""Readings that set a cell's correctness limits: the program's own, the
control's and the planted faults', at the cell's size, over seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 10]

The configuration states bfloat16 matmul operands (the TPU's default
precision for float32 matmuls), so the control is the plain reference
with float8 (e4m3) operands, read two ways: cast as they are
(``control_fp8``), and on per-tensor power-of-two scales, operands and
backward cotangents both (``control_fp8_scaled``), as float8 training
does.

Training cells: per seed, the plain reference in float32 against
(a) both controls, (b) bfloat16 operands and (c) itself with the
cross-entropy taken over half of each batch's rows, the extremes and
the regularizer over all of them (a planted fault).  A step that
returns its state unchanged reads 1 on ``grad_gap`` by construction and
needs no run.

Serving cells: per seed, the program serves the cell's mix for
``--seconds``, then on the same sample of finished requests the
reference reads the gaps (widest, mean, 99th percentile, share below
the best) of the served tokens (the program's reading), of the tokens
that each control puts first, of those that bfloat16 operands put first,
and of the served tokens with one token altered (a planted fault).  One
JSON line per seed.  Runs on the chip; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROL = "float8_e4m3fn"     # the control's matmul operands


def train_readings(h, seed: int):
    import jax
    import jax.numpy as jnp
    import traffic
    train = _driver(h)
    ref = h.reference()
    cfg, mix = h.config, h.mix
    tcfg = dict(cfg["runspec"]["train"])
    V = cfg["vocab_size"]
    p0 = jax.jit(lambda k: ref.make_weights(k, cfg)[0])(
        traffic.seed_key(seed, 0))
    key = traffic.seed_key(seed, 1)
    batches = [traffic.train_batch(key, jnp.int32(s), mix["batch"],
                                   mix["seq"], V) for s in range(3)]

    base = ref.train_steps(p0, batches, cfg, tcfg)
    runs = {"control_fp8": dict(dtype=CONTROL),
            "control_fp8_scaled": dict(dtype=ref.SCALED_FP8),
            "bf16_operands": dict(dtype=jnp.bfloat16),
            "half_loss": dict(half_loss=True)}
    return {k: train.gaps(ref.train_steps(p0, batches, cfg, tcfg, **kw),
                          base)
            for k, kw in runs.items()}


def serve_readings(h, seed: int, seconds: float):
    import numpy as np
    import traffic
    serve = _driver(h)
    ctx, eng = serve.build_engine(h)
    serve.warm_shapes(eng)
    warm = h.mix["warmup_s"]
    arrivals = (traffic.schedule(h.mix, seed, -warm, warm, ctx.cfg.vocab, 1)
                + traffic.schedule(h.mix, seed, 0.0, seconds, ctx.cfg.vocab,
                                   2))
    streams = serve.serve(h, eng, arrivals, time.perf_counter() + warm,
                          seconds)
    reqs = serve.sample(streams, seed, h.mix["check_tokens"])
    del eng, ctx, streams
    h.free()
    prog = serve.reference_gaps(h, reqs)
    import jax.numpy as jnp
    ctrl = serve.reference_gaps(h, reqs, CONTROL)
    ctrl_s = serve.reference_gaps(h, reqs, h.reference().SCALED_FP8)
    bf16 = serve.reference_gaps(h, reqs, jnp.bfloat16)
    rng = traffic.seed_rng(seed, 11)
    bad = []
    for prompt, out in reqs:
        out = list(out)
        j = int(rng.integers(len(out)))
        out[j] = (out[j] + 1 + int(rng.integers(h.config["vocab_size"] - 1))
                  ) % h.config["vocab_size"]
        bad.append((prompt, out))
    fault = serve.reference_gaps(h, bad)

    def stats(gs):
        g = np.concatenate(gs)
        return {"max_gap_std": float(g.max()), "mean_gap_std":
                float(g.mean()), "p99_gap_std": float(np.percentile(g, 99)),
                "share_below": float((g > 0).mean())}
    return {"program": stats(prog), "control_fp8": stats(ctrl),
            "control_fp8_scaled": stats(ctrl_s),
            "bf16_operands": stats(bf16), "token_altered": stats(fault),
            "tokens": sum(len(o) for _, o in reqs)}


def _driver(h):
    from harness import load_module
    return load_module(h.driver_path, "bench_driver")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")
    sys.path.insert(0, HERE)
    from harness import Harness
    for seed in (int(s) for s in args.seeds.split(",")):
        h = Harness(ROOT, args.workload, seed, args.seconds, False,
                    time.perf_counter())
        h.require_chips()
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
        if h.mix["driver"] == "train":
            r = train_readings(h, seed)
        else:
            r = serve_readings(h, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, **r,
                          "device": h.devices[0].device_kind}), flush=True)
        h.free()


if __name__ == "__main__":
    main()
