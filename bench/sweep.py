"""Knee sweep of a serving cell: the same engine under the cell's mix at a
list of fixed rates, to find the highest rate it sustains.

    python3 bench/sweep.py --workload <serving cell> --seed <n> \\
        --seconds <s> --rates 2,4,6,8

One process builds and warms the engine once, then serves each rate for
the mix's ``warmup_s`` plus ``--seconds`` seconds of open-loop traffic.
One line per rate: latencies, tokens per second, the generator's
lateness, and the 90th percentile of time to first token over the first
and the last third of the window (a backlog that grows shows as the
second above the first).  Runs on the chip only; no correctness check.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")
    sys.path.insert(0, HERE)
    from harness import Harness, load_module, percentile
    h = Harness(ROOT, args.workload, args.seed, args.seconds, False,
                time.perf_counter())
    h.require_chips()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import traffic
    serve = load_module(h.driver_path, "bench_driver")
    ctx, eng = serve.build_engine(h)
    serve.warm_shapes(eng)
    T, warm = args.seconds, h.mix["warmup_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(h.mix, rate_per_s=rate)
        arrivals = (traffic.schedule(mix, h.seed, -warm, warm,
                                     ctx.cfg.vocab, 1)
                    + traffic.schedule(mix, h.seed, 0.0, T, ctx.cfg.vocab,
                                       2))
        origin = time.perf_counter() + warm
        streams = serve.serve(h, eng, arrivals, origin, T)
        m, gen = serve.window_metrics(streams, T)
        thirds = [[(s.times[0] - s.due) * 1e3 for s in streams
                   if k * T / 3 <= s.due < (k + 1) * T / 3]
                  for k in (0, 2)]
        steps = [b - a for n, a, b, _ in h.spans.items
                 if n == "bench.step" and origin <= a < origin + T]
        print(json.dumps({
            "rate_per_s": rate, **m, **gen,
            "ttft_p90_first_third_ms": percentile(thirds[0], 90),
            "ttft_p90_last_third_ms": percentile(thirds[1], 90),
            "tick_p50_ms": percentile(steps, 50) * 1e3,
            "device": h.devices[0].device_kind}), flush=True)
        # drain what is left before the next rate
        while any(r is not None for r in eng.slot_req):
            eng.step()
        h.spans.items.clear()


if __name__ == "__main__":
    main()
