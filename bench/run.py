"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout.  The cell is an entry of ``BENCHMARK.json``;
everything else it needs is found by name under ``bench/`` (see
``bench/harness.py``).  Exits non-zero, before any work, unless JAX sees
a TPU with the cell's chip count.

Output: progress lines; the generator's counts and lateness; then, as
the last lines on stderr, each number the correctness check compared
beside its limit; and as the last line on stdout one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _applies(metric, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # JAX's persistent compilation cache at a fixed path in the checkout,
    # set before JAX is imported so that it overrides any inherited one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")
    sys.path.insert(0, HERE)
    from harness import Harness, load_module, trace_lib
    h = Harness(ROOT, args.workload, args.seed, args.seconds,
                bool(args.trace), T_START)
    h.require_chips()

    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    h.say(f"{args.workload} on {len(h.devices)} x "
          f"{h.devices[0].device_kind}, seed {args.seed}")

    driver = load_module(h.driver_path, "bench_driver")
    res = driver.run(h)

    cell = args.workload
    metrics = {}
    if args.trace:
        h.reduce_trace()
        for m in h.bench["per_layer"]:
            if not _applies(m, cell):
                continue
            reader = load_module(os.path.join(HERE, "metrics",
                                              f"{m['name']}.py"),
                                 "bench_metric")
            v = reader.read(dict(res["record"], trace=h.trace_red,
                                 peaks=h.peaks))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in h.bench["end_to_end"]:
            if _applies(m, cell):
                metrics[m["name"]] = {"value": res["metrics"][m["name"]],
                                      "unit": m["unit"]}
    checks = {name: {"value": v, "limit": lim}
              for name, v, lim in res["checks"]}
    correct = bool(res["checks"]) and all(
        v is not None and v <= lim for _, v, lim in res["checks"])
    for name, v, lim in res["checks"]:
        print(f"check {name}: {v!r} (limit {lim!r})", file=sys.stderr)
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": h.device_record()}
    if args.trace and h.trace_red is not None:
        out["breakdown"] = trace_lib.breakdown(h.trace_red)
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
