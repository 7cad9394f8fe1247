"""Gradient-collective benchmark: bytes on the wire and step time for the
data-parallel mean-reduce, fp32 (ring all-reduce) vs bf16-wire vs
int8-wire (``repro.dist.collectives`` two-phase exchange), plus the 2D
(data x model) sliced exchange on DxM meshes, plus a mixed-precision
section where every packable matmul layer rides the int4 nibble wire
(``core.plan.mixed_low_plan``) against the uniform int8 wire.

Builds the real gradient-shaped tree of an architecture (every parameter
leaf), stacks it per data shard, and runs each reduction jitted on an
``n``-device host mesh.  Bytes are *measured from the traced collectives*
(``collectives.record_wire_bytes`` records every all_to_all / all_gather /
scale-pmax payload the compressed path actually emits, at its true dtype
and padded shape; the fp32/bf16-on-fp32-ring baselines use the ring
all-reduce model on the same leaves).  Wall time on this CPU container
reflects host collectives plus quantize arithmetic — the bytes column is
the interconnect story; on real inter-pod links the bytes ARE the time.

The 2D section compares, on 2x4 and 4x2 meshes of the same 8 devices:

* ``int8-wire`` (1D): the in-collective bytes PLUS the fp32 model-axis
  all_gather a TP train step pays to rematerialize model-sharded
  gradients before the model-replicated shard_map
  (``collectives.tp_replication_bytes`` per leaf — GSPMD inserts it
  implicitly, so the recorder cannot see it);
* ``int8-wire-2d``: in-collective bytes only — its per-leaf in_specs
  consume model-sharded gradients directly (replication cost 0), the
  data exchange runs on the 1/M slice, and the model-axis
  rematerialization moves int8.

    PYTHONPATH=src python benchmarks/collectives_bench.py --smoke
    PYTHONPATH=src python benchmarks/collectives_bench.py \
        --arch qwen2-0.5b --devices 8 --out BENCH_collectives.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--full", action="store_true",
                    help="use the full (published) config, not smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smoke config, few timing reps")
    ap.add_argument("--devices", type=int, default=8,
                    help="host data-parallel device count (forced via "
                         "XLA_FLAGS before jax init)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the timed "
                         "reductions to DIR (view with tensorboard or "
                         "xprof)")
    ap.add_argument("--out", default="BENCH_collectives.json")
    args = ap.parse_args()
    if args.smoke:
        args.reps = 9               # p50 of 9 — launch-latency noise on
        #                             1-core hosts swamps a 3-rep median

    flag = f"--xla_force_host_platform_device_count={args.devices}"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + flag).strip()
    import jax                      # noqa: E402 — after the device flag
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import common                   # noqa: E402 — benchmarks/ is sys.path[0]

    from repro.api import (CompressionSpec, MeshSpec, RunSpec, build,
                           build_mesh)
    from repro.dist import collectives
    from repro.dist.sharding import ef_residual_sharding, stacked_tree
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    # the bench measures the same declarative config surface the
    # launcher trains: one RunSpec per (mesh, compression) cell
    n = args.devices
    spec_1d = RunSpec(arch=args.arch, full=args.full,
                      mesh=MeshSpec.host(n, 1),
                      compression=CompressionSpec(kind="int8-wire"))
    ctx = build(spec_1d)
    cfg = ctx.cfg
    mesh = ctx.mesh
    params, _ = ctx.init_state()

    leaves = jax.tree.leaves(params)
    stacked_flags = jax.tree.leaves(stacked_tree(params))
    elements = int(sum(x.size for x in leaves))
    scale_rows = int(sum(x.shape[0] if (st and x.ndim >= 3) else 1
                         for x, st in zip(leaves, stacked_flags)))
    stacked = jax.tree.map(
        lambda x: jax.random.normal(
            jax.random.PRNGKey(x.size % 9973),
            (n,) + tuple(x.shape), jnp.float32) * 1e-3, params)

    def time_reduce(fn, tree):
        """Gate-worthy timing: warmup discarded, p50/p90/mean over reps."""
        return common.time_stats(fn, tree, warmup=2, reps=args.reps)

    def fp32_pmean_for(mesh_obj):
        # the ring all-reduce baseline: pmean over the data axis only
        def fp32_pmean(tree):
            spec = jax.tree.map(
                lambda leaf: P(("data",), *([None] * (leaf.ndim - 1))),
                tree)
            return jax.shard_map(
                lambda t: jax.tree.map(
                    lambda x: jax.lax.pmean(x[0], ("data",)), t),
                mesh=mesh_obj, in_specs=(spec,),
                out_specs=jax.tree.map(
                    lambda leaf: P(*([None] * (leaf.ndim - 1))), tree),
                check_vma=False)(tree)
        return fp32_pmean

    if args.profile:
        jax.profiler.start_trace(args.profile)

    rows = []
    placed = jax.device_put(stacked,
                            ef_residual_sharding(stacked, mesh))
    # fp32 baseline: the ring all-reduce the wire path replaces
    st = time_reduce(jax.jit(fp32_pmean_for(mesh)), placed)
    fp32_ms = st["p50_ms"]
    fp32_bytes = sum(collectives.fp32_allreduce_bytes(x.size, n)
                     for x in leaves)
    rows.append({"mode": "fp32", "bytes_on_wire_per_device": fp32_bytes,
                 "bytes_per_element": round(fp32_bytes / elements, 3),
                 "step_ms": round(st["p50_ms"], 2),
                 "p50_ms": round(st["p50_ms"], 2),
                 "p90_ms": round(st["p90_ms"], 2),
                 "reduction_vs_fp32": 1.0})
    for kind in ("bf16", "int8"):
        fn = jax.jit(lambda t, k=kind:
                     collectives.ef_wire_pmean(t, mesh, k))
        with collectives.record_wire_bytes() as rec:
            fn.lower(placed)                    # trace -> record bytes
        st = time_reduce(fn, placed)
        b = rec.total()
        rows.append({
            "mode": f"{kind}-wire",
            "bytes_on_wire_per_device": b,
            "bytes_per_element": round(b / elements, 3),
            "step_ms": round(st["p50_ms"], 2),
            "p50_ms": round(st["p50_ms"], 2),
            "p90_ms": round(st["p90_ms"], 2),
            "step_ratio_vs_fp32": round(st["p50_ms"] / fp32_ms, 3),
            "reduction_vs_fp32": round(fp32_bytes / b, 2)})

    # ---- mixed-precision section: every packable matmul layer on the
    # int4 nibble wire (a learned PrecisionPlan's maximal mixed plan),
    # everything else (biases, norms, activation f) at int8 — vs the
    # uniform int8 wire above
    from repro.core.plan import mixed_low_plan
    plan = mixed_low_plan(params, low_bits=4)
    widths = plan.wire_bits_tree(placed)
    uniform_b = rows[-1]["bytes_on_wire_per_device"]   # int8-wire
    fnm = jax.jit(lambda t: collectives.ef_wire_pmean(
        t, mesh, "int8", widths=widths))
    with collectives.record_wire_bytes() as recm:
        fnm.lower(placed)
    stm = time_reduce(fnm, placed)
    bm = recm.total()
    mixed = {
        "plan_summary": plan.summary(),
        "low_bits": 4,
        "runs": [{
            "mode": "int8-wire-uniform",
            "bytes_on_wire_per_device": uniform_b,
            "bytes_per_element": round(uniform_b / elements, 3)},
            {"mode": "int8-wire-mixed-w4w8",
             "bytes_on_wire_per_device": bm,
             "bytes_per_element": round(bm / elements, 3),
             "step_ms": round(stm["p50_ms"], 2),
             "p50_ms": round(stm["p50_ms"], 2),
             "p90_ms": round(stm["p90_ms"], 2),
             "step_ratio_vs_fp32": round(stm["p50_ms"] / fp32_ms, 3),
             "reduction_vs_uniform": round(uniform_b / bm, 2)}],
    }

    # ---- 2D (data x model) section: 1D vs 2D on DxM meshes of n devices
    mesh2d = []
    shapes_2d = [(n // m, m) for m in (4, 2)
                 if m < n and n % m == 0 and n // m >= 1]
    for (D, M) in shapes_2d:
        spec_2d = RunSpec(arch=args.arch, full=args.full,
                          mesh=MeshSpec.host(D, M),
                          compression=CompressionSpec(kind="int8-wire-2d"))
        mesh_dm = build_mesh(spec_2d.mesh)
        stacked_dm = jax.tree.map(
            lambda x, D=D: jax.random.normal(
                jax.random.PRNGKey(x.size % 9973),
                (D,) + tuple(x.shape), jnp.float32) * 1e-3, params)
        res2d = collectives.ef_wire2d_init(params, D, M)
        tp_repl = sum(collectives.tp_replication_bytes(x.shape, M)
                      for x in leaves)
        dm_rows = []
        placed_dm = jax.device_put(
            stacked_dm, ef_residual_sharding(stacked_dm, mesh_dm))
        res_placed = jax.device_put(
            res2d, ef_residual_sharding(res2d, mesh_dm, layout="2d"))
        # fp32 baseline on THIS mesh: D-device ring all-reduce plus
        # the fp32 model-axis replication a TP step pays either way
        st0 = time_reduce(jax.jit(fp32_pmean_for(mesh_dm)), placed_dm)
        fp32_dm_ms = st0["p50_ms"]
        fp32_b_dm = sum(collectives.fp32_allreduce_bytes(x.size, D)
                        for x in leaves)
        dm_rows.append({
            "mode": "fp32",
            "bytes_on_wire_per_device": fp32_b_dm,
            "tp_replication_bytes": tp_repl,
            "total_bytes_per_element": round(
                (fp32_b_dm + tp_repl) / elements, 3),
            "step_ms": round(st0["p50_ms"], 2),
            "p50_ms": round(st0["p50_ms"], 2),
            "p90_ms": round(st0["p90_ms"], 2)})
        fn1 = jax.jit(lambda t: collectives.ef_wire_pmean(
            t, mesh_dm, "int8"))
        with collectives.record_wire_bytes() as rec1:
            fn1.lower(placed_dm)
        st1 = time_reduce(fn1, placed_dm)
        total1 = rec1.total() + tp_repl
        dm_rows.append({
            "mode": "int8-wire",
            "bytes_on_wire_per_device": rec1.total(),
            "tp_replication_bytes": tp_repl,
            "total_bytes_per_element": round(total1 / elements, 3),
            "step_ms": round(st1["p50_ms"], 2),
            "p50_ms": round(st1["p50_ms"], 2),
            "p90_ms": round(st1["p90_ms"], 2),
            "step_ratio_vs_fp32": round(
                st1["p50_ms"] / fp32_dm_ms, 3)})
        fn2 = jax.jit(lambda t, r: collectives.ef_wire_pmean_2d(
            t, r, mesh_dm, "int8"))
        with collectives.record_wire_bytes() as rec2:
            fn2.lower(placed_dm, res_placed)
        st2 = time_reduce(lambda _: fn2(placed_dm, res_placed), None)
        total2 = rec2.total()
        dm_rows.append({
            "mode": "int8-wire-2d",
            "bytes_on_wire_per_device": rec2.total(),
            "tp_replication_bytes": 0.0,
            "total_bytes_per_element": round(total2 / elements, 3),
            "step_ms": round(st2["p50_ms"], 2),
            "p50_ms": round(st2["p50_ms"], 2),
            "p90_ms": round(st2["p90_ms"], 2),
            "step_ratio_vs_fp32": round(
                st2["p50_ms"] / fp32_dm_ms, 3),
            "reduction_vs_1d": round(total1 / total2, 2)})
        mesh2d.append({"mesh": f"{D}x{M}", "spec": spec_2d.to_dict(),
                       "runs": dm_rows})

    if args.profile:
        jax.profiler.stop_trace()
        print(f"profiler trace written to {args.profile}")

    result = {
        "bench": "collectives", "arch": cfg.name,
        "spec": spec_1d.to_dict(),
        "backend": jax.default_backend(), "devices": n,
        "grad_elements": elements, "scale_rows": scale_rows,
        "bytes_model": {
            k: collectives.wire_bytes_model(elements, n, k, scale_rows)
            for k in collectives.WIRE_KINDS},
        "runs": rows,
        "mixed_precision": mixed,
        "mesh2d": mesh2d,
    }
    for r in rows:
        print(f"collectives.{r['mode']}: "
              f"{r['bytes_per_element']} B/elt on the wire, "
              f"{r['step_ms']} ms/reduce "
              f"({r['reduction_vs_fp32']}x vs fp32)")
    for r in mixed["runs"]:
        extra = (f" ({r['reduction_vs_uniform']}x vs uniform int8)"
                 if "reduction_vs_uniform" in r else "")
        print(f"collectives[mixed].{r['mode']}: "
              f"{r['bytes_per_element']} B/elt on the wire{extra}")
    for sec in mesh2d:
        for r in sec["runs"]:
            extra = (f" ({r['reduction_vs_1d']}x vs 1d)"
                     if "reduction_vs_1d" in r else "")
            print(f"collectives[{sec['mesh']}].{r['mode']}: "
                  f"{r['total_bytes_per_element']} B/elt total "
                  f"(incl. {r['tp_replication_bytes']:.0f} B fp32 TP "
                  f"replication), {r['step_ms']} ms/reduce{extra}")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    int8 = next(r for r in rows if r["mode"] == "int8-wire")
    if int8["reduction_vs_fp32"] < 3.0:
        print("FAIL: int8-wire byte reduction below 3x", file=sys.stderr)
        sys.exit(1)
    rmix = next(r for r in mixed["runs"]
                if r["mode"] == "int8-wire-mixed-w4w8")
    if rmix["bytes_per_element"] >= mixed["runs"][0]["bytes_per_element"]:
        print("FAIL: mixed w4/w8 wire B/elt did not drop below the "
              "uniform int8 wire", file=sys.stderr)
        sys.exit(1)
    for sec in mesh2d:
        r2d = next(r for r in sec["runs"] if r["mode"] == "int8-wire-2d")
        if r2d["reduction_vs_1d"] < 1.9:
            print(f"FAIL: int8-wire-2d byte reduction vs 1D below 1.9x "
                  f"on the {sec['mesh']} mesh", file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
