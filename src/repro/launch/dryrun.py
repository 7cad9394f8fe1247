"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract roofline terms.  No arrays are materialized —
params/state are ShapeDtypeStructs, the compile proves the sharding config
is coherent and the memory/cost analysis feeds EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod]
"""
# The 512 placeholder devices MUST be requested before any jax init:
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_BASE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..api import MeshSpec, PrecisionSpec, RunSpec, build
from ..configs import ARCHS, SHAPES, ShapeSpec, get
from ..core import hgq
from ..dist.sharding import (batch_sharding, cache_sharding, replicated,
                             shard_tree)
from ..models import (GriffinCaches, ModelConfig, RWKVCaches,
                      WhisperCaches, model_for)
from ..nn.attention import KVCache
from ..train import TrainConfig, lm_loss, make_train_step
from .roofline import mfu

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocated)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Abstract model inputs for one cell (weak-type-correct, shardable)."""
    B = shape.global_batch
    S = shape.seq_len if shape.kind != "decode" else 1
    f32 = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    specs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, cfg.d_model), f32)
    if cfg.family == "audio" and shape.kind != "decode":
        specs["frame_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.d_model), f32)
    return specs


def abstract_model_state(M, cfg: ModelConfig):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: M.init(k, cfg),
                          jax.eval_shape(jax.random.PRNGKey, 0))


def abstract_cache(M, cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: M.init_cache(cfg, batch, max_len))


def cache_shardings(caches, mesh, cfg: ModelConfig):
    """Family-aware cache sharding (DESIGN.md SS5)."""
    if isinstance(caches, KVCache):
        sh = cache_sharding(mesh, caches.k.shape, batch_axis=1, seq_axis=2)
        return KVCache(sh, sh)
    if isinstance(caches, RWKVCaches):
        shift = cache_sharding(mesh, caches.shift_a.shape, batch_axis=1,
                               head_axis=2)
        wkv = cache_sharding(mesh, caches.wkv.shape, batch_axis=1,
                             head_axis=2)
        return RWKVCaches(shift, shift, wkv)
    if isinstance(caches, GriffinCaches):
        conv = cache_sharding(mesh, caches.conv.shape, batch_axis=1,
                              head_axis=3)
        h = cache_sharding(mesh, caches.h.shape, batch_axis=1, head_axis=2)
        kv = cache_sharding(mesh, caches.k.shape, batch_axis=1, seq_axis=2)
        return GriffinCaches(conv, h, kv, kv)
    if isinstance(caches, WhisperCaches):
        s = cache_sharding(mesh, caches.self_k.shape, batch_axis=1,
                           seq_axis=2)
        c = cache_sharding(mesh, caches.cross_k.shape, batch_axis=1,
                           head_axis=4)
        return WhisperCaches(s, s, c, c, replicated(mesh))
    raise TypeError(type(caches))


# --------------------------------------------------------------------------
# cell builders
# --------------------------------------------------------------------------

def cell_spec(arch: str, shape_name: str, multi_pod: bool,
              variant: str, plan=None) -> RunSpec:
    """The declarative config of one dry-run cell — the same RunSpec
    surface the training launcher parses, so a dry-run cell and a real
    run describe their mesh/precision identically.  ``plan`` embeds a
    learned :class:`core.plan.PrecisionPlan` (per-layer wire/pack
    widths); the cell then reports them under ``plan_widths``."""
    return RunSpec(
        arch=arch, full=True, plan=plan,
        mesh=MeshSpec.production(multi_pod=multi_pod),
        precision=PrecisionSpec(
            # bf16 compute-cast everywhere: fp32-master FSDP gathers and
            # the TP partial-sum all-reduces run on bf16 values
            compute_dtype="bfloat16" if variant == "opt" else None,
            packed_serving=(variant == "opt"
                            and SHAPES[shape_name].kind == "decode"),
            # the compile-only dry-run keeps packed weights on the
            # XLA-fused dequant path (no Pallas kernel in the lowering)
            packed_matmul=False))


def build_cell(arch: str, shape_name: str, multi_pod: bool = False,
               variant: str = "base", plan=None) -> Dict[str, Any]:
    """variant='opt' enables the beyond-paper knobs (dist.perf):
    train -> bf16 compute-cast (halves FSDP gather volume);
    decode -> HGQ-packed int8 weights + int8 KV cache."""
    shape = SHAPES[shape_name]
    # applicability check BEFORE building the context: a skipped cell
    # must not pay the 256/512-device mesh construction
    if shape_name == "long_500k" and not get(arch).sub_quadratic:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full quadratic attention at 524288 tokens "
                          "(see DESIGN.md SS4 Arch-applicability)"}
    ctx = build(cell_spec(arch, shape_name, multi_pod, variant, plan))
    cfg = ctx.cfg
    if shape.kind != "train":
        cfg = dataclasses.replace(cfg, dtype="bfloat16", remat=False)
    M = model_for(cfg)
    mesh = ctx.mesh
    chips = mesh.devices.size
    params_abs, qstate_abs = abstract_model_state(M, cfg)
    if ctx.spec.precision.packed_serving:
        from ..dist.perf import pack_params_for_serving
        params_abs = jax.eval_shape(pack_params_for_serving, params_abs)
    batch_abs = input_specs(cfg, shape)
    mode = "train" if shape.kind == "train" else "serve"
    params_sh = shard_tree(params_abs, mesh, mode)
    qstate_sh = shard_tree(qstate_abs, mesh, mode)
    batch_sh = {k: batch_sharding(mesh, v.shape[0], len(v.shape))
                for k, v in batch_abs.items()}
    t0 = time.time()

    if shape.kind == "train":
        from ..optim import adamw_init
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        opt_sh = type(opt_abs)(step=replicated(mesh),
                               mu=shard_tree(opt_abs.mu, mesh, "train"),
                               nu=shard_tree(opt_abs.nu, mesh, "train"))
        fwd = lambda p, q, b, mode: M.forward(p, q, b, cfg, mode)
        step_fn = ctx.wrap(make_train_step(
            fwd, lambda out, b: lm_loss(out, b["tokens"]),
            TrainConfig(steps=1000)))
        jitted = jax.jit(step_fn,
                         in_shardings=(params_sh, qstate_sh, opt_sh,
                                       batch_sh, replicated(mesh)))
        lowered = jitted.lower(params_abs, qstate_abs, opt_abs,
                               batch_abs,
                               jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    elif shape.kind == "prefill":
        @ctx.wrap
        def prefill(p, q, b):
            logits, _, _ = M.forward(p, q, b, cfg, mode=hgq.EVAL)
            return logits
        jitted = jax.jit(prefill, in_shardings=(params_sh, qstate_sh,
                                                batch_sh))
        lowered = jitted.lower(params_abs, qstate_abs, batch_abs)
        compiled = lowered.compile()
    else:  # decode
        max_len = shape.seq_len
        if variant == "opt" and cfg.family not in ("ssm",):
            caches_abs = jax.eval_shape(
                lambda: M.init_cache(cfg, shape.global_batch, max_len,
                                     dtype=jnp.int8))
        else:
            caches_abs = abstract_cache(M, cfg, shape.global_batch, max_len)
        caches_sh = cache_shardings(caches_abs, mesh, cfg)

        @ctx.wrap
        def serve_step(p, q, c, tokens, pos):
            return M.decode_step(p, q, c, tokens, pos, cfg)

        # per-slot position vector [B]: the continuous-batching ragged
        # decode step (serving/engine.py) — every slot at its own offset
        jitted = jax.jit(serve_step,
                         in_shardings=(params_sh, qstate_sh, caches_sh,
                                       batch_sh["tokens"],
                                       replicated(mesh)))
        lowered = jitted.lower(params_abs, qstate_abs, caches_abs,
                               batch_abs["tokens"],
                               jax.ShapeDtypeStruct(
                                   (shape.global_batch,), jnp.int32))
        compiled = lowered.compile()

    compile_s = time.time() - t0
    hlo = compiled.as_text()
    from .analytic import analytic_flops_total, hbm_bytes_per_chip
    from .roofline import RooflineTerms, parse_collective_bytes, \
        parse_dot_flops
    flops_dev = parse_dot_flops(hlo)           # trip-count-scaled, per device
    coll = parse_collective_bytes(hlo)
    opt_decode = variant == "opt" and shape.kind == "decode"
    mem_model = hbm_bytes_per_chip(
        cfg, shape, chips, weight_bits=8.0 if opt_decode else 16.0,
        cache_bytes=1.0 if opt_decode else 2.0)
    terms = RooflineTerms(flops=flops_dev,
                          hbm_bytes=mem_model["total"],
                          coll_bytes=sum(coll.values()),
                          coll_breakdown=coll, chips=chips)
    # raw cost_analysis for reference (known loop-body undercount)
    raw = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        raw = {"flops": float(ca.get("flops", 0.0)),
               "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:
        pass
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)

    # useful-model-FLOPs ratio
    n_act = cfg.n_active_params()
    tokens_processed = shape.global_batch * (shape.seq_len
                                             if shape.kind != "decode" else 1)
    flops_factor = 6.0 if shape.kind == "train" else 2.0
    model_flops = flops_factor * n_act * tokens_processed
    hlo_total = terms.flops * chips
    result = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok", "compile_s": round(compile_s, 1),
        "kind": shape.kind,
        **terms.as_dict(),
        "hbm_model_breakdown": mem_model,
        "analytic_flops_total": analytic_flops_total(cfg, shape),
        "raw_cost_analysis": raw,
        "memory_analysis": mem,
        "model_flops_total": model_flops,
        "useful_flops_ratio": (model_flops / hlo_total) if hlo_total else 0.0,
        "roofline_fraction": mfu(model_flops, terms),
        # per-layer wire/pack widths of the cell's precision plan
        # (None == uniform int8, the plan-free default)
        "plan_widths": ctx.plan_summary(),
    }
    return result


def run_cells(archs, shapes, multi_pod: bool, out_dir: str,
              variant: str = "base") -> None:
    os.makedirs(out_dir, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            vtag = "" if variant == "base" else f"_{variant}"
            tag = f"{arch}_{shape}_{'2x16x16' if multi_pod else '16x16'}"                 + vtag
            path = os.path.join(out_dir, tag + ".json")
            if os.path.exists(path):
                print(f"[skip existing] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                res = build_cell(arch, shape, multi_pod, variant=variant)
            except Exception as e:
                res = {"arch": arch, "shape": shape, "status": "FAILED",
                       "mesh": "2x16x16" if multi_pod else "16x16",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            with open(path, "w") as f:
                json.dump(res, f, indent=1, default=str)
            stat = res["status"]
            extra = ""
            if stat == "ok":
                extra = (f" bottleneck={res['bottleneck']}"
                         f" t=({res['t_compute_s']:.2e},"
                         f"{res['t_memory_s']:.2e},"
                         f"{res['t_collective_s']:.2e})s"
                         f" compile={res['compile_s']}s")
            print(f"[dryrun] {tag}: {stat}{extra}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    args = ap.parse_args()
    archs = ARCHS if args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        run_cells(archs, shapes, mp, args.out, variant=args.variant)


if __name__ == "__main__":
    main()
