"""repro.api — the declarative RunSpec surface and the RunContext builder.

Covers: exact JSON/CLI round-trips (hypothesis property tests over random
specs), the shipped examples/specs/*.json files, seed threading, the
no-global-leak contract (two contexts with different precision in one
process: neither retraces nor perturbs the other, nothing escapes the
scope), and HLO identity — the spec-built train step lowers to the same
program as the legacy global-state setup (``--spec`` file == classic
flags), on 1x1 here and on the 2x4/4x2 meshes in the multi-device CI job.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import strip_metadata, train_step_hlo
from repro.api import (CompressionSpec, GRAD_COMPRESSION_KINDS, MeshSpec,
                       PrecisionSpec, RunSpec, ServingSpec, build,
                       build_mesh)

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

SPEC_DIR = "examples/specs"


# ----------------------------- round-trips ---------------------------------

def test_default_spec_roundtrip_exact():
    s = RunSpec()
    assert RunSpec.from_json(s.to_json()) == s
    assert RunSpec.from_dict(s.to_dict()) == s


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=10 ** 6),
       st.integers(min_value=1, max_value=16),
       st.integers(min_value=1, max_value=16),
       st.integers(min_value=0, max_value=len(GRAD_COMPRESSION_KINDS) - 1),
       st.integers(min_value=0, max_value=2),
       st.integers(min_value=1, max_value=4096),
       st.floats(min_value=float(np.float32(1e-6)), max_value=1.0,
                 width=32))
def test_spec_json_roundtrip_property(seed, d, m, comp_i, dtype_i, steps,
                                      lr):
    """RunSpec.from_json(spec.to_json()) == spec for random specs — every
    field class exercised: ints, floats (exact via JSON repr), None-able
    strings, nested frozen dataclasses."""
    s = RunSpec(
        arch="qwen2-0.5b", seed=seed,
        mesh=MeshSpec.host(d, m),
        precision=PrecisionSpec(
            compute_dtype=[None, "bfloat16", "float32"][dtype_i],
            packed_serving=bool(seed % 2),
            packed_matmul=[None, True, False][dtype_i]),
        compression=CompressionSpec(kind=GRAD_COMPRESSION_KINDS[comp_i]),
        train=dataclasses.replace(RunSpec().train, steps=steps,
                                  lr=float(lr)),
        data=dataclasses.replace(RunSpec().data, batch=d * 2, seed=seed))
    s2 = RunSpec.from_json(s.to_json())
    assert s2 == s
    # and the JSON itself is stable (canonical key order)
    assert s2.to_json() == s.to_json()


def test_spec_rejects_unknown_fields_and_bad_values():
    with pytest.raises(ValueError, match="unknown RunSpec fields"):
        RunSpec.from_dict({"archh": "x"})
    with pytest.raises(ValueError, match="unknown MeshSpec fields"):
        RunSpec.from_dict({"mesh": {"rows": 2}})
    with pytest.raises(ValueError, match="kind"):
        MeshSpec(kind="ring")
    with pytest.raises(ValueError, match="compute_dtype"):
        PrecisionSpec(compute_dtype="fp8")
    with pytest.raises(ValueError, match="CompressionSpec.kind"):
        CompressionSpec(kind="topk")
    with pytest.raises(ValueError, match="contradicts"):
        CompressionSpec(kind="int8-wire-2d", wire_layout="1d")


def test_cli_flags_equal_spec_file():
    """The acceptance contract: `--spec examples/specs/
    host_2x4_int8wire2d.json` parses to the SAME RunSpec value as the
    classic `--mesh 2x4 --grad-compression int8-wire-2d` flags."""
    from_flags = RunSpec.from_args(
        ["--mesh", "2x4", "--grad-compression", "int8-wire-2d"])
    from_file = RunSpec.from_args(
        ["--spec", f"{SPEC_DIR}/host_2x4_int8wire2d.json"])
    assert from_flags == from_file
    # flags override spec-file fields
    over = RunSpec.from_args(
        ["--spec", f"{SPEC_DIR}/host_2x4_int8wire2d.json",
         "--steps", "7", "--seed", "3"])
    assert over.train.steps == 7 and over.seed == 3
    assert over.data.seed == 3
    assert over.mesh == MeshSpec.host(2, 4)


def test_shipped_specs_roundtrip_exact():
    """Every shipped spec/plan file loads with its loader (plan_*.json
    are bare PrecisionPlans, the rest full RunSpecs), round-trips
    exactly, and re-emits byte-identically (the file IS the canonical
    serialization) — the same contract tools/check_specs.py gates."""
    import glob
    import os
    from repro.core.plan import PrecisionPlan
    paths = sorted(glob.glob(f"{SPEC_DIR}/*.json"))
    assert len(paths) >= 4, paths
    n_plans = 0
    for path in paths:
        loader = (PrecisionPlan
                  if os.path.basename(path).startswith("plan_")
                  else RunSpec)
        n_plans += loader is PrecisionPlan
        obj = loader.from_file(path)
        assert loader.from_json(obj.to_json()) == obj, path
        with open(path) as f:
            assert obj.to_json() == f.read(), path
    assert n_plans >= 1    # the golden mixed w4/w8 plan ships


def test_compression_layout_resolution():
    c = CompressionSpec(kind="int8-wire")
    assert c.resolved_wire_layout(1) == "1d"
    assert c.resolved_wire_layout(4) == "2d"       # auto-upgrade under TP
    assert CompressionSpec(kind="int8-wire-2d").resolved_wire_layout(1) \
        == "2d"
    pinned = CompressionSpec(kind="int8-wire", wire_layout="1d")
    assert pinned.resolved_wire_layout(4) == "1d"
    assert pinned.resolved_residual_layout(4) == "1d"


# ------------------------------- seeding -----------------------------------

def test_seed_threads_into_init_and_data():
    ctx0 = build(RunSpec())
    ctx3 = build(RunSpec.from_args(["--seed", "3"]))
    p0, _ = ctx0.init_state()
    p3, _ = ctx3.init_state()
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p3)))
    b0 = ctx0.make_pipeline()(0)["tokens"]
    b3 = ctx3.make_pipeline()(0)["tokens"]
    assert not np.array_equal(np.asarray(b0), np.asarray(b3))
    # same seed reproduces bit-for-bit
    p0b, _ = build(RunSpec()).init_state()
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p0b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------- no-global-leak --------------------------------

def test_two_contexts_no_retrace_no_perturbation():
    """Two RunContexts with different precision in one process: each
    jitted function traces ONCE under its own flags, repeated calls hit
    the cache (no retrace), outputs stay bit-identical across
    interleaving, and nothing leaks into the ambient defaults."""
    from repro.dist.perf import cast_for_matmul, get_compute_dtype

    ctx_fp = build(RunSpec())
    ctx_bf = build(RunSpec(precision=PrecisionSpec(
        compute_dtype="bfloat16")))
    traces = {"fp": 0, "bf": 0}

    def make(tag):
        def f(x):
            traces[tag] += 1          # runs at trace time only
            return cast_for_matmul(x).astype(jnp.float32) * 3.0
        return f

    j_fp = jax.jit(ctx_fp.wrap(make("fp")))
    j_bf = jax.jit(ctx_bf.wrap(make("bf")))
    x = jnp.asarray([1.0, 1.0 + 2.0 ** -12, -0.3], jnp.float32)
    y_fp1 = j_fp(x)
    y_bf1 = j_bf(x)
    y_fp2 = j_fp(x)
    y_bf2 = j_bf(x)
    assert traces == {"fp": 1, "bf": 1}, traces
    np.testing.assert_array_equal(np.asarray(y_fp1), np.asarray(y_fp2))
    np.testing.assert_array_equal(np.asarray(y_bf1), np.asarray(y_bf2))
    # the bf16 context really cast (1 + 2^-12 rounds away in bf16) — the
    # fp context really didn't; neither saw the other's dtype
    assert float(y_fp1[1]) != float(y_bf1[1])
    # and nothing escaped the scopes
    assert get_compute_dtype() is None


def test_two_contexts_training_isolated():
    """Full train steps from two specs (fp32 vs bf16 compute) interleave
    in one process without retracing or perturbing each other."""
    spec = dataclasses.replace(
        RunSpec(), train=dataclasses.replace(RunSpec().train, steps=3),
        data=dataclasses.replace(RunSpec().data, batch=2, seq=8))
    ctx_a = build(spec)
    ctx_b = build(dataclasses.replace(
        spec, precision=PrecisionSpec(compute_dtype="bfloat16")))
    sa, sb = ctx_a.init_training(), ctx_b.init_training()
    with ctx_a.mesh:
        ma0 = {k: float(v) for k, v in sa.step(0).items()}
    with ctx_b.mesh:
        mb0 = {k: float(v) for k, v in sb.step(0).items()}
    # re-run step 1 then step 0's batch again on a FRESH setup of A: the
    # interleaved A must match the isolated A bit-for-bit
    with ctx_b.mesh:
        sb.step(1)
    sa_iso = build(spec).init_training()
    with ctx_a.mesh:
        ma1 = sa.step(1)
    with build(spec).mesh:
        sa_iso.step(0)
        ma1_iso = sa_iso.step(1)
    for k in ma1:
        assert float(ma1[k]) == float(ma1_iso[k]), k
    # bf16 compute is a genuinely different program
    assert ma0["loss"] != mb0["loss"]


# ------------------------------ HLO identity -------------------------------
# the stripper and the spec-side lowering are the shared
# repro.analysis parsers — the identity the tests pin here is asserted
# over the SAME artifact the program linter (tools/lint_programs.py)
# gates, not a subtly different re-lowering

_strip_metadata = strip_metadata
_spec_step_hlo = train_step_hlo       # argv list -> compiled HLO text
_spec_hlo_from_spec = train_step_hlo  # RunSpec   -> compiled HLO text


def _legacy_step_hlo(mesh_str, grad_compression):
    """The pre-RunSpec launcher wiring: hand-built shardings + the axis
    registry bound directly (what launch.train did before repro.api,
    with the removed ``set_axes`` global swapped for its scoped
    equivalent — same registry value, same trace)."""
    from repro.configs import get
    from repro.data import DataSpec, make_pipeline
    from repro.dist import EFState, collectives, ef_compress, ef_init
    from repro.dist.axes import axis_scope, registry_for_mesh
    from repro.dist.sharding import (batch_sharding, ef_residual_sharding,
                                     replicated, shard_tree)
    from repro.models import model_for
    from repro.optim import adamw_init
    from repro.train import TrainConfig, lm_loss, make_train_step

    cfg = get("qwen2-0.5b", smoke=True)
    M = model_for(cfg)
    d, m = (int(v) for v in mesh_str.split("x"))
    mesh = build_mesh(MeshSpec.host(d, m))
    with axis_scope(registry_for_mesh(mesh)):
        params, qstate = M.init(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params)
        pipe = make_pipeline(DataSpec(kind="lm", batch=4, seq=32,
                                      vocab=cfg.vocab))
        tcfg = TrainConfig(steps=20, lr=1e-3, beta0=1e-9, beta1=1e-7)
        fwd = lambda p, q, b, mode: M.forward(p, q, b, cfg, mode)
        dsize = collectives.data_axis_size(mesh)
        msize = collectives.model_axis_size(mesh)
        wire_kinds = ("int8-wire", "int8-wire-2d")
        wire_layout = ("2d" if (grad_compression == "int8-wire-2d"
                                or msize > 1) else "1d")
        wire = (grad_compression in wire_kinds
                and (dsize > 1 or (wire_layout == "2d" and msize > 1)))
        grad_tx = None
        ef_state = None
        if grad_compression in wire_kinds:
            if wire and wire_layout == "2d":
                ef_state = EFState(residual=collectives.ef_wire2d_init(
                    params, dsize, msize))
            elif wire:
                ef_state = EFState(residual=collectives.ef_wire_init(
                    params, dsize))
            else:
                grad_tx = lambda g, s: ef_compress(g, s, kind="int8")
                ef_state = ef_init(params)
        elif grad_compression != "none":
            grad_tx = lambda g, s: ef_compress(g, s,
                                               kind=grad_compression)
            ef_state = ef_init(params)
        step_fn = make_train_step(
            fwd, lambda out, b: lm_loss(out, b["tokens"]), tcfg,
            grad_tx=grad_tx, reduce="compressed" if wire else "full",
            mesh=mesh if wire else None,
            wire_layout=wire_layout if wire else "auto")
        with mesh:
            in_shardings = (shard_tree(params, mesh, "train"),
                            shard_tree(qstate, mesh, "train"),
                            type(opt)(step=replicated(mesh),
                                      mu=shard_tree(opt.mu, mesh, "train"),
                                      nu=shard_tree(opt.nu, mesh, "train")),
                            {"tokens": batch_sharding(mesh, 4, 2)},
                            replicated(mesh))
            donate = (0, 2)
            # the state placed on its shardings, as init_training does
            args = [*jax.device_put((params, qstate, opt), in_shardings[:3]),
                    pipe(0), jnp.int32(0)]
            if ef_state is not None:
                res_sh = (ef_residual_sharding(
                    ef_state.residual, mesh, layout=wire_layout) if wire
                    else shard_tree(ef_state.residual, mesh, "train"))
                in_shardings += (EFState(residual=res_sh),)
                donate += (5,)
                args.append(jax.device_put(ef_state, in_shardings[5]))
            out_shardings = (in_shardings[:3] + (replicated(mesh),)
                             + in_shardings[5:])
            jitted = jax.jit(step_fn, in_shardings=in_shardings,
                             out_shardings=out_shardings,
                             donate_argnums=donate)
            return jitted.lower(*args).compile().as_text()


def test_hlo_identity_1x1():
    """The spec-built step lowers to the same program as the legacy
    global-state wiring (single device, no compression)."""
    legacy = _legacy_step_hlo("1x1", "none")
    fresh = _spec_step_hlo(["--mesh", "1x1"])
    assert _strip_metadata(fresh) == _strip_metadata(legacy)


def test_hlo_identity_1x1_post_reduce_int8():
    legacy = _legacy_step_hlo("1x1", "int8")
    fresh = _spec_step_hlo(["--mesh", "1x1",
                            "--grad-compression", "int8"])
    assert _strip_metadata(fresh) == _strip_metadata(legacy)


@multidevice
@pytest.mark.parametrize("mesh_str", ["2x4", "4x2"])
def test_hlo_identity_wire2d(mesh_str):
    """The acceptance contract: `--spec examples/specs/
    host_2x4_int8wire2d.json` (and its flag twin on both mesh tests)
    lowers to the same compiled step as the legacy global wiring with
    `--mesh DxM --grad-compression int8-wire-2d`."""
    legacy = _legacy_step_hlo(mesh_str, "int8-wire-2d")
    if mesh_str == "2x4":
        argv = ["--spec", f"{SPEC_DIR}/host_2x4_int8wire2d.json"]
    else:
        argv = ["--mesh", mesh_str,
                "--grad-compression", "int8-wire-2d"]
    fresh = _spec_step_hlo(argv)
    assert _strip_metadata(fresh) == _strip_metadata(legacy)


# --------------------------- precision plans -------------------------------

def test_spec_plan_field_roundtrip():
    """A RunSpec with an embedded PrecisionPlan round-trips exactly, and
    a plan-free spec serializes with ``"plan": null``."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    plan = PrecisionPlan(layers={
        "layers/mlp/up/kernel": LayerPlan(wire_bits=4, pack_bits=4)})
    s = RunSpec(plan=plan)
    s2 = RunSpec.from_json(s.to_json())
    assert s2 == s
    assert s2.plan.entry_for("layers/mlp/up/kernel/w").wire_bits == 4
    import json
    assert json.loads(RunSpec().to_json())["plan"] is None


def test_plan_flag_loads_plan_file():
    """``--plan plan.json`` attaches the width table to the spec; the
    shipped golden mixed plan is the fixture."""
    s = RunSpec.from_args(["--plan", f"{SPEC_DIR}/plan_mixed_w4w8.json"])
    assert s.plan is not None and not s.plan.is_uniform_int8
    assert s.plan.entry_for("layers/mlp/down/kernel").wire_bits == 4
    assert s.plan.entry_for("layers/attn/wq/kernel").wire_bits == 8
    assert s.plan.entry_for("embed/table").wire_bits == 8   # default


def test_uniform_plan_resolves_to_none():
    """build() normalizes both a missing plan and an explicit uniform
    int8 plan to None — consumers take the exact legacy trace."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    assert build(RunSpec()).plan is None
    assert build(RunSpec(plan=PrecisionPlan())).plan is None
    mixed = PrecisionPlan(layers={"x": LayerPlan(wire_bits=4)})
    ctx = build(RunSpec(plan=mixed))
    assert ctx.plan is mixed
    assert ctx.plan_summary() == mixed.summary()
    assert build(RunSpec()).plan_summary() is None


def test_hlo_identity_uniform_plan_1x1():
    """Acceptance contract: a spec carrying the explicit uniform-int8
    plan compiles the byte-identical train step to the plan-free spec."""
    base = _spec_step_hlo(["--mesh", "1x1"])
    import json
    import tempfile
    d = json.loads(RunSpec.from_args(["--mesh", "1x1"]).to_json())
    d["plan"] = {"default": {"wire_bits": 8, "pack_bits": 8,
                             "scale_exp": None}, "layers": {}}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(d, f)
    with_plan = _spec_step_hlo(["--spec", f.name])
    assert _strip_metadata(with_plan) == _strip_metadata(base)


@multidevice
def test_hlo_identity_uniform_plan_wire2d():
    """Same contract on the 2x4 int8-wire-2d mesh: the uniform plan must
    not perturb the compiled wire collective by a single instruction."""
    import dataclasses as dc
    from repro.core.plan import PrecisionPlan
    spec = RunSpec.from_file(f"{SPEC_DIR}/host_2x4_int8wire2d.json")
    base = _spec_hlo_from_spec(spec)
    with_plan = _spec_hlo_from_spec(dc.replace(spec,
                                               plan=PrecisionPlan()))
    assert _strip_metadata(with_plan) == _strip_metadata(base)


# --------------------------- serving contexts ------------------------------

def test_engine_snapshot_isolated_from_later_scopes():
    """An Engine built under one context keeps decoding identically even
    while another context with different precision is active — the
    engine's trace-time snapshot, not ambient state, governs it."""
    spec = RunSpec(arch="qwen2-0.5b", serving=ServingSpec(slots=2))
    ctx = build(spec)
    params, qstate = ctx.init_state()
    eng = ctx.make_engine(params, qstate, max_len=32)
    from repro.serving import Request
    r1 = Request(prompt=[3, 1, 4, 1], max_new=5)
    eng.run([r1])
    ctx_bf = build(dataclasses.replace(
        spec, precision=PrecisionSpec(compute_dtype="bfloat16")))
    with ctx_bf.activate():
        r2 = Request(prompt=[3, 1, 4, 1], max_new=5)
        eng.run([r2])          # traces/caches under the engine snapshot
    assert r1.out == r2.out


# ----------------------------- ServingSpec ---------------------------------

def test_serving_spec_roundtrip_and_validation():
    s = RunSpec(serving=ServingSpec(slots=4, kv_cache="plan",
                                    packed=True, prefix_reuse=True))
    assert RunSpec.from_json(s.to_json()) == s
    with pytest.raises(ValueError, match="kv_cache"):
        ServingSpec(kv_cache="int4")
    with pytest.raises(ValueError, match="slots"):
        ServingSpec(slots=0)
    with pytest.raises(ValueError, match="unknown ServingSpec fields"):
        RunSpec.from_dict({"serving": {"slotss": 2}})
    # CLI flags map onto the spec
    s2 = RunSpec.from_args(["--kv-cache", "int8", "--slots", "3"])
    assert s2.serving.kv_cache == "int8" and s2.serving.slots == 3
    # packed=None follows PrecisionSpec.packed_serving
    assert not ServingSpec().resolved_packed(PrecisionSpec())
    assert ServingSpec().resolved_packed(
        PrecisionSpec(packed_serving=True))
    assert not ServingSpec(packed=False).resolved_packed(
        PrecisionSpec(packed_serving=True))


def test_make_engine_removed_kwargs_rejected():
    """The one-release batch_slots/packed/plan kwarg shims are gone:
    make_engine must reject them with a pointer to the spec field, not
    silently pass them through to Engine."""
    ctx = build(RunSpec(arch="qwen2-0.5b", serving=ServingSpec(slots=4)))
    params, qstate = ctx.init_state()
    for kw, field in (("batch_slots", "serving.slots"),
                      ("packed", "serving.packed"),
                      ("plan", "RunSpec.plan")):
        with pytest.raises(TypeError, match=field.replace(".", r"\.")):
            ctx.make_engine(params, qstate, max_len=32, **{kw: 2})
    eng = ctx.make_engine(params, qstate, max_len=32)
    assert eng.slots == 4      # the spec field governs


def test_kv_cache_fp_hlo_identical_to_legacy_engine():
    """Acceptance contract: a spec with ``kv_cache="fp"`` (the default)
    compiles the byte-identical decode program to the pre-ServingSpec
    Engine construction — quantized-KV support must not perturb the fp
    decode path by a single instruction.  A kv-carrying plan under
    ``kv_cache="fp"`` must not either."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.serving import Engine
    spec = RunSpec(arch="qwen2-0.5b", serving=ServingSpec(slots=2))
    ctx = build(spec)
    params, qstate = ctx.init_state()
    _, fresh = ctx.make_engine(params, qstate, max_len=32).decode_program()
    # the legacy surface: direct Engine kwargs, no serving spec at all
    legacy_eng = Engine(ctx.model, params, qstate, ctx.cfg,
                        batch_slots=2, max_len=32)
    _, legacy = legacy_eng.decode_program()
    assert _strip_metadata(fresh) == _strip_metadata(legacy)
    # a plan carrying narrow KV widths changes nothing while kv_cache=fp
    kv_plan = PrecisionPlan(default=LayerPlan(kv_bits=4))
    ctx2 = build(dataclasses.replace(spec, plan=kv_plan))
    _, fp_planned = ctx2.make_engine(params, qstate,
                                     max_len=32).decode_program()
    assert _strip_metadata(fp_planned) == _strip_metadata(legacy)


def test_kv_cache_plan_resolution():
    """kv_cache mode -> storage width: fp -> None, int8 -> 8, plan ->
    the narrowest kv_bits across entries (uniform wire/pack plans are
    NOT normalized away for KV resolution)."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.serving import resolve_kv_bits
    assert resolve_kv_bits("fp", None) is None
    assert resolve_kv_bits("int8", None) == 8
    assert resolve_kv_bits("plan", None) == 8
    plan = PrecisionPlan(layers={"layers/attn/wk/kernel":
                                 LayerPlan(kv_bits=4)})
    assert resolve_kv_bits("plan", plan) == 4
    # a kv-only plan is wire/pack-uniform: build() normalizes ctx.plan
    # to None, but make_engine still resolves kv widths from the full one
    ctx = build(RunSpec(arch="qwen2-0.5b", plan=plan,
                        serving=ServingSpec(slots=2, kv_cache="plan")))
    assert ctx.plan is None
    params, qstate = ctx.init_state()
    eng = ctx.make_engine(params, qstate, max_len=32)
    assert eng.kv_bits == 4
