"""Spec -> compiled program artifacts: the linter's unit of analysis.

For each ``RunSpec`` the repo ships (``examples/specs/*.json``) two
programs matter:

* the **train step** — ``repro.api.build(spec).init_training()``'s
  jitted function, exactly as the launcher runs it (shardings, donation,
  wire collective and all); and
* the **serving decode step** — the ``serving.Engine``'s ragged decode
  tick, built on a 1x1 mesh with the spec's packing flags.

``artifacts_for_spec`` traces both (where the mesh fits the host) and
captures the jaxpr plus the compiled HLO text; the declarative rules in
``analysis.rules`` and the census in ``analysis.report`` run over these
:class:`ProgramArtifacts` — never over re-derived, subtly-different
lowerings.  ``tests/test_api.py`` shares :func:`train_traced` /
:func:`train_step_hlo` for its HLO-identity assertions, so the program
the tests pin and the program the linter gates are the same object.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..api import RunSpec, build
from .hlo import input_output_aliases, parse_collectives
from .jaxpr import explicit_collectives


@dataclasses.dataclass
class ProgramArtifacts:
    """One compiled program plus everything the rules need to judge it."""
    # "train:host_1x1", "decode:serving_packed" — colon, not brackets:
    # these names feed fnmatch override patterns, where [..] is a class
    name: str
    kind: str                     # "train" | "decode"
    spec: RunSpec
    spec_path: str                # "" when built from an in-memory spec
    mesh_shape: Tuple[int, int]   # (data, model)
    jaxpr: Any                    # ClosedJaxpr of the traced program
    hlo: str                      # compiled StableHLO/HLO text
    meta: Dict[str, Any]          # kind-specific facts (see builders)

    # cached derived views -------------------------------------------------
    def explicit_collectives(self):
        return explicit_collectives(self.jaxpr)

    def hlo_collectives(self):
        return parse_collectives(self.hlo)

    def aliased_buffers(self) -> int:
        return len(input_output_aliases(self.hlo))


def _spec_name(spec_path: str, spec: RunSpec) -> str:
    import os
    if spec_path:
        return os.path.splitext(os.path.basename(spec_path))[0]
    return f"{spec.arch}_{spec.mesh.data}x{spec.mesh.model}"


def train_traced(spec: RunSpec):
    """(ctx, setup, traced) for the spec's jitted train step — the one
    ``init_training`` builds, traced on its own representative args."""
    ctx = build(spec)
    setup = ctx.init_training()
    args = [setup.params, setup.qstate, setup.opt,
            setup.pipeline(0), jnp.int32(0)]
    if setup.ef_state is not None:
        args.append(setup.ef_state)
    traced = setup.jitted.trace(*args)
    return ctx, setup, traced


def train_step_hlo(spec_or_argv) -> str:
    """Compiled HLO text of the spec-built train step.  Accepts a
    ``RunSpec`` or a CLI argv list (``["--mesh", "2x4", ...]``) — the
    helper ``tests/test_api.py`` builds its HLO-identity pins on."""
    spec = (spec_or_argv if isinstance(spec_or_argv, RunSpec)
            else RunSpec.from_args(list(spec_or_argv)))
    _, _, traced = train_traced(spec)
    return traced.lower().compile().as_text()


def train_artifacts(spec: RunSpec, spec_path: str = "") -> ProgramArtifacts:
    ctx, setup, traced = train_traced(spec)
    comp = ctx.grad_compression()
    n_leaves = len(jax.tree.leaves(setup.params))
    donated = 2 * n_leaves + len(jax.tree.leaves(setup.opt.mu)) \
        + len(jax.tree.leaves(setup.opt.nu)) - n_leaves
    # donated buffers that must come back aliased: params + opt.mu/nu
    # (all round-trip the step with unchanged shapes); the EF residual
    # rides on top when compression is on
    if setup.ef_state is not None:
        donated += len(jax.tree.leaves(setup.ef_state.residual))
    return ProgramArtifacts(
        name=f"train:{_spec_name(spec_path, spec)}",
        kind="train", spec=spec, spec_path=spec_path,
        mesh_shape=(ctx.n_data, ctx.n_model),
        jaxpr=traced.jaxpr,
        hlo=traced.lower().compile().as_text(),
        meta={
            "wire": comp.wire,
            "wire_layout": comp.wire_layout,
            "compression": spec.compression.kind,
            "wire_payload": spec.compression.wire_kind,
            "donated_leaves": donated,
            "param_leaves": n_leaves,
        })


def decode_artifacts(spec: RunSpec, spec_path: str = "") -> ProgramArtifacts:
    """The serving decode-step program for a (1x1-mesh) spec: the
    Engine's jitted ragged tick with the spec's serving/packing flags.
    The census engine is built small (2 slots, 32-token cache) but
    otherwise exactly as ``make_engine`` would serve the spec."""
    spec = dataclasses.replace(
        spec, serving=dataclasses.replace(spec.serving, slots=2))
    ctx = build(spec)
    params, qstate = ctx.init_state()
    unpacked_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    eng = ctx.make_engine(params, qstate, max_len=32)
    jaxpr, hlo = eng.decode_program()
    return ProgramArtifacts(
        name=f"decode:{_spec_name(spec_path, spec)}",
        kind="decode", spec=spec, spec_path=spec_path,
        mesh_shape=(1, 1), jaxpr=jaxpr, hlo=hlo,
        meta={
            "packed": bool(eng.packed),
            "unpacked_param_bytes": int(unpacked_bytes),
            "kv_cache": spec.serving.kv_cache,
            "kv_bits": eng.kv_bits,
            # quantized cache trees are all-int8; what the quantized-kv
            # rule requires the entry layout to store as integer bytes
            "kv_cache_int_bytes": (0 if eng.kv_bits is None else sum(
                a.size for a in jax.tree.leaves(eng.caches)
                if a.dtype == jnp.int8)),
        })


def artifacts_for_spec(spec: RunSpec, spec_path: str = "",
                       kinds: Optional[Tuple[str, ...]] = None
                       ) -> List[ProgramArtifacts]:
    """Every analyzable program of one spec.  The train step needs the
    spec's full mesh; the decode engine is a single-replica object, so it
    is built only for 1x1-mesh specs (a sharded-serving spec would need
    its own engine-per-replica story first)."""
    need = spec.mesh.device_count
    if need > jax.device_count():
        raise RuntimeError(
            f"spec {spec_path or spec.arch} needs {need} devices, host "
            f"has {jax.device_count()} (force more with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}, or let "
            f"tools/lint_programs.py --devices do it)")
    out = []
    if kinds is None or "train" in kinds:
        out.append(train_artifacts(spec, spec_path))
    if (kinds is None or "decode" in kinds) and need == 1:
        out.append(decode_artifacts(spec, spec_path))
    return out
