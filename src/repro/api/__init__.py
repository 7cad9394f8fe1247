"""``repro.api`` — the declarative run-configuration surface.

One frozen, JSON-serializable :class:`RunSpec` (mesh + precision +
compression + train/data config + seed) replaces the old trace-time
globals (``dist.axes.set_axes`` / ``dist.perf.set_compute_dtype``,
both since removed) and
the per-launcher argparse/setup blocks; :func:`build` turns a spec into
a :class:`RunContext` that constructs the mesh, axis registry,
shardings, train step, and serving engine from the spec alone, with no
module-level mutable state.

    from repro.api import RunSpec, build
    spec = RunSpec.from_file("examples/specs/host_2x4_int8wire2d.json")
    ctx = build(spec)
    setup = ctx.init_training()
    metrics = setup.step(0)
"""
from ..core.plan import LayerPlan, PrecisionPlan  # noqa: F401
from .spec import (AudioSpec, CompressionSpec,  # noqa: F401
                   GRAD_COMPRESSION_KINDS, KV_CACHE_MODES, MeshSpec,
                   PrecisionSpec, RunSpec, SERVING_WORKLOADS, ServingSpec,
                   emit_pareto_specs)
from .context import (GradCompression, RunContext,  # noqa: F401
                      TrainSetup, build, build_mesh)
