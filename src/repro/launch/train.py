"""Pod-scale LM training launcher: pjit'd train step under the production
mesh with the full sharding rules.

The launcher is a thin shell over ``repro.api``: CLI flags (or a
``--spec run.json`` file — see ``examples/specs/``) parse into one
declarative :class:`repro.api.RunSpec`, and :func:`repro.api.build`
constructs the mesh, axis registry, shardings, compressed train step,
and checkpoint/resume flow from the spec alone — the exact config that
ran is reprintable as JSON, and no module-level globals are touched.

On this CPU container it runs the smoke config on a 1x1 mesh; on
hardware, ``--multi-pod`` builds the (2, 16, 16) mesh and the same code
paths shard per repro.dist.sharding (exactly what launch/dryrun.py
proves compiles).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 20 --batch 4 --seq 32
    PYTHONPATH=src python -m repro.launch.train \
        --spec examples/specs/host_2x4_int8wire2d.json
"""
from __future__ import annotations

import time

from ..api import RunSpec, build
from .cache import enable_compile_cache


def main() -> None:
    spec = RunSpec.from_args()
    enable_compile_cache()
    ctx = build(spec)
    comp = ctx.grad_compression()
    if (spec.compression.kind == "int8-wire" and comp.wire
            and comp.wire_layout == "2d"):
        print(f"mesh has model axis of size {ctx.n_model}: upgrading "
              f"int8-wire to the 2D-sliced exchange (int8-wire-2d)")
    setup = ctx.init_training()
    tcfg = spec.train
    if tcfg.ckpt_dir and setup.maybe_resume():
        print(f"resumed from step {setup.start_step}")
    start = setup.start_step
    t0 = time.time()
    for step in range(start, tcfg.steps):
        m = setup.step(step)
        if step % max(tcfg.steps // 10, 1) == 0:
            print(f"step {step}: loss={float(m['loss']):.4f} "
                  f"ebops={float(m['ebops']):.3g}")
        if tcfg.ckpt_dir and step and step % tcfg.ckpt_every == 0:
            # label = steps applied = next step to run; labelling
            # with `step` would replay an already-applied batch
            setup.checkpoint(step + 1)
    print(f"done: {tcfg.steps - start} steps in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
