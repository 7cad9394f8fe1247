"""chip_smoke.py's fp serving check, run on the CPU at the smoke config:
it passes on the real engine and fails on one that decodes a position
late."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.api import ServingSpec, build  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402


@pytest.fixture(scope="module")
def fp_state():
    chip_smoke.FULL = False
    try:
        ctx = build(chip_smoke._spec(
            0, serving=ServingSpec(slots=chip_smoke.SLOTS)))
        yield (ctx, *ctx.init_state())
    finally:
        chip_smoke.FULL = True


@pytest.mark.parametrize("plant", [None, "late-position"])
def test_check_fp_holds_engine_to_reference(fp_state, plant, monkeypatch):
    ctx, params, qstate = fp_state
    if plant:
        build_jits = engine_mod.Engine._build

        def late_build(self):
            build_jits(self)
            decode = self._decode
            self._decode = lambda p, q, c, tok, pos, *a: decode(
                p, q, c, tok, pos + 1, *a)
        monkeypatch.setattr(engine_mod.Engine, "_build", late_build)
        with pytest.raises(RuntimeError, match="off the reference"):
            chip_smoke._check_fp(ctx, params, qstate)
    else:
        chip_smoke._check_fp(ctx, params, qstate)
