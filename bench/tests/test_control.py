"""The correctness checks fail what they must, on the CPU: the control
(the plain reference with float8 matmul operands, the precision below
the bfloat16 operands the configuration states, put in the program's
place) and the faults each cell can have, planted under the timed path
of an otherwise whole run at the SMOKE sizes.  The limits are the cells'
own (``limits`` in each mix)."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import smoke  # noqa: E402

sys.path.insert(0, smoke.BENCH)
from harness import load_module  # noqa: E402

TRAIN, SERVE = "qwen2-0.5b.train", "qwen2-0.5b.serve.decode-heavy"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return smoke.make_checkout(str(tmp_path_factory.mktemp("bench")))


def drive(root, cell, seed):
    h = smoke.harness(root, cell, seed, seconds=1.0)
    drv = load_module(h.driver_path, "bench_driver")
    return drv.run(h)


def failed(res):
    return [name for name, v, lim in res["checks"] if not v <= lim]


def test_train_control_fp8_fails(checkout):
    h = smoke.harness(checkout, TRAIN, 4200000001)
    ctl = load_module(os.path.join(smoke.BENCH, "control.py"), "ctl")
    r = ctl.train_readings(h, h.seed)
    lim = h.mix["limits"]
    assert any(v > lim[k] for k, v in r["control_fp8"].items()
               if k in lim), r


def test_train_state_unchanged_fails(checkout, monkeypatch):
    from repro.api import context

    def frozen(self, step):             # runs the step, keeps the state
        import jax
        import jax.numpy as jnp

        def copy(tree):                 # the step donates what it is given
            return jax.tree.map(jnp.copy, tree)
        return self.jitted(copy(self.params), self.qstate, copy(self.opt),
                           self.pipeline(step), jnp.int32(step))[3]
    monkeypatch.setattr(context.TrainSetup, "step", frozen)
    res = drive(checkout, TRAIN, 4200000002)
    assert failed(res), res["checks"]


def test_train_half_batch_fails(checkout, monkeypatch):
    """The loss over half of the rows, the mean taken over them; the
    forward, the ranges and EBOPs still see the whole batch."""
    from repro.api import context
    lm_loss = context.lm_loss

    def half(logits, tokens):
        b = tokens.shape[0] // 2
        return lm_loss(logits[:b], tokens[:b])
    monkeypatch.setattr(context, "lm_loss", half)
    res = drive(checkout, TRAIN, 4200000003)
    assert failed(res), res["checks"]


def test_train_control_fp8_scaled_reads(checkout):
    """The scaled float8 control drifts from the float32 reference
    without zeroing the gradient: the readings lie strictly between 0
    and the 1 of a state left unchanged."""
    h = smoke.harness(checkout, TRAIN, 4200000006)
    ctl = load_module(os.path.join(smoke.BENCH, "control.py"), "ctl")
    r = ctl.train_readings(h, h.seed)["control_fp8_scaled"]
    assert 0.0 < r["grad_gap"] < 1.0 and 0.0 < r["change_gap"] < 1.0, r


@pytest.mark.parametrize("seed", [4200000004, 4200000014])
def test_serve_control_fp8_fails(checkout, seed):
    """The control reads its own first choice at each position of the
    same prompts and tokens, so it needs no engine.  Two layers of SMOKE
    width do not amplify float8 rounding the way the served model does;
    eight layers at the published widths (vocab cut to 8,192) do."""
    import numpy as np
    import traffic
    h = smoke.harness(checkout, SERVE, seed)
    h.config = dict(h.config, **smoke.PUBLISHED, num_hidden_layers=8,
                    vocab_size=8192)
    h.mix = dict(h.mix, max_len=256)
    ctl = load_module(os.path.join(smoke.BENCH, "control.py"), "ctl")
    serve = load_module(h.driver_path, "bench_driver")
    rng = traffic.seed_rng(seed, 0)
    reqs = [(rng.integers(0, 8192, 128).tolist(),
             rng.integers(0, 8192, 128).tolist())]
    gaps = np.concatenate(serve.reference_gaps(h, reqs, ctl.CONTROL))
    assert float(gaps.mean()) > h.mix["limits"]["mean_gap_std"]


def test_serve_token_altered_fails(checkout, monkeypatch):
    from repro.serving import engine as engine_mod
    step = engine_mod.Engine.step
    ticks = {"n": 0}

    def altered(self):                  # one sampled token comes out wrong
        step(self)
        ticks["n"] += 1
        if ticks["n"] % 7 == 0:
            for r in self.slot_req:
                if r is not None and r.out:
                    r.out[-1] = (r.out[-1] + 1) % self.cfg.vocab
                    break
    monkeypatch.setattr(engine_mod.Engine, "step", altered)
    res = drive(checkout, SERVE, 4200000005)
    assert failed(res), res["checks"]
