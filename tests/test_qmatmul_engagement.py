"""The packed serving forward feeds every packed weight to ``qmatmul``
as stored.

A 2-layer model at qwen2-0.5b's widths (d_model 896, d_ff 4864; small
vocab) is served under ``examples/specs/serving_kv_plan.json`` (int8
attention, 4-bit MLP, int8 tied head, 4-bit KV).  The engine's decode
and prefill programs are traced, and each packed weight leaf is followed
through the jaxpr to the ``pallas_call`` it feeds.  A weight that passes
a transpose, pad, concatenate, reshape or convert on the way is rebuilt
in HBM on every call; none may.  Each layer holds seven packed
projections, and the tied table feeds the head.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
from jax.extend.core import Jaxpr, Var

from repro.api import RunSpec
from repro.configs import get
from repro.models import model_for
from repro.serving import Engine

RELAYOUT = {"transpose", "pad", "concatenate", "reshape",
            "convert_element_type"}
PLAN = os.path.join(os.path.dirname(__file__), "..", "examples", "specs",
                    "serving_kv_plan.json")
LAYERS, PROJECTIONS = 2, 7


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if isinstance(j, Jaxpr):
                yield j


def weights_into_kernels(closed, weight_invars):
    """(as stored, relaid): how many packed weights reach a
    ``pallas_call`` unchanged, and how many through a :data:`RELAYOUT`
    equation on the way.  A weight used inside a ``scan`` counts once per
    iteration: once per layer."""
    counts = {False: 0, True: 0}

    def walk(jaxpr, taint, times):
        for eqn in jaxpr.eqns:
            ins = [taint.get(v) if isinstance(v, Var) else None
                   for v in eqn.invars]
            if all(t is None for t in ins):
                continue
            name = eqn.primitive.name
            if name == "pallas_call":
                for t in ins:
                    if t is not None:
                        counts[t] += times
                continue
            subs = list(_sub_jaxprs(eqn))
            if subs:
                inner_times = times * eqn.params.get("length", 1)
                for sub in subs:
                    # operands map onto the sub-jaxpr's inputs in order
                    # (a cond's branches skip the predicate), results
                    # onto the equation's
                    off = len(ins) - len(sub.invars)
                    inner = {v: t for v, t in zip(sub.invars, ins[off:])
                             if t is not None} if off >= 0 else {}
                    walk(sub, inner, inner_times)
                    for o, r in zip(eqn.outvars, sub.outvars):
                        t = inner.get(r) if isinstance(r, Var) else None
                        if t is not None:
                            taint[o] = taint.get(o, False) or t
                continue
            # a result at least half a tainted operand's size is that
            # weight rewritten; smaller ones (a gathered row, a matmul's
            # output) are activations and end the trail
            size = max(v.aval.size for v, t in zip(eqn.invars, ins)
                       if t is not None)
            relaid = any(bool(t) for t in ins) or name in RELAYOUT
            for o in eqn.outvars:
                if 2 * getattr(o.aval, "size", 0) >= size:
                    taint[o] = relaid

    walk(closed.jaxpr, {v: False for v in weight_invars}, 1)
    return counts[False], counts[True]


def _packed_invars(closed, tree):
    """The jaxpr inputs that are stored packed weights of ``tree`` (the
    program's first argument)."""
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return [v for p, v in zip(paths, closed.jaxpr.invars)
            if getattr(p[-1], "key", None) in ("w_int8", "w_nib")]


def test_packed_weights_reach_qmatmul_as_stored():
    spec = RunSpec.from_file(PLAN)
    cfg = dataclasses.replace(get("qwen2-0.5b", smoke=False),
                              n_layers=LAYERS, vocab=512)
    model = model_for(cfg)
    p, q = model.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(model, p, q, cfg, batch_slots=4, max_len=64, packed=True,
                 plan=spec.plan, kv_bits=4)
    assert "w_nib" in eng.p["layers"]["mlp"]["down"]["kernel"]
    decode = eng.decode_program()[0]
    prefill = eng._run(eng._prefill.trace, eng.p, eng.q, eng._fresh_slot,
                       jnp.zeros((1, eng.prefill_chunk), jnp.int32),
                       jnp.int32(0)).jaxpr
    want = LAYERS * PROJECTIONS + 1
    for name, closed in (("decode", decode), ("prefill", prefill)):
        stored, relaid = weights_into_kernels(
            closed, _packed_invars(closed, eng.p))
        assert relaid == 0, (
            f"{name}: {relaid} packed weights reach qmatmul through a "
            f"relayout (transpose/pad/concatenate/reshape/convert), "
            f"{stored} as stored")
        assert stored == want, f"{name}: {stored} as stored, want {want}"
