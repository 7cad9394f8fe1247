"""repro.analysis — the precision-flow program linter.

Covers: the HLO parsers (collective lines incl. tuple/async results,
brace + iota replica groups, input-output aliases), the jaxpr walker
(explicit collectives with logical axis names through shard_map), the
program rules firing on injected violations (an fp32 wire payload, a
dropped donation, a missing exchange), the direction-aware report diff,
and — on 8 devices — the real 2x4 wire-2d program: exactly the explicit
launches the wire wrote, all of them int8 at gradient size, plus the
row-major mesh-layout assumption ``crosses_data_axis`` is built on.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import (SCALAR_MAX, Collective, ExplicitCollective,
                            ProgramArtifacts, Violation)
from repro.analysis.rules import run_rules
from repro.api import MeshSpec, RunSpec, build, build_mesh

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ------------------------------ HLO parsers --------------------------------

def test_parse_collectives_basic_line():
    hlo = ('  %all-reduce.1 = f32[64,128]{1,0} all-reduce(%x), '
           'replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add\n')
    (c,) = analysis.parse_collectives(hlo)
    assert c.kind == "all-reduce" and c.dtype == "f32"
    assert c.dims == (64, 128) and c.numel == 64 * 128
    assert c.groups == ((0, 4), (1, 5), (2, 6), (3, 7))


def test_parse_collectives_tuple_and_async():
    hlo = "\n".join([
        "%a2a = (s8[1,8478]{1,0}, s8[1,8478]{1,0}) all-to-all(%p, %q), "
        "replica_groups={{0,1}}, dimensions={0}",
        "%ag = s8[2,512]{1,0} all-gather-start(%g), replica_groups=[2,4]<=[8]",
        "%f = f32[8]{0} fusion(%all-reduce.169), kind=kLoop",  # operand ref
        # TPU tiled layouts nest parentheses inside the result type
        "%all-gather.4 = s8[1024,512]{1,0:T(8,128)(4,1)S(1)} all-gather("
        "%b), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}",
    ])
    cs = analysis.parse_collectives(hlo)
    assert [(c.kind, c.dtype) for c in cs] == [
        ("all-to-all", "s8"), ("all-gather", "s8"), ("all-gather", "s8")]
    assert cs[2].dims == (1024, 512)
    assert cs[2].groups == ((0, 1), (2, 3))
    # iota without transpose: [2,4]<=[8] -> rows of consecutive ids
    assert cs[1].groups == ((0, 1, 2, 3), (4, 5, 6, 7))


def test_replica_groups_iota_transposed():
    # [4,2]<=[2,4]T(1,0): iota reshaped (2,4), transposed, re-read 4x2 —
    # columns of the row-major 2x4 mesh, i.e. groups that CROSS data
    groups = analysis.parse_replica_groups(
        "replica_groups=[4,2]<=[2,4]T(1,0)")
    assert groups == [[0, 4], [1, 5], [2, 6], [3, 7]]
    c = Collective(kind="all-reduce", dtype="f32", dims=(512,),
                   groups=tuple(tuple(g) for g in groups), line="")
    assert c.crosses_data_axis(model_size=4)
    # rows of the same mesh stay inside one data shard
    rows = Collective(kind="all-gather", dtype="s8", dims=(512,),
                      groups=((0, 1, 2, 3), (4, 5, 6, 7)), line="")
    assert not rows.crosses_data_axis(model_size=4)
    # unknown grouping reads as crossing (conservative)
    unk = Collective(kind="all-reduce", dtype="f32", dims=(512,),
                     groups=None, line="")
    assert unk.crosses_data_axis(model_size=4)


def test_collective_permute_pairs_as_groups():
    groups = analysis.parse_replica_groups(
        "source_target_pairs={{0,4},{4,0}}")
    assert groups == [[0, 4], [4, 0]]


def test_strip_metadata_removes_location_noise():
    a = 'op(%x), metadata={op_name="f/alpha" source_file="a.py"}, calls=%c'
    b = 'op(%x), metadata={op_name="g/beta" source_file="b.py"}, calls=%c'
    assert analysis.strip_metadata(a) == analysis.strip_metadata(b)
    assert "alpha" not in analysis.strip_metadata(a)
    # the stack-frame tables compiled modules open with
    tables = ('HloModule m\n\nFileNames\n1 "a.py"\n\nFileLocations\n'
              '1 {file_name_id=1 line=3}\n\nStackFrames\n'
              '1 {file_location_id=1 parent_frame_id=1}\n\nENTRY %e {}\n')
    assert analysis.strip_metadata(tables) == "HloModule m\n\n\n\n\nENTRY %e {}\n"


def test_input_output_aliases_nested_braces():
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{3}: (7, {}, may-alias) }, entry_computation_layout={()->()}")
    assert analysis.input_output_aliases(hlo) == [(0, 0), (3, 7)]
    assert analysis.input_output_aliases("HloModule bare") == []


# ------------------------------ jaxpr walker -------------------------------

@multidevice
def test_explicit_collectives_through_shard_map():
    """The walker finds a psum written inside a shard_map body, with the
    logical axis name attached (a size-1 axis would be elided at trace
    time, hence the real 2x4 mesh)."""
    from jax.sharding import PartitionSpec as P
    mesh = build_mesh(MeshSpec.host(2, 4))

    def f(x):
        return jax.lax.psum(x, "data")

    sm = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    traced = jax.jit(sm).trace(jnp.zeros((8, 4), jnp.float32))
    (c,) = analysis.explicit_collectives(traced.jaxpr)
    assert c.primitive == "psum" and c.axes == ("data",)
    assert c.dtype == "float32" and c.numel == 4 * 4
    assert c.over("data") and not c.over("model")


# ----------------------- rules on injected violations ----------------------

def _fake_art(explicit=(), hlo="HloModule m", kind="train", mesh=(2, 4),
              meta=None):
    """A ProgramArtifacts with hand-planted collectives — the injection
    point for violation tests (subclassing keeps the rule code on the
    exact production path)."""
    class Injected(ProgramArtifacts):
        def explicit_collectives(self):
            return list(explicit)
    return Injected(
        name="train:injected", kind=kind, spec=RunSpec(),
        spec_path="", mesh_shape=mesh, jaxpr=None, hlo=hlo,
        meta={"wire": True, "wire_payload": "int8",
              "donated_leaves": 0, **(meta or {})})


def _ec(primitive, axes, dtype, dims):
    return ExplicitCollective(primitive=primitive, axes=axes, dtype=dtype,
                              dims=dims)


def test_fp32_wire_payload_is_a_violation():
    """The acceptance-criterion injection: force an fp32 wire path —
    a gradient-sized f32 collective over data must trip wire-dtype."""
    art = _fake_art(explicit=[
        _ec("all_to_all", ("data",), "float32", (2, 8478)),
        _ec("pmax", ("data", "model"), "float32", (49,)),   # scalar: fine
    ])
    names = [v.rule for v in run_rules(art)]
    assert "wire-dtype" in names
    # and the clean int8 version of the same program passes
    ok = _fake_art(explicit=[
        _ec("all_to_all", ("data",), "int8", (2, 8478)),
        _ec("pmax", ("data", "model"), "float32", (49,)),
    ])
    assert [v.rule for v in run_rules(ok)] == []


def test_missing_wire_exchange_is_a_violation():
    art = _fake_art(explicit=[_ec("pmax", ("data", "model"),
                                  "float32", (49,))])
    assert "wire-present" in [v.rule for v in run_rules(art)]


def test_dropped_donation_is_a_violation():
    art = _fake_art(
        explicit=[_ec("all_to_all", ("data",), "int8", (2, 8478))],
        meta={"donated_leaves": 10})   # hlo has no alias header -> 0
    assert "donation" in [v.rule for v in run_rules(art)]


def test_f64_leak_is_a_violation():
    art = _fake_art(
        explicit=[_ec("all_to_all", ("data",), "int8", (2, 8478))],
        hlo="HloModule m\n %x = f64[3]{0} convert(%y)\n")
    assert "no-f64" in [v.rule for v in run_rules(art)]


def test_violation_str_names_rule_and_program():
    v = Violation(rule="wire-dtype", program="train:x", message="boom")
    assert "wire-dtype" in str(v) and "train:x" in str(v)


# --------------------------- report + baseline diff ------------------------

def _report_with(launches, aliased=5, crossing=None):
    return {"report": "programs", "programs": {"train:x": {
        "kind": "train", "spec": "s.json", "mesh": [2, 4],
        "launches": launches, "explicit": {"all_to_all[data]": launches},
        "collectives": {"all-reduce.f32": 3},
        "crossing": crossing or {}, "aliased_buffers": aliased,
        "violations": []}}}


def test_compare_extra_launch_fails():
    base, fresh = _report_with(3), _report_with(4)
    failures, _ = analysis.compare(base, fresh)
    assert any("launches" in f for f in failures)
    # the good direction (fewer launches) is a note, not a failure
    failures, notes = analysis.compare(_report_with(4), _report_with(3))
    assert not failures and any("launches" in n for n in notes)


def test_compare_lost_alias_fails_but_gain_passes():
    failures, _ = analysis.compare(_report_with(3, aliased=5),
                                   _report_with(3, aliased=4))
    assert any("aliased_buffers" in f for f in failures)
    failures, _ = analysis.compare(_report_with(3, aliased=5),
                                   _report_with(3, aliased=9))
    assert not failures


def test_compare_override_widens_tolerance():
    base, fresh = _report_with(3), _report_with(4)
    failures, _ = analysis.compare(
        base, fresh, overrides=[("train:x.*", 0.5)])
    assert not failures
    # last match wins, same as check_regression.py
    failures, _ = analysis.compare(
        base, fresh, overrides=[("train:x.*", 0.5), ("*launches", 0.0)])
    assert any("launches" in f for f in failures)


def test_compare_new_and_missing_metrics_are_notes():
    base, fresh = _report_with(3), _report_with(3)
    fresh["programs"]["train:x"]["crossing"] = {"all-to-all.s8": 1}
    failures, notes = analysis.compare(base, fresh)
    assert not failures and any("new metric" in n for n in notes)


def test_report_json_is_deterministic():
    r = _report_with(3)
    assert analysis.dumps(r) == analysis.dumps(json.loads(analysis.dumps(r)))


# --------------------------- real programs ---------------------------------

def test_host_1x1_programs_clean():
    """The shipped single-host spec builds, lints clean, and donates:
    train params/opt round-trip aliased, the decode cache too."""
    spec = RunSpec.from_json(open("examples/specs/host_1x1.json").read())
    arts = analysis.artifacts_for_spec(spec, "examples/specs/host_1x1.json")
    assert [a.kind for a in arts] == ["train", "decode"]
    for a in arts:
        rep = analysis.program_report(a)
        assert rep["violations"] == [], rep["violations"]
    train, decode = arts
    assert train.aliased_buffers() >= train.meta["donated_leaves"] > 0
    assert decode.aliased_buffers() > 0


@multidevice
def test_wire2d_program_census_and_rules():
    """The real 2x4 int8-wire-2d program: the explicit collectives are
    exactly the wire's launches (scale pmax + payload all_to_all + the
    two all_gathers), every gradient-sized one int8 — and the census
    the ROADMAP's fold-pmax work must move is visible in the report."""
    spec = RunSpec.from_json(
        open("examples/specs/host_2x4_int8wire2d.json").read())
    art = analysis.train_artifacts(spec, "specs/host_2x4_int8wire2d.json")
    rep = analysis.program_report(art)
    assert rep["violations"] == [], rep["violations"]
    assert rep["explicit"] == {"all_gather[data]": 1,
                               "all_gather[model]": 1,
                               "all_to_all[data]": 1,
                               "pmax[data,model]": 1}
    assert rep["launches"] == 4
    for c in art.explicit_collectives():
        if c.numel >= SCALAR_MAX:
            assert c.dtype in ("int8", "uint8"), dataclasses.asdict(c)


@multidevice
def test_mesh_layout_is_row_major():
    """crosses_data_axis assumes jax.make_mesh((D, M)) lays device ids
    out row-major (id = d*M + m) — pin that, since every grouping
    classification in the linter rests on it."""
    mesh = build_mesh(MeshSpec.host(2, 4))
    ids = [[d.id for d in row] for row in mesh.devices]
    assert ids == [[0, 1, 2, 3], [4, 5, 6, 7]]


@multidevice
def test_wire2d_hlo_census_matches_committed_baseline():
    """The committed golden PROGRAMS.json stays truthful for the 2x4
    program on these exact package versions: explicit-launch metrics are
    deterministic; if THIS test fails after an intentional program
    change, re-baseline with `tools/lint_programs.py --devices 8
    --update`."""
    base = json.load(open("benchmarks/baselines/PROGRAMS.json"))
    prog = base["programs"]["train:host_2x4_int8wire2d"]
    spec = RunSpec.from_json(
        open("examples/specs/host_2x4_int8wire2d.json").read())
    art = analysis.train_artifacts(spec)
    rep = analysis.program_report(art)
    assert rep["launches"] == prog["launches"]
    assert rep["explicit"] == prog["explicit"]
