"""Unit tests for the repro.dist subsystem itself (axes / sharding / perf /
error-feedback compression) — the sharding *rule* tests against fake meshes
live in test_recurrent_sharding.py; this file covers the rest of the
contract."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.api import MeshSpec, build_mesh
from repro.dist import EFState, ef_compress, ef_init
from repro.dist.axes import (AxisRegistry, axis_scope, constrain,
                             get_model_size)
from repro.dist.perf import (cast_for_matmul, compute_dtype_scope,
                             get_compute_dtype, pack_params_for_serving,
                             unpack_weight)
from repro.dist.sharding import spec_for_param, shard_tree, stacked_tree


class _FakeMesh:
    axis_names = ("data", "model")
    devices = types.SimpleNamespace(shape=(16, 16))


# ------------------------------- axes --------------------------------------

def test_constrain_identity_on_single_device():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 2, 16))
    for pat in ("b.m.", "b...", "....", ".bm."[:4]):
        y = constrain(x, pat)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    # and it is jit-traceable as an identity
    y = jax.jit(lambda v: constrain(v, "b.m."))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_constrain_pattern_validation():
    x = jnp.zeros((2, 3))
    with pytest.raises(ValueError):
        constrain(x, "b.m")        # rank mismatch
    with pytest.raises(ValueError):
        constrain(x, "bx")         # unknown axis char


def test_axes_scope_roundtrip():
    """axis_scope binds the registry for the dynamic extent only — and
    nests (inner scope wins, outer restored)."""
    assert get_model_size() == 1
    with axis_scope(AxisRegistry(("pod", "data"), "model", 32, 16)):
        assert get_model_size() == 16
        with axis_scope(AxisRegistry(("data",), "model", 2, 4)):
            assert get_model_size() == 4
        assert get_model_size() == 16
    assert get_model_size() == 1


# ----------------------------- sharding ------------------------------------

class K:
    def __init__(self, key):
        self.key = key


def _spec(path, shape, mode="train"):
    return spec_for_param([K(k) for k in path], shape, _FakeMesh(), mode)


def test_spec_low_rank_replicates():
    assert _spec(("bias", "w"), (4864,)) == P(None)
    assert _spec(("out_f",), ()) == P()


def test_spec_square_tie_prefers_last_axis():
    assert _spec(("kernel", "w"), (1024, 1024)) == P("data", "model")


def test_spec_per_channel_f_leaf():
    # (1, N) fractional-bit tensors: broadcast axis replicates, N -> model
    assert _spec(("kernel", "f"), (1, 4864)) == P(None, "model")


def test_spec_serve_mode_non_divisible():
    assert _spec(("kernel", "w"), (7, 13), mode="serve") == P(None, None)


def test_spec_bad_mode_raises():
    with pytest.raises(ValueError):
        _spec(("kernel", "w"), (8, 8), mode="decode")


def test_spec_from_real_tree_paths():
    """spec_for_param must understand tree_flatten_with_path key types
    (DictKey etc.), not just the fake .key records."""
    tree = {"kernel": {"w": jax.ShapeDtypeStruct((896, 4864), jnp.float32),
                       "f": jax.ShapeDtypeStruct((1, 4864), jnp.float32)},
            "bias": {"w": jax.ShapeDtypeStruct((4864,), jnp.float32)},
            "step": jax.ShapeDtypeStruct((), jnp.int32)}
    flat = dict(jax.tree_util.tree_flatten_with_path(tree)[0])
    specs = {tuple(str(getattr(k, "key", k)) for k in path):
             spec_for_param(path, leaf.shape, _FakeMesh(), "train")
             for path, leaf in flat.items()}
    assert specs[("kernel", "w")] == P("data", "model")
    assert specs[("kernel", "f")] == P(None, "model")
    assert specs[("bias", "w")] == P(None)
    assert specs[("step",)] == P()


def test_shard_tree_on_real_mesh():
    """On the 1x1 host mesh everything replicates (axis size 1 never
    shards) but the NamedSharding tree must build and jit-apply."""
    from jax.sharding import NamedSharding
    mesh = build_mesh(MeshSpec.host(1, 1))
    tree = {"kernel": {"w": jnp.zeros((8, 16)), "f": jnp.zeros((1, 16))}}
    sh = shard_tree(tree, mesh, "train")
    assert all(isinstance(s, NamedSharding)
               for s in jax.tree.leaves(sh))
    assert sh["kernel"]["w"].spec == P(None, None)
    with mesh:
        out = jax.jit(lambda t: t, in_shardings=(sh,))(tree)
    assert out["kernel"]["w"].shape == (8, 16)


# ------------------------------- perf --------------------------------------

def test_compute_dtype_cast():
    assert get_compute_dtype() is None
    x = jnp.ones((3, 3), jnp.float32)
    ids = jnp.ones((3,), jnp.int32)
    assert cast_for_matmul(x).dtype == jnp.float32
    with compute_dtype_scope(jnp.bfloat16):
        assert cast_for_matmul(x).dtype == jnp.bfloat16
        assert cast_for_matmul(ids).dtype == jnp.int32  # ints untouched
    assert cast_for_matmul(x).dtype == jnp.float32


def test_pack_unpack_roundtrip_on_grid():
    """Weights already on the 2^-f grid survive packing exactly."""
    key = jax.random.PRNGKey(1)
    f = 6.0
    # keep |w| < 127 * 2^-f so the int8 mantissa never saturates
    w = jnp.round(jnp.clip(jax.random.normal(key, (32, 16)) * 0.5,
                           -1.9, 1.9) * 2.0 ** f) / 2.0 ** f
    p = {"kernel": {"w": w, "f": jnp.full((32, 16), f)},
         "bias": {"w": jnp.zeros((16,))}}
    packed = pack_params_for_serving(p)
    assert packed["kernel"]["w_int8"].dtype == jnp.int8
    assert "w" in packed["bias"], "biases must not be packed"
    got = unpack_weight(packed["kernel"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(w), atol=1e-7)


def test_pack_never_saturates_large_weights():
    """Per-parameter f can put >8 bits in one column (regression: the
    column-max grid clipped w=2.0 at f=[2,9] to 127 * 2^-9 = 0.248 — an
    8x silent error on the *large* weight).  The exponent must cap so big
    weights stay exact and only sub-grid small ones floor."""
    w = jnp.array([[2.0], [0.001953125]])          # 2^1 and 2^-9
    f = jnp.array([[2.0], [9.0]])
    packed = pack_params_for_serving({"k": {"w": w, "f": f}})["k"]
    got = unpack_weight(packed)
    step = float(packed["scale"].max())
    assert abs(float(got[0, 0]) - 2.0) <= step / 2, float(got[0, 0])
    assert abs(float(got[1, 0])) <= step            # floored, not exploded
    # homogeneous f with int bits beyond 8 total: w=3.0 at f=6 needs 192
    w2 = jnp.array([[3.0], [-3.0]])
    p2 = pack_params_for_serving({"k": {"w": w2, "f": jnp.full((2, 1), 6.0)}})
    got2 = unpack_weight(p2["k"])
    np.testing.assert_allclose(np.asarray(got2), np.asarray(w2),
                               atol=float(p2["k"]["scale"].max()) / 2)


def test_pack_skips_conv_kernels():
    p = {"kernel": {"w": jnp.zeros((3, 3, 4, 8)), "f": jnp.zeros(())}}
    packed = pack_params_for_serving(p)
    assert "w" in packed["kernel"] and "w_int8" not in packed["kernel"]


def test_pack_is_eval_shape_traceable():
    abs_p = {"kernel": {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                        "f": jax.ShapeDtypeStruct((1, 4), jnp.float32)}}
    out = jax.eval_shape(pack_params_for_serving, abs_p)
    assert out["kernel"]["w_int8"].shape == (8, 4)
    assert out["kernel"]["w_int8"].dtype == jnp.int8


def test_packed_weights_flow_through_get_qw():
    from repro.nn.common import get_qw
    from repro.core import hgq
    w = jnp.round(jax.random.normal(jax.random.PRNGKey(2), (16, 8)) * 4) / 4
    p = {"kernel": {"w": w, "f": jnp.full((16, 8), 2.0)}}
    qt_ref = get_qw(p["kernel"], hgq.EVAL)
    qt_packed = get_qw(pack_params_for_serving(p)["kernel"], hgq.EVAL)
    np.testing.assert_allclose(np.asarray(qt_packed.q), np.asarray(qt_ref.q),
                               atol=1e-6)


# ----------------------- plan-width serving packing ------------------------

def _grid_params(shape=(32, 16), f=4.0):
    """A matmul weight already on the 2^-f grid, small enough that no
    width's channel cap saturates."""
    key = jax.random.PRNGKey(5)
    w = jnp.round(jnp.clip(jax.random.normal(key, shape) * 0.2, -0.9, 0.9)
                  * 2.0 ** f) / 2.0 ** f
    return {"kernel": {"w": w, "f": jnp.full(shape, f)}}


def test_pack_with_plan_nibble_storage_and_roundtrip():
    """A w4 plan layer stores two mantissas per byte along K; the accessor
    recovers full-width int4-range mantissas and dequant stays within half
    a step of the original weights."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.dist.perf import is_packed, packed_mantissas
    p = _grid_params()
    plan = PrecisionPlan(layers={"kernel": LayerPlan(wire_bits=4,
                                                     pack_bits=4)})
    packed = pack_params_for_serving(p, plan)["kernel"]
    assert "w_nib" in packed and "w_int8" not in packed
    assert packed["w_nib"].shape == (16, 16)       # K halves
    assert is_packed(packed)
    m = packed_mantissas(packed)
    assert m.shape == (32, 16)
    assert int(jnp.max(jnp.abs(m))) <= 7
    got = unpack_weight(packed)
    err = np.abs(np.asarray(got) - np.asarray(p["kernel"]["w"]))
    step = np.asarray(packed["scale"]).reshape(1, -1)
    assert (err <= step / 2 + 1e-7).all()


def test_packed_nbytes_nibble_halves_mantissa_bytes():
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.serving.packed import packed_nbytes
    p = _grid_params()
    plan4 = PrecisionPlan(layers={"kernel": LayerPlan(wire_bits=4,
                                                      pack_bits=4)})
    p8 = pack_params_for_serving(p)
    p4 = pack_params_for_serving(p, plan4)
    assert p4["kernel"]["w_nib"].nbytes \
        == p8["kernel"]["w_int8"].nbytes // 2
    # scales and f pass through identically, so the tree totals differ
    # by exactly the halved mantissa payload
    assert packed_nbytes(p8) - packed_nbytes(p4) \
        == p8["kernel"]["w_int8"].nbytes // 2


def test_pack_plan_odd_k_falls_back_to_int8_storage():
    """Odd-K layers keep int8 storage (no pad metadata on disk) but still
    quantize on the narrow grid the plan asked for."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    from repro.dist.perf import packed_mantissas
    p = _grid_params(shape=(7, 4))
    plan = PrecisionPlan(layers={"kernel": LayerPlan(wire_bits=4,
                                                     pack_bits=4)})
    packed = pack_params_for_serving(p, plan)["kernel"]
    assert "w_int8" in packed and "w_nib" not in packed
    assert int(jnp.max(jnp.abs(packed["w_int8"]))) <= 7
    np.testing.assert_array_equal(np.asarray(packed_mantissas(packed)),
                                  np.asarray(packed["w_int8"]))


def test_plan_widths_address_tree_paths():
    """Plan keys are the /-joined tree paths iter_packable yields: a
    d0/kernel entry packs only that layer, siblings stay uniform int8."""
    from repro.core.plan import LayerPlan, PrecisionPlan
    params = {"d0": _grid_params(), "d1": _grid_params()}
    plan = PrecisionPlan(layers={"d0/kernel": LayerPlan(wire_bits=4,
                                                        pack_bits=4)})
    packed = pack_params_for_serving(params, plan)
    assert "w_nib" in packed["d0"]["kernel"]
    assert "w_int8" in packed["d1"]["kernel"]


def test_pack_with_plan_is_eval_shape_traceable():
    from repro.core.plan import LayerPlan, PrecisionPlan
    abs_p = {"kernel": {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                        "f": jax.ShapeDtypeStruct((1, 4), jnp.float32)}}
    plan = PrecisionPlan(layers={"kernel": LayerPlan(wire_bits=4,
                                                     pack_bits=4)})
    out = jax.eval_shape(lambda t: pack_params_for_serving(t, plan), abs_p)
    assert out["kernel"]["w_nib"].shape == (4, 4)
    assert out["kernel"]["w_nib"].dtype == jnp.int8


# --------------------------- error feedback --------------------------------

def test_ef_unsupported_kind_raises():
    grads = {"w": jnp.ones((4,))}
    st = ef_init(grads)
    with pytest.raises(ValueError, match="topk"):
        ef_compress(grads, st, kind="topk")
    with pytest.raises(ValueError):
        ef_compress(grads, st, kind="fp4")


def test_ef_none_is_passthrough():
    grads = {"w": jnp.linspace(-1.0, 1.0, 7)}
    st = ef_init(grads)
    sent, st2 = ef_compress(grads, st, kind="none")
    np.testing.assert_array_equal(np.asarray(sent["w"]),
                                  np.asarray(grads["w"]))
    assert float(jnp.max(jnp.abs(st2.residual["w"]))) == 0.0


def test_ef_bf16_residual_bounded():
    grads = {"w": jnp.linspace(-1e-3, 1e-3, 101)}
    st = ef_init(grads)
    for _ in range(20):
        sent, st = ef_compress(grads, st, kind="bf16")
        # bf16 has ~8 mantissa bits: residual < 2^-8 * max|e|
        assert float(jnp.max(jnp.abs(st.residual["w"]))) < 1e-5


def test_ef_int8_stacked_leaf_per_layer_grid():
    """Regression: a stacked [L, ...] leaf used ONE per-tensor int8 grid,
    so a single outlier layer crushed quantization resolution for all L
    layers.  The grid must be per leading (layer) axis: each layer's
    max-abs error stays within one step of its OWN grid.  Stackedness is
    marked by the tree path (the scan'd ``layers`` container here)."""
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(key, (4, 8, 6)) * 1e-3
    g = g.at[2].mul(1e4)                     # layer 2 is a 10-scale outlier
    grads = {"layers": {"w": g}}
    sent, st = ef_compress(grads, ef_init(grads), kind="int8")
    err = np.abs(np.asarray(sent["layers"]["w"] - g))
    for layer in range(4):
        own_grid = float(jnp.max(jnp.abs(g[layer]))) / 127.0
        assert err[layer].max() <= own_grid, (
            f"layer {layer}: err {err[layer].max():.2e} > grid {own_grid:.2e}")
    # the old per-tensor grid floored every non-outlier layer to zero with
    # error ~= the full value; per-layer grids keep them finite-resolution
    assert err[0].max() < float(jnp.max(jnp.abs(g[0]))) / 64
    # rank <= 2 leaves keep the per-tensor grid
    flat = {"w": jnp.linspace(-1.0, 1.0, 33).reshape(3, 11)}
    s2, _ = ef_compress(flat, ef_init(flat), kind="int8")
    m = np.asarray(s2["w"]) * 127.0
    np.testing.assert_allclose(m, np.round(m), atol=1e-4)


def test_ef_int8_genuine_3d_weight_one_grid():
    """Regression (rank-sniffing bug): a genuinely 3-D weight — e.g. a
    per-head attention tensor NOT under a stacked-layer container — must
    get ONE per-tensor grid, not a silent per-slice grid along axis 0.
    Every sent value lies on the single global max|e|/127 grid."""
    key = jax.random.PRNGKey(4)
    g = jax.random.normal(key, (4, 8, 6))      # [heads, d, d] — one tensor
    g = g.at[2].mul(100.0)                     # head 2 dominates the amax
    grads = {"attn_heads": {"w": g}}
    assert jax.tree.leaves(stacked_tree(grads)) == [False]
    sent, _ = ef_compress(grads, ef_init(grads), kind="int8")
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    m = np.asarray(sent["attn_heads"]["w"]) / scale
    # on one global grid every mantissa is an integer; per-slice grids
    # (the old rank>=3 sniff) would put slices 0/1/3 on much finer grids
    np.testing.assert_allclose(m, np.round(m), atol=1e-3)
    # explicit override: the same tree CAN be marked stacked by metadata
    sent2, _ = ef_compress(grads, ef_init(grads), kind="int8",
                           stacked={"attn_heads": {"w": True}})
    err2 = np.abs(np.asarray(sent2["attn_heads"]["w"] - g))
    own_grid = float(jnp.max(jnp.abs(g[0]))) / 127.0
    assert err2[0].max() <= own_grid


def test_stacked_tree_path_rule():
    """stacked_tree marks exactly the leaves under stacked containers
    (scan'd layer stacks, MoE expert stacks) — param metadata, not rank."""
    tree = {"layers": {"attn": {"wq": {"kernel": {"w": jnp.zeros((2, 4, 4))}}}},
            "units": {"mlp": {"w": jnp.zeros((1, 4, 8))}},
            "head": {"kernel": {"w": jnp.zeros((4, 4))}},
            "attn_heads": {"w": jnp.zeros((4, 4, 4))}}
    marks = stacked_tree(tree)
    assert marks["layers"]["attn"]["wq"]["kernel"]["w"] is True
    assert marks["units"]["mlp"]["w"] is True
    assert marks["head"]["kernel"]["w"] is False
    assert marks["attn_heads"]["w"] is False


def test_ef_state_is_jit_compatible():
    grads = {"w": jnp.linspace(-1.0, 1.0, 33)}
    step = jax.jit(lambda g, s: ef_compress(g, s, kind="int8"))
    sent, st = step(grads, ef_init(grads))
    assert isinstance(st, EFState)
    # sent values lie on the int8 grid of max|e|
    scale = float(jnp.max(jnp.abs(grads["w"]))) / 127.0
    m = np.asarray(sent["w"]) / scale
    np.testing.assert_allclose(m, np.round(m), atol=1e-4)
