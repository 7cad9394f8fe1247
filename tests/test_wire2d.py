"""dist.collectives 2D (data x model) sliced wire collective.

Single-device tests drive the collective-free reference
(``simulate_wire_pmean_2d``) plus the slice-layout/bytes/EF-property
contracts — including the hypothesis property that 1D and 2D deliver
identical time-averaged mean gradients on random shapes/meshes.  The
``@multidevice`` tests (CI job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) assert the real
``shard_map`` path matches the reference bit-for-bit on 2x4 AND 4x2
meshes, that a pure-TP 1xM mesh takes the sliced path with no data-axis
exchange, that the compressed-2d train step tracks the post-reduce loss
curve with s8-only gradient collectives, and that checkpoint resume of
the sliced residual is exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import SCALAR_MAX, parse_collectives
from repro.api import MeshSpec, build_mesh
from repro.dist import EFState, ef_init, ef_compress
from repro.dist.collectives import (data_axis_size, ef_wire2d_init,
                                    ef_wire_init, ef_wire_pmean_2d,
                                    model_axis_size, record_wire_bytes,
                                    simulate_wire_pmean,
                                    simulate_wire_pmean_2d,
                                    tp_replication_bytes, wire2d_leaf_bytes,
                                    wire2d_slice_len, wire_bytes_model)
from repro.dist.sharding import ef_residual_sharding, model_axis_for

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _stacked(key, n=2):
    """A per-shard tree with a model-shardable matrix, a stacked [L, ...]
    leaf (under the ``layers`` container, which marks it stacked by
    path), a flat (model-replicated) vector, and a scalar."""
    ks = jax.random.split(key, 4)
    return {"w": jax.random.normal(ks[0], (n, 6, 8)),
            "layers": jax.random.normal(ks[1], (n, 3, 8, 6)),
            "vec": jax.random.normal(ks[2], (n, 17)),
            "scalar": jax.random.normal(ks[3], (n,))}


def _init_res(tree, D, M):
    return ef_wire2d_init({k: v[0] for k, v in tree.items()}, D, M)


# ----------------------------- slice layout ---------------------------------

def test_model_axis_rule_matches_param_placement():
    assert model_axis_for((6, 8), 4) == 1       # larger trailing axis
    assert model_axis_for((16, 8), 4) == 0
    assert model_axis_for((3, 8, 6), 2) == 1    # leading L stays stacked
    assert model_axis_for((6, 9), 4) is None    # not divisible
    assert model_axis_for((17,), 4) is None     # rank < 2
    assert model_axis_for((6, 8), 1) is None


def test_wire2d_slice_len_padding():
    # model-shardable: block of 48/4=12, padded to D=2 chunks -> 12
    assert wire2d_slice_len((6, 8), 2, 4) == 12
    # flat: ceil(17/4)=5, padded to D=2 -> 6
    assert wire2d_slice_len((17,), 2, 4) == 6
    # scalar: one element, one slice
    assert wire2d_slice_len((), 2, 4) == 2


def test_wire2d_init_shapes():
    tree = _stacked(jax.random.PRNGKey(0), 2)
    res = _init_res(tree, 2, 4)
    for k, leaf in res.items():
        assert leaf.shape[:2] == (2, 4), k
        assert leaf.shape[2] == wire2d_slice_len(tree[k].shape[1:], 2, 4), k
        assert not np.asarray(leaf).any()


# ------------------------- reference semantics ------------------------------

@pytest.mark.parametrize("D,M", [(2, 4), (4, 2), (1, 8)])
def test_simulate_2d_delivers_near_mean(D, M):
    tree = _stacked(jax.random.PRNGKey(0), D)
    delivered, residual = simulate_wire_pmean_2d(tree, _init_res(tree, D, M),
                                                 M, "int8")
    for k in tree:
        true = np.mean(np.asarray(tree[k]), axis=0)
        grid = np.max(np.abs(np.asarray(tree[k]))) / 127 * 2
        np.testing.assert_allclose(np.asarray(delivered[k]), true,
                                   atol=4 * grid)
        assert residual[k].shape == (D, M,
                                     wire2d_slice_len(tree[k].shape[1:],
                                                      D, M))


def test_simulate_2d_stacked_leaf_per_layer_grids():
    """The per-layer grid survives the model slicing: an outlier layer in
    a stacked [L, ...] leaf must not crush the other layers.  The leaf is
    marked stacked explicitly (the metadata override; a ``layers`` path
    would derive the same)."""
    e = jnp.ones((2, 3, 8, 6)) * 1e-3
    e = e.at[:, 1].mul(1e4)
    delivered, _ = simulate_wire_pmean_2d(
        {"w": e}, ef_wire2d_init({"w": e[0]}, 2, 2), 2, "int8",
        stacked={"w": True})
    err = np.abs(np.asarray(delivered["w"]) - np.mean(np.asarray(e), axis=0))
    for layer in range(3):
        own_grid = float(np.max(np.abs(np.asarray(e[:, layer])))) / 127
        assert err[layer].max() <= 2.5 * own_grid, layer
    assert err[0].max() < 1e-4


def test_simulate_2d_unmarked_3d_leaf_single_grid():
    """Regression (rank-sniffing bug): a rank-3 leaf NOT under a stacked
    container gets ONE quantization grid in the wire path too — the
    delivered mean of a uniform-magnitude tensor with one dominant slice
    lands on the single global grid."""
    e = jnp.ones((2, 3, 8, 6)) * 1e-3
    e = e.at[:, 1].mul(1e4)
    delivered, _ = simulate_wire_pmean_2d(
        {"w": e}, ef_wire2d_init({"w": e[0]}, 2, 2), 2, "int8")
    # one global grid (step ~ amax/127 ~ 0.08): the 1e-3 slices floor to
    # exactly 0 on the first step (their EF residual recovers them over
    # time); the old per-slice grids delivered them at fine resolution
    # immediately, which is the bug for a genuine 3-D tensor
    got = np.asarray(delivered["w"])
    assert np.all(got[0] == 0.0) and np.all(got[2] == 0.0), got
    step = float(np.max(np.abs(np.asarray(e)))) / 127.0
    np.testing.assert_allclose(got[1], 10.0, atol=2 * step)


def test_simulate_2d_bad_kind_raises():
    with pytest.raises(ValueError, match="int8"):
        simulate_wire_pmean_2d({"w": jnp.zeros((2, 4))},
                               {"w": jnp.zeros((2, 2, 2))}, 2, "fp4")


# ------------------------ error-feedback property ---------------------------

def test_ef2d_time_average_unbiased():
    """Over K steps of a constant gradient, the 2D path's time-averaged
    delivered gradient telescopes to the true mean on BOTH axes (the
    phase-1/phase-2 errors stay within each (d, m) slice)."""
    K, D, M = 14, 2, 4
    tree = _stacked(jax.random.PRNGKey(3), D)
    res = _init_res(tree, D, M)
    acc = {k: jnp.zeros(v.shape[1:]) for k, v in tree.items()}
    for _ in range(K):
        d, res = simulate_wire_pmean_2d(tree, res, M, "int8")
        acc = {k: acc[k] + d[k] for k in acc}
    for k in tree:
        true = np.mean(np.asarray(tree[k]), axis=0)
        grid = max(float(np.max(np.abs(np.asarray(tree[k])))), 1e-30) \
            / 127 * 2
        np.testing.assert_allclose(np.asarray(acc[k]) / K, true,
                                   atol=grid + 1e-7)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=4),
       st.lists(st.floats(min_value=-1.0, max_value=1.0), min_size=4,
                max_size=24),
       st.integers(min_value=2, max_value=13))
def test_property_1d_2d_same_time_averaged_mean(D, M, vals, rows):
    """On random shapes and DxM meshes, the 1D wire and the 2D sliced
    wire deliver IDENTICAL time-averaged mean gradients — both telescope
    to the true mean within one grid step."""
    K = 10
    base = jnp.asarray(vals, jnp.float32)
    # a [D, rows, len(vals)] matrix leaf: model-shardable iff divisible
    fac = (0.5 + jnp.arange(D, dtype=jnp.float32))[:, None, None]
    gs = fac * jnp.broadcast_to(base, (rows, base.shape[0]))[None]
    tree = {"w": gs}
    true = np.mean(np.asarray(gs), axis=0)
    grid = max(float(jnp.max(jnp.abs(gs))), 1e-30) / 127.0 * 2

    res1 = ef_wire_init({"w": true}, D)
    acc1 = jnp.zeros_like(gs[0])
    for _ in range(K):
        d, res1 = simulate_wire_pmean({"w": gs + res1["w"]}, "int8")
        acc1 = acc1 + d["w"]

    res2 = ef_wire2d_init({"w": gs[0]}, D, M)
    acc2 = jnp.zeros_like(gs[0])
    for _ in range(K):
        d, res2 = simulate_wire_pmean_2d(tree, res2, M, "int8")
        acc2 = acc2 + d["w"]

    tol = grid + 1e-7
    np.testing.assert_allclose(np.asarray(acc1) / K, true, atol=tol)
    np.testing.assert_allclose(np.asarray(acc2) / K, true, atol=tol)
    np.testing.assert_allclose(np.asarray(acc2) / K, np.asarray(acc1) / K,
                               atol=2 * tol)


# ----------------------------- mixed widths ---------------------------------

def test_simulate_2d_mixed_widths():
    """Per-leaf widths through the 2D sliced path: the w4 leaf lands
    within its int4 grid of the true mean, the w8 leaves stay
    bit-identical to the widths-free trace."""
    D, M = 2, 4
    tree = _stacked(jax.random.PRNGKey(8), D)
    widths = {"w": 4, "layers": 4, "vec": 8, "scalar": 8}
    d, r = simulate_wire_pmean_2d(tree, _init_res(tree, D, M), M, "int8",
                                  widths=widths)
    d8, r8 = simulate_wire_pmean_2d(tree, _init_res(tree, D, M), M,
                                    "int8")
    for k in ("w", "layers"):
        true = np.mean(np.asarray(tree[k]), axis=0)
        grid4 = np.max(np.abs(np.asarray(tree[k]))) / 7 * 2
        np.testing.assert_allclose(np.asarray(d[k]), true, atol=4 * grid4)
        assert not np.array_equal(np.asarray(d[k]), np.asarray(d8[k]))
    for k in ("vec", "scalar"):
        np.testing.assert_array_equal(np.asarray(d[k]), np.asarray(d8[k]))
        np.testing.assert_array_equal(np.asarray(r[k]), np.asarray(r8[k]))


def test_ef2d_mixed_time_average_unbiased():
    """EF still telescopes to the true mean when leaves ride different
    widths — the w4 leaf just converges on its coarser grid."""
    K, D, M = 14, 2, 4
    tree = _stacked(jax.random.PRNGKey(9), D)
    widths = {"w": 4, "layers": 8, "vec": 4, "scalar": 8}
    res = _init_res(tree, D, M)
    acc = {k: jnp.zeros(v.shape[1:]) for k, v in tree.items()}
    for _ in range(K):
        d, res = simulate_wire_pmean_2d(tree, res, M, "int8",
                                        widths=widths)
        acc = {k: acc[k] + d[k] for k in acc}
    for k in tree:
        true = np.mean(np.asarray(tree[k]), axis=0)
        qmax = 7.0 if widths[k] <= 4 else 127.0
        grid = max(float(np.max(np.abs(np.asarray(tree[k])))), 1e-30) \
            / qmax * 2
        np.testing.assert_allclose(np.asarray(acc[k]) / K, true,
                                   atol=grid + 1e-7)


# ------------------------------ byte model ----------------------------------

def test_wire2d_bytes_beat_1d_with_tp_replication():
    """The acceptance ratio, analytically: on 2x4 and 4x2 meshes the 2D
    sliced exchange must cut total per-device wire bytes >= 1.9x vs the
    1D path (whose model-replicated shard_map costs an fp32 model-axis
    all_gather per model-sharded gradient leaf on top of its data-axis
    int8 phases)."""
    shape = (512, 1024)
    elems = 512 * 1024
    for (D, M) in [(2, 4), (4, 2)]:
        b2d = wire2d_leaf_bytes(shape, D, M, "int8")
        b1d = (wire_bytes_model(elems, D, "int8", 1)
               + tp_replication_bytes(shape, M))
        assert b1d / b2d >= 1.9, (D, M, b1d, b2d)
    # no model axis -> no replication cost and no model gather
    assert tp_replication_bytes(shape, 1) == 0.0
    assert tp_replication_bytes((17,), 8) == 0.0


# --------------------------- multi-device path ------------------------------

@multidevice
@pytest.mark.parametrize("D,M", [(2, 4), (4, 2)])
def test_wire2d_shard_map_matches_simulate(D, M):
    mesh = build_mesh(MeshSpec.host(D, M))
    assert data_axis_size(mesh) == D and model_axis_size(mesh) == M
    tree = _stacked(jax.random.PRNGKey(1), D)
    res = _init_res(tree, D, M)
    with mesh:
        res_p = jax.device_put(res, ef_residual_sharding(res, mesh, "2d"))
        for kind in ("int8", "bf16"):
            d, r = jax.jit(lambda t, rr, k=kind: ef_wire_pmean_2d(
                t, rr, mesh, k))(tree, res_p)
            ds, rs = simulate_wire_pmean_2d(tree, res, M, kind)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(d[k]),
                                              np.asarray(ds[k]))
                np.testing.assert_array_equal(np.asarray(r[k]),
                                              np.asarray(rs[k]))


@multidevice
@pytest.mark.parametrize("D,M", [(2, 4), (4, 2)])
def test_wire2d_shard_map_matches_simulate_mixed_widths(D, M):
    """The acceptance contract for mixed widths: the real 2D shard_map
    collective is bit-for-bit equal to its simulator when leaves ride
    different wire widths."""
    mesh = build_mesh(MeshSpec.host(D, M))
    tree = _stacked(jax.random.PRNGKey(10), D)
    widths = {"w": 4, "layers": 4, "vec": 8, "scalar": 8}
    res = _init_res(tree, D, M)
    with mesh:
        res_p = jax.device_put(res, ef_residual_sharding(res, mesh, "2d"))
        d, r = jax.jit(lambda t, rr: ef_wire_pmean_2d(
            t, rr, mesh, "int8", widths=widths))(tree, res_p)
    ds, rs = simulate_wire_pmean_2d(tree, res, M, "int8", widths=widths)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(d[k]), np.asarray(ds[k]))
        np.testing.assert_array_equal(np.asarray(r[k]), np.asarray(rs[k]))


@multidevice
@pytest.mark.parametrize("kind,bits", [("int8", 8), ("int8", 4),
                                       ("bf16", 8)])
def test_wire2d_leaf_bytes_pins_measured_trace(kind, bits):
    """wire2d_leaf_bytes == the recorder's measured per-leaf trace bytes
    at the leaf's ACTUAL wire width — for int8 at w8, nibble-packed w4,
    and bf16 (the satellite contract: the byte model may not drift from
    the traced collectives)."""
    D, M = 2, 4
    mesh = build_mesh(MeshSpec.host(D, M))
    full = _stacked(jax.random.PRNGKey(11), D)
    with mesh:
        for name in ("w", "layers", "vec", "scalar"):
            tree = {name: full[name]}
            res = _init_res(tree, D, M)
            res_p = jax.device_put(res,
                                   ef_residual_sharding(res, mesh, "2d"))
            fn = jax.jit(lambda t, rr, n_=name: ef_wire_pmean_2d(
                t, rr, mesh, kind, widths={n_: bits}))
            with record_wire_bytes() as rec:
                fn.lower(tree, res_p)
            stacked = name == "layers"
            want = wire2d_leaf_bytes(full[name].shape[1:], D, M, kind,
                                     stacked=stacked, bits=bits)
            assert rec.total() == want, (name, kind, bits,
                                         rec.records, want)


@multidevice
def test_wire2d_pure_tp_takes_sliced_path_no_data_exchange():
    """--mesh 1xM (pure TP): the sliced path runs — and the trace emits
    NO data-axis exchange (no all_to_all, no data all_gather), only the
    model-axis rematerialization plus the scale pmax."""
    mesh = build_mesh(MeshSpec.host(1, 8))
    tree = _stacked(jax.random.PRNGKey(2), 1)
    res = _init_res(tree, 1, 8)
    with mesh:
        res_p = jax.device_put(res, ef_residual_sharding(res, mesh, "2d"))
        fn = jax.jit(lambda t, r: ef_wire_pmean_2d(t, r, mesh, "int8"))
        with record_wire_bytes() as rec:
            fn.lower(tree, res_p)
        d, r = fn(tree, res_p)
    ops = {op for op, _ in rec.records}
    assert not any("all_to_all" in op for op in ops), ops
    assert ops == {"pmax.scale", "all_gather.int8.model"}, ops
    # the delivered mean IS the single shard's quantized gradient
    ds, _ = simulate_wire_pmean_2d(tree, res, 8, "int8")
    for k in tree:
        np.testing.assert_array_equal(np.asarray(d[k]), np.asarray(ds[k]))


@multidevice
def test_wire2d_pure_tp_train_step_selected():
    """make_train_step(reduce='compressed') on a 1xM mesh must take the
    sliced wire path (NOT the single-device post-reduce fallback): the
    step accepts the [1, M, C] residual and trains."""
    from repro.data import DataSpec, make_pipeline
    from repro.models import JetTagger
    from repro.nn import HGQConfig
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step, softmax_xent

    cfg = HGQConfig(weight_gran="per_parameter", act_gran="per_parameter",
                    init_weight_f=2, init_act_f=2)
    p0, q0 = JetTagger.init(jax.random.PRNGKey(0), cfg)
    fwd = lambda p, q, b, mode: JetTagger.forward(p, q, b, mode)
    loss = lambda out, b: softmax_xent(out, b["y"])
    pipe = make_pipeline(DataSpec(kind="jet", batch=64))
    tc = TrainConfig(steps=4, lr=3e-3)
    mesh = build_mesh(MeshSpec.host(1, 8))
    step = make_train_step(fwd, loss, tc, reduce="compressed", mesh=mesh)
    with mesh:
        ec = EFState(residual=ef_wire2d_init(p0, 1, 8))
        p, q, o = p0, q0, adamw_init(p0)
        losses = []
        for s in range(4):
            p, q, o, m, ec = jax.jit(step)(p, q, o, pipe(s), jnp.int32(s),
                                           ec)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    # residual kept the sliced layout end-to-end
    for leaf in jax.tree.leaves(ec.residual):
        assert leaf.shape[:2] == (1, 8)


@multidevice
def test_wire2d_vjp_composes():
    mesh = build_mesh(MeshSpec.host(2, 4))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(2), (2, 6, 8))}
    res = ef_wire2d_init({"w": tree["w"][0]}, 2, 4)
    with mesh:
        val, grads = jax.value_and_grad(
            lambda t: jnp.sum(ef_wire_pmean_2d(t, res, mesh,
                                               "int8")[0]["w"]))(tree)
    assert np.isfinite(float(val))
    np.testing.assert_allclose(np.asarray(grads["w"]), 0.5, atol=1e-6)


def _jet_setup():
    from repro.data import DataSpec, make_pipeline
    from repro.models import JetTagger
    from repro.nn import HGQConfig
    from repro.train import softmax_xent

    cfg = HGQConfig(weight_gran="per_parameter", act_gran="per_parameter",
                    init_weight_f=2, init_act_f=2)
    p0, q0 = JetTagger.init(jax.random.PRNGKey(0), cfg)
    fwd = lambda p, q, b, mode: JetTagger.forward(p, q, b, mode)
    loss = lambda out, b: softmax_xent(out, b["y"])
    pipe = make_pipeline(DataSpec(kind="jet", batch=256))
    return p0, q0, fwd, loss, pipe


@multidevice
def test_compressed_2d_step_tracks_post_reduce():
    """reduce='compressed' with the 2D layout on a 2x4 mesh trains to the
    same loss curve as the post-reduce int8 path.  (Unlike the 1D test,
    step 0 is only near-equal: the model-sharded grad in_specs make GSPMD
    genuinely TP-partition the forward, and HGQ's activation quantization
    amplifies fp reassociation to grid-step size.)"""
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step

    p0, q0, fwd, loss, pipe = _jet_setup()
    tc = TrainConfig(steps=20, lr=3e-3, beta0=1e-7, beta1=1e-6)
    mesh = build_mesh(MeshSpec.host(2, 4))
    step_c = make_train_step(fwd, loss, tc, reduce="compressed", mesh=mesh,
                             wire_layout="2d")
    step_r = make_train_step(
        fwd, loss, tc, grad_tx=lambda g, s: ef_compress(g, s, kind="int8"))
    with mesh:
        jc, jr = jax.jit(step_c), jax.jit(step_r)
        pc, qc, oc = p0, q0, adamw_init(p0)
        ec = EFState(residual=ef_wire2d_init(p0, 2, 4))
        pr, qr, orr = p0, q0, adamw_init(p0)
        er = ef_init(p0)
        lc, lr_ = [], []
        for s in range(8):
            b = pipe(s)
            pc, qc, oc, mc, ec = jc(pc, qc, oc, b, jnp.int32(s), ec)
            pr, qr, orr, mr, er = jr(pr, qr, orr, b, jnp.int32(s), er)
            lc.append(float(mc["loss"]))
            lr_.append(float(mr["loss"]))
    assert abs(lc[0] - lr_[0]) < 5e-3, (lc[0], lr_[0])
    assert max(abs(a - b) for a, b in zip(lc, lr_)) < 0.05, (lc, lr_)
    assert lc[-1] < lc[0]


@multidevice
def test_compressed_2d_step_hlo_moves_int8():
    """The compiled 2D step must contain s8 gradient collectives and NO
    non-scalar fp32 all-reduce that crosses the DATA axis — fp32
    all-reduces inside a model group are the TP forward's activation
    math, which the model-sharded grad in_specs legitimately enable."""
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step

    p0, q0, fwd, loss, pipe = _jet_setup()
    tc = TrainConfig(steps=8, lr=3e-3)
    D, M = 2, 4
    mesh = build_mesh(MeshSpec.host(D, M))
    step = make_train_step(fwd, loss, tc, reduce="compressed", mesh=mesh)
    with mesh:
        ec = EFState(residual=ef_wire2d_init(p0, D, M))
        hlo = jax.jit(step).lower(p0, q0, adamw_init(p0), pipe(0),
                                  jnp.int32(0), ec).compile().as_text()
    assert "s8[" in hlo and "all-to-all" in hlo

    # shared repro.analysis parser: surviving small f32 all-reduces
    # (loss/gnorm scalars, amax grids) stay under SCALAR_MAX elements
    bad = [c.line[:160] for c in parse_collectives(hlo)
           if c.kind == "all-reduce" and c.dtype == "f32"
           and c.numel >= SCALAR_MAX and c.crosses_data_axis(M)]
    assert not bad, bad


@multidevice
def test_wire2d_resume_exact(tmp_path):
    """Checkpoint the sliced residual mid-run, restore, continue: params
    and residual must match the uninterrupted run bit-for-bit (the
    acceptance contract for 2D checkpoint/resume)."""
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step
    from repro.train import checkpoint as ckpt_lib

    p0, q0, fwd, loss, pipe = _jet_setup()
    tc = TrainConfig(steps=8, lr=3e-3)
    D, M = 2, 4
    mesh = build_mesh(MeshSpec.host(D, M))
    step = jax.jit(make_train_step(fwd, loss, tc, reduce="compressed",
                                   mesh=mesh))
    with mesh:
        # uninterrupted: 5 steps
        pa, qa, oa = p0, q0, adamw_init(p0)
        ea = EFState(residual=ef_wire2d_init(p0, D, M))
        for s in range(5):
            pa, qa, oa, _, ea = step(pa, qa, oa, pipe(s), jnp.int32(s), ea)
        # interrupted at 3: checkpoint, restore into fresh templates, go on
        pb, qb, ob = p0, q0, adamw_init(p0)
        eb = EFState(residual=ef_wire2d_init(p0, D, M))
        for s in range(3):
            pb, qb, ob, _, eb = step(pb, qb, ob, pipe(s), jnp.int32(s), eb)
        ckpt_lib.save(str(tmp_path), 3, {"params": pb, "opt": ob, "ef": eb})
        tmpl = {"params": p0, "opt": adamw_init(p0),
                "ef": EFState(residual=ef_wire2d_init(p0, D, M))}
        start, trees = ckpt_lib.restore(str(tmp_path), 3, tmpl)
        assert start == 3
        pc, oc, ec = trees["params"], trees["opt"], trees["ef"]
        qc = qb
        for s in range(3, 5):
            pc, qc, oc, _, ec = step(pc, qc, oc, pipe(s), jnp.int32(s), ec)
    for got, want in zip(jax.tree.leaves(pc), jax.tree.leaves(pa)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(jax.tree.leaves(ec.residual),
                         jax.tree.leaves(ea.residual)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------- fused bucketed path ------------------------------

@multidevice
@pytest.mark.parametrize("D,M", [(2, 4), (4, 2), (1, 8)])
def test_wire2d_fused_matches_legacy(D, M):
    """The fused bucketed 2D wire (concatenated pmax + pipelined
    per-bucket a2a/gather) is bit-for-bit the legacy per-leaf path and
    the simulator — on both DxM shapes AND the pure-TP 1x8 mesh, with
    mixed widths, at the default and a bucket-per-leaf budget."""
    mesh = build_mesh(MeshSpec.host(D, M))
    tree = _stacked(jax.random.PRNGKey(30), D)
    widths = {"w": 4, "layers": 4, "vec": 8, "scalar": 8}
    res = _init_res(tree, D, M)
    ds, rs = simulate_wire_pmean_2d(tree, res, M, "int8", widths=widths)
    with mesh:
        res_p = jax.device_put(res, ef_residual_sharding(res, mesh, "2d"))
        dl, rl = jax.jit(lambda t, rr: ef_wire_pmean_2d(
            t, rr, mesh, "int8", widths=widths, fused=False))(tree, res_p)
        for bb in (None, 1):
            df, rf = jax.jit(lambda t, rr, b=bb: ef_wire_pmean_2d(
                t, rr, mesh, "int8", widths=widths, fused=True,
                bucket_bytes=b))(tree, res_p)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(df[k]),
                                              np.asarray(dl[k]))
                np.testing.assert_array_equal(np.asarray(rf[k]),
                                              np.asarray(rl[k]))
                np.testing.assert_array_equal(np.asarray(df[k]),
                                              np.asarray(ds[k]))
                np.testing.assert_array_equal(np.asarray(rf[k]),
                                              np.asarray(rs[k]))


@multidevice
def test_wire2d_fused_records_same_bytes_as_legacy():
    """Fused and legacy 2D traces emit identical per-leaf wire records
    (bf16 and int8, stacked and flat leaves) — bucketing changes launch
    count, never bytes."""
    D, M = 2, 4
    mesh = build_mesh(MeshSpec.host(D, M))
    tree = _stacked(jax.random.PRNGKey(31), D)
    res = _init_res(tree, D, M)
    with mesh:
        res_p = jax.device_put(res, ef_residual_sharding(res, mesh, "2d"))
        for kind in ("int8", "bf16"):
            recs = {}
            for fused in (True, False):
                fn = jax.jit(lambda t, rr, k=kind, f=fused:
                             ef_wire_pmean_2d(t, rr, mesh, k, fused=f))
                with record_wire_bytes() as rec:
                    fn.lower(tree, res_p)
                recs[fused] = sorted(rec.records)
            assert recs[True] == recs[False], (kind, recs)
