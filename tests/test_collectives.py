"""dist.collectives — the int8-on-the-wire compressed mean all-reduce.

Single-device tests drive the collective-free reference
(``simulate_wire_pmean``) plus the grid/bytes/EF-property contracts; the
``@multidevice`` tests (CI job with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) assert the real
``shard_map`` path matches the reference bit-for-bit, that the compressed
train step tracks the post-reduce one, and that the compiled HLO moves
int8 — not fp32 — gradient bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import SCALAR_MAX, parse_collectives
from repro.api import MeshSpec, build_mesh
from repro.dist import EFState, ef_compress, ef_init
from repro.dist.collectives import (data_axis_size, ef_wire_init,
                                    ef_wire_pmean, fp32_allreduce_bytes,
                                    simulate_wire_pmean, wire_bytes_model)

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _stacked(key, n=4):
    """A per-shard tree with a stacked [L, ...] leaf (under the
    ``layers`` container, which marks it stacked by path), a flat leaf,
    and a scalar leaf."""
    ks = jax.random.split(key, 3)
    return {"layers": jax.random.normal(ks[0], (n, 3, 8, 5)),
            "vec": jax.random.normal(ks[1], (n, 17)),
            "scalar": jax.random.normal(ks[2], (n,))}


# ------------------------- reference semantics ------------------------------

def test_simulate_delivers_near_mean():
    tree = _stacked(jax.random.PRNGKey(0))
    delivered, residual = simulate_wire_pmean(tree, "int8")
    for k in tree:
        true = np.mean(np.asarray(tree[k]), axis=0)
        grid = np.max(np.abs(np.asarray(tree[k]))) / 127 * 2
        np.testing.assert_allclose(np.asarray(delivered[k]), true,
                                   atol=4 * grid)
        assert residual[k].shape == tree[k].shape


def test_simulate_stacked_leaf_per_layer_grids():
    """One outlier layer in a stacked [L, ...] leaf must not crush the
    other layers' resolution: each layer's one-step quantization error is
    bounded by its OWN grid step, not the outlier's."""
    e = jnp.ones((2, 3, 8, 5)) * 1e-3
    e = e.at[:, 1].mul(1e4)  # layer 1 is a 10.0-scale outlier
    delivered, _ = simulate_wire_pmean({"w": e}, "int8",
                                       stacked={"w": True})
    err = np.abs(np.asarray(delivered["w"]) - np.mean(np.asarray(e), axis=0))
    for layer in range(3):
        own_grid = float(np.max(np.abs(np.asarray(e[:, layer])))) / 127
        assert err[layer].max() <= 2.5 * own_grid, (
            f"layer {layer}: err {err[layer].max()} vs own grid {own_grid}")
    # the old per-tensor grid would have made layer-0 error ~outlier/127
    assert err[0].max() < 1e-4


def test_wire_bad_kind_raises():
    tree = {"w": jnp.zeros((2, 4))}
    with pytest.raises(ValueError, match="int8"):
        simulate_wire_pmean(tree, "fp4")


def test_bytes_model_hits_4x():
    """The acceptance ratio: int8-wire must cut gradient collective bytes
    >= 3x vs a ring fp32 all-reduce at n=8 (analytically it is ~4x; the
    per-layer scale sidecar eats a sliver)."""
    n, elems = 8, 500_000
    int8 = wire_bytes_model(elems, n, "int8", n_scale_rows=64)
    bf16 = wire_bytes_model(elems, n, "bf16", n_scale_rows=64)
    fp32 = fp32_allreduce_bytes(elems, n)
    assert fp32 / int8 >= 3.0, (fp32, int8)
    assert fp32 / bf16 >= 1.9, (fp32, bf16)


# ------------------------ error-feedback property ---------------------------

@settings(max_examples=15)
@given(st.lists(st.floats(min_value=-1.0, max_value=1.0), min_size=4,
                max_size=24),
       st.integers(min_value=8, max_value=20))
def test_ef_time_average_unbiased(vals, K):
    """Over K steps of a constant gradient, the time-averaged delivered
    gradient is within one grid step of the truth — for post-reduce int8
    EF and for the two-phase int8-wire reduce (simulated 4 shards)."""
    g = jnp.asarray(vals, jnp.float32)
    grid = max(float(jnp.max(jnp.abs(g))), 1e-30) / 127.0

    st_ = ef_init({"w": g})
    acc = jnp.zeros_like(g)
    for _ in range(K):
        sent, st_ = ef_compress({"w": g}, st_, kind="int8")
        acc = acc + sent["w"]
    np.testing.assert_allclose(np.asarray(acc / K), np.asarray(g),
                               atol=grid + 1e-7)

    # int8-wire: 4 simulated shards, distinct per-shard gradients whose
    # mean is g (shard i sees g scaled by a fixed factor)
    fac = jnp.asarray([0.4, 0.8, 1.2, 1.6])[:, None]
    gs = fac * g[None, :]
    true_mean = jnp.mean(gs, axis=0)
    wire_grid = max(float(jnp.max(jnp.abs(gs))), 1e-30) / 127.0 * 2
    res = ef_wire_init({"w": true_mean}, 4)
    acc = jnp.zeros_like(g)
    for _ in range(K):
        e = {"w": gs + res["w"]}
        d, res = simulate_wire_pmean(e, "int8")
        acc = acc + d["w"]
    np.testing.assert_allclose(np.asarray(acc / K), np.asarray(true_mean),
                               atol=wire_grid + 1e-7)


def test_compression_none_step_bit_exact():
    """kind='none' must be bit-exact with the uncompressed train step."""
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

    plain = jax.jit(make_train_step(fwd, loss, tc))
    nones = jax.jit(make_train_step(
        fwd, loss, tc, grad_tx=lambda g, s: ef_compress(g, s, kind="none")))

    pa, qa, oa = p0, q0, adamw_init(p0)
    pb, qb, ob = p0, q0, adamw_init(p0)
    eb = ef_init(p0)
    for s in range(3):
        b = pipe(s)
        pa, qa, oa, _ = plain(pa, qa, oa, b, jnp.int32(s))
        pb, qb, ob, _, eb = nones(pb, qb, ob, b, jnp.int32(s), eb)
    for got, want in zip(jax.tree.leaves(pb), jax.tree.leaves(pa)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_compressed_rejects_grad_tx():
    """grad_tx and reduce='compressed' are mutually exclusive — silently
    replacing a caller's transform would be the same bug class Trainer
    just had fixed."""
    from repro.train import TrainConfig, make_train_step
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_train_step(lambda *a, **k: None, lambda *a: None,
                        TrainConfig(steps=1),
                        grad_tx=lambda g, s: (g, s), reduce="compressed")


def test_compressed_single_device_is_post_reduce_path():
    """On one data shard the wire is a no-op: reduce='compressed' must be
    token-for-token exact with the post-reduce ef_compress(kind='int8')
    step (the acceptance contract for single-device fallback)."""
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

    wire = jax.jit(make_train_step(fwd, loss, tc, reduce="compressed",
                                   mesh=None))
    post = jax.jit(make_train_step(
        fwd, loss, tc, grad_tx=lambda g, s: ef_compress(g, s, kind="int8")))
    pa, qa, oa, ea = p0, q0, adamw_init(p0), ef_init(p0)
    pb, qb, ob, eb = p0, q0, adamw_init(p0), ef_init(p0)
    for s in range(3):
        b = pipe(s)
        pa, qa, oa, _, ea = wire(pa, qa, oa, b, jnp.int32(s), ea)
        pb, qb, ob, _, eb = post(pb, qb, ob, b, jnp.int32(s), eb)
    for got, want in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for got, want in zip(jax.tree.leaves(ea.residual),
                         jax.tree.leaves(eb.residual)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------- mixed widths ---------------------------------

def test_simulate_mixed_widths_grid_and_error():
    """Per-leaf wire widths: a w4 leaf quantizes on the 2^(4-1)-1 = 7
    grid (coarser error bound), w8 leaves are untouched — byte-for-byte
    equal to the no-widths trace."""
    tree = _stacked(jax.random.PRNGKey(5))
    widths = {"layers": 4, "vec": 8, "scalar": 8}
    d, r = simulate_wire_pmean(tree, "int8", widths=widths)
    d8, r8 = simulate_wire_pmean(tree, "int8")
    true = np.mean(np.asarray(tree["layers"]), axis=0)
    grid4 = np.max(np.abs(np.asarray(tree["layers"]))) / 7 * 2
    np.testing.assert_allclose(np.asarray(d["layers"]), true,
                               atol=4 * grid4)
    # w8 leaves must be bit-identical to the widths-free path
    for k in ("vec", "scalar"):
        np.testing.assert_array_equal(np.asarray(d[k]), np.asarray(d8[k]))
        np.testing.assert_array_equal(np.asarray(r[k]), np.asarray(r8[k]))
    # the w4 leaf genuinely moved to the coarser grid
    assert not np.array_equal(np.asarray(d["layers"]),
                              np.asarray(d8["layers"]))


def test_width_flags_validation():
    from repro.dist.collectives import _width_flags
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((2,))}
    assert _width_flags(tree, None) == (8, 8)
    assert _width_flags(tree, {"a": 4, "b": 8}) == (4, 8)
    with pytest.raises(ValueError, match="wire width"):
        _width_flags(tree, {"a": 1, "b": 8})


@pytest.mark.parametrize("bits", [4, 5, 8])
@pytest.mark.parametrize("n", [1, 2, 3, 4, 8])
def test_phase2_shift_fits_every_width(bits, n):
    """The phase-2 requantize is width-independent: with shift
    k = ceil(log2 n), |round(sum / 2^k)| <= qmax for ANY payload width
    (2^k >= n bounds the worst-case sum of n in-range mantissas) — so
    phase-2/3 payloads always repack into the leaf's width."""
    from repro.dist.collectives import _phase2_shift
    qmax = 2 ** (bits - 1) - 1
    k = _phase2_shift(n)
    worst = n * qmax
    assert round(worst / 2 ** k) <= qmax, (bits, n, k)
    assert round(-worst / 2 ** k) >= -qmax


def test_bytes_model_nibble_halves_payload():
    """bits<=4 int8-wire chunks count nibble-packed (ceil(C/2)) bytes;
    the scale sidecar is width-independent."""
    n, elems, rows = 8, 500_000, 64
    b8 = wire_bytes_model(elems, n, "int8", rows)
    b4 = wire_bytes_model(elems, n, "int8", rows, bits=4)
    b5 = wire_bytes_model(elems, n, "int8", rows, bits=5)
    scales = wire_bytes_model(0, n, "int8", rows)
    assert b5 == b8                       # only <=4 bits nibble-pack
    np.testing.assert_allclose(b4 - scales, (b8 - scales) / 2, rtol=1e-3)
    # bf16 ignores bits (payload carries its own exponents)
    assert wire_bytes_model(elems, n, "bf16", rows, bits=4) \
        == wire_bytes_model(elems, n, "bf16", rows)


# --------------------------- multi-device path ------------------------------

@multidevice
def test_shard_map_matches_simulate():
    mesh = build_mesh(MeshSpec.host(4, 2))
    assert data_axis_size(mesh) == 4
    tree = _stacked(jax.random.PRNGKey(1))
    from repro.dist.sharding import ef_residual_sharding
    with mesh:
        placed = jax.device_put(tree, ef_residual_sharding(tree, mesh))
        for kind in ("int8", "bf16"):
            d, r = jax.jit(
                lambda t, k=kind: ef_wire_pmean(t, mesh, k))(placed)
            ds, rs = simulate_wire_pmean(tree, kind)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(d[k]),
                                              np.asarray(ds[k]))
                np.testing.assert_array_equal(np.asarray(r[k]),
                                              np.asarray(rs[k]))


@multidevice
def test_shard_map_matches_simulate_mixed_widths():
    """Mixed per-leaf widths on the real 1D shard_map path: bit-for-bit
    equal to the simulator (pack∘unpack is the identity on in-range int4
    mantissas, so the packed wire changes no delivered value)."""
    mesh = build_mesh(MeshSpec.host(4, 2))
    tree = _stacked(jax.random.PRNGKey(6))
    widths = {"layers": 4, "vec": 8, "scalar": 8}
    from repro.dist.sharding import ef_residual_sharding
    with mesh:
        placed = jax.device_put(tree, ef_residual_sharding(tree, mesh))
        d, r = jax.jit(lambda t: ef_wire_pmean(
            t, mesh, "int8", widths=widths))(placed)
    ds, rs = simulate_wire_pmean(tree, "int8", widths=widths)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(d[k]), np.asarray(ds[k]))
        np.testing.assert_array_equal(np.asarray(r[k]), np.asarray(rs[k]))


@multidevice
def test_wire_1d_bytes_model_pins_measured_trace():
    """wire_bytes_model == the recorder's measured per-leaf totals, for
    int8 at w8 and w4 (nibble chunks) and for bf16 — the byte model and
    the traced collectives must not drift apart."""
    from repro.dist.collectives import record_wire_bytes
    from repro.dist.sharding import ef_residual_sharding
    mesh = build_mesh(MeshSpec.host(4, 2))
    n = data_axis_size(mesh)
    cases = [("layers", "int8", 8, 3), ("layers", "int8", 4, 3),
             ("vec", "int8", 4, 1), ("vec", "bf16", 8, 1)]
    full = _stacked(jax.random.PRNGKey(7))
    with mesh:
        for name, kind, bits, rows in cases:
            tree = {name: full[name]}
            placed = jax.device_put(tree,
                                    ef_residual_sharding(tree, mesh))
            fn = jax.jit(lambda t, k=kind, b=bits, n_=name: ef_wire_pmean(
                t, mesh, k, widths={n_: b}))
            with record_wire_bytes() as rec:
                fn.lower(placed)
            want = wire_bytes_model(full[name][0].size, n, kind,
                                    n_scale_rows=rows, bits=bits)
            assert rec.total() == want, (name, kind, bits,
                                         rec.records, want)


@multidevice
def test_wire_vjp_composes():
    """value_and_grad through the collective: the backward is the
    transpose of an uncompressed shard mean (cotangent / n per shard)."""
    mesh = build_mesh(MeshSpec.host(4, 2))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(2), (4, 6, 5))}
    with mesh:
        val, grads = jax.value_and_grad(
            lambda t: jnp.sum(ef_wire_pmean(t, mesh, "int8")[0]["w"]))(tree)
    assert np.isfinite(float(val))
    np.testing.assert_allclose(np.asarray(grads["w"]), 0.25, atol=1e-6)


@multidevice
def test_compressed_step_tracks_post_reduce():
    """reduce='compressed' on a 4x2 FSDPxTP mesh trains to the same loss
    curve as the post-reduce int8 path (both carry one-grid-step EF
    noise), starting from an identical first step."""
    from repro.data import DataSpec, make_pipeline
    from repro.dist import collectives
    from repro.models import JetTagger
    from repro.nn import HGQConfig
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step, softmax_xent

    cfg = HGQConfig(weight_gran="per_parameter", act_gran="per_parameter",
                    init_weight_f=2, init_act_f=2)
    p0, q0 = JetTagger.init(jax.random.PRNGKey(0), cfg)
    fwd = lambda p, q, b, mode: JetTagger.forward(p, q, b, mode)
    loss = lambda out, b: softmax_xent(out, b["y"])
    pipe = make_pipeline(DataSpec(kind="jet", batch=256))
    tc = TrainConfig(steps=20, lr=3e-3, beta0=1e-7, beta1=1e-6)
    mesh = build_mesh(MeshSpec.host(4, 2))
    n = collectives.data_axis_size(mesh)

    # wire_layout pinned to "1d": this test drives the 1D collective (the
    # 2x4-mesh default would auto-select the 2D sliced path, see
    # tests/test_wire2d.py)
    step_c = make_train_step(fwd, loss, tc, reduce="compressed", mesh=mesh,
                             wire_layout="1d")
    step_r = make_train_step(
        fwd, loss, tc, grad_tx=lambda g, s: ef_compress(g, s, kind="int8"))
    with mesh:
        jc, jr = jax.jit(step_c), jax.jit(step_r)
        pc, qc, oc = p0, q0, adamw_init(p0)
        ec = EFState(residual=ef_wire_init(p0, n))
        pr, qr, orr = p0, q0, adamw_init(p0)
        er = ef_init(p0)
        lc, lr_ = [], []
        for s in range(8):
            b = pipe(s)
            pc, qc, oc, mc, ec = jc(pc, qc, oc, b, jnp.int32(s), ec)
            pr, qr, orr, mr, er = jr(pr, qr, orr, b, jnp.int32(s), er)
            lc.append(float(mc["loss"]))
            lr_.append(float(mr["loss"]))
    # step 0 is pre-update: identical up to slice-mean reassociation
    assert abs(lc[0] - lr_[0]) < 1e-5, (lc[0], lr_[0])
    # both curves descend together within EF (one-grid-step) noise
    assert max(abs(a - b) for a, b in zip(lc, lr_)) < 0.05, (lc, lr_)
    assert lc[-1] < lc[0]


@multidevice
def test_compressed_step_hlo_moves_int8():
    """The compiled compressed step must contain s8 gradient collectives
    and NO non-scalar fp32 all-reduce/all-gather of gradient size — the
    fp32 reduction is gone, not merely post-processed."""
    from repro.data import DataSpec, make_pipeline
    from repro.dist import collectives
    from repro.models import JetTagger
    from repro.nn import HGQConfig
    from repro.optim import adamw_init
    from repro.train import TrainConfig, make_train_step, softmax_xent

    cfg = HGQConfig(weight_gran="per_parameter", act_gran="per_parameter",
                    init_weight_f=2, init_act_f=2)
    p0, q0 = JetTagger.init(jax.random.PRNGKey(0), cfg)
    fwd = lambda p, q, b, mode: JetTagger.forward(p, q, b, mode)
    loss = lambda out, b: softmax_xent(out, b["y"])
    pipe = make_pipeline(DataSpec(kind="jet", batch=256))
    tc = TrainConfig(steps=8, lr=3e-3)
    mesh = build_mesh(MeshSpec.host(4, 2))
    n = collectives.data_axis_size(mesh)
    step = make_train_step(fwd, loss, tc, reduce="compressed", mesh=mesh,
                           wire_layout="1d")
    with mesh:
        ec = EFState(residual=ef_wire_init(p0, n))
        hlo = jax.jit(step).lower(p0, q0, adamw_init(p0), pipe(0),
                                  jnp.int32(0), ec).compile().as_text()
    assert "s8[" in hlo and "all-to-all" in hlo
    # shared repro.analysis parser: every surviving f32 all-reduce is
    # tiny — loss/gnorm scalars, amax grids, TP feature extremes; a
    # gradient-sized one (smallest JetTagger matmul leaf is 16*64) would
    # mean fp32 crossed the wire
    for c in parse_collectives(hlo):
        if c.kind == "all-reduce" and c.dtype == "f32":
            assert c.numel < SCALAR_MAX, c.line[:160]


# ------------------------- fused bucketed path ------------------------------

@settings(max_examples=20)
@given(st.lists(st.integers(min_value=1, max_value=4000), min_size=0,
                max_size=12),
       st.integers(min_value=1, max_value=5000))
def test_property_bucket_leaves_partition(sizes, bucket_bytes):
    """_bucket_leaves is a true partition: every leaf index exactly once,
    every bucket within the budget unless it holds a single oversized
    leaf, and the result deterministic in the input."""
    from repro.dist.collectives import _bucket_leaves
    buckets = _bucket_leaves(sizes, bucket_bytes)
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == list(range(len(sizes)))
    for b in buckets:
        assert b, "empty bucket"
        if len(b) > 1:
            assert sum(sizes[i] for i in b) <= bucket_bytes, (b, sizes)
    assert buckets == _bucket_leaves(sizes, bucket_bytes)


@multidevice
@pytest.mark.parametrize("kind", ["int8", "bf16"])
def test_fused_matches_legacy_1d(kind):
    """The tentpole bit-exactness contract: the fused bucketed wire (one
    concatenated pmax/all_to_all/all_gather per bucket) delivers the SAME
    bits as the legacy per-leaf path and the simulator."""
    from repro.dist.sharding import ef_residual_sharding
    mesh = build_mesh(MeshSpec.host(4, 2))
    tree = _stacked(jax.random.PRNGKey(20))
    with mesh:
        placed = jax.device_put(tree, ef_residual_sharding(tree, mesh))
        df, rf = jax.jit(lambda t: ef_wire_pmean(
            t, mesh, kind, fused=True))(placed)
        dl, rl = jax.jit(lambda t: ef_wire_pmean(
            t, mesh, kind, fused=False))(placed)
    ds, rs = simulate_wire_pmean(tree, kind)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(df[k]), np.asarray(dl[k]))
        np.testing.assert_array_equal(np.asarray(rf[k]), np.asarray(rl[k]))
        np.testing.assert_array_equal(np.asarray(df[k]), np.asarray(ds[k]))
        np.testing.assert_array_equal(np.asarray(rf[k]), np.asarray(rs[k]))


@multidevice
def test_fused_multi_bucket_matches_simulator():
    """A tiny bucket budget forces every leaf into its own pipelined
    bucket (odd chunk tails included) — still bit-for-bit the simulator,
    with mixed per-leaf widths riding the nibble wire."""
    from repro.dist.sharding import ef_residual_sharding
    mesh = build_mesh(MeshSpec.host(4, 2))
    tree = _stacked(jax.random.PRNGKey(21))
    widths = {"layers": 4, "vec": 8, "scalar": 8}
    from repro.dist.collectives import _bucket_leaves, _WIRE_BUCKET_BYTES
    assert _WIRE_BUCKET_BYTES >= 1 << 20
    ds, rs = simulate_wire_pmean(tree, "int8", widths=widths)
    with mesh:
        placed = jax.device_put(tree, ef_residual_sharding(tree, mesh))
        for bb in (1, 256):                    # 3 buckets / mixed buckets
            d, r = jax.jit(lambda t, b=bb: ef_wire_pmean(
                t, mesh, "int8", widths=widths, fused=True,
                bucket_bytes=b))(placed)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(d[k]),
                                              np.asarray(ds[k]))
                np.testing.assert_array_equal(np.asarray(r[k]),
                                              np.asarray(rs[k]))


@multidevice
def test_fused_records_same_bytes_as_legacy():
    """The byte recorder sees identical per-leaf wire records from the
    fused and legacy traces (tags and values; order may differ with the
    bucket schedule) — the fusion moves launches, not bytes."""
    from repro.dist.collectives import record_wire_bytes
    from repro.dist.sharding import ef_residual_sharding
    mesh = build_mesh(MeshSpec.host(4, 2))
    tree = _stacked(jax.random.PRNGKey(22))
    widths = {"layers": 4, "vec": 8, "scalar": 8}
    with mesh:
        placed = jax.device_put(tree, ef_residual_sharding(tree, mesh))
        recs = {}
        for fused in (True, False):
            fn = jax.jit(lambda t, f=fused: ef_wire_pmean(
                t, mesh, "int8", widths=widths, fused=f))
            with record_wire_bytes() as rec:
                fn.lower(placed)
            recs[fused] = sorted(rec.records)
    assert recs[True] == recs[False], recs
