"""The benchmark's own tests, on the CPU at the SMOKE sizes:

    python -m pytest bench/tests

The trace reduction on a trace recorded on a v5e, the work counts
against hand counts, the open-loop schedule, each driver end to end,
the refusal to run without a TPU, and a cell added as files alone."""
from __future__ import annotations

import hashlib
import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import smoke  # noqa: E402

BENCH = smoke.BENCH
sys.path.insert(0, BENCH)
import traffic  # noqa: E402
import work  # noqa: E402
from harness import load_module, trace_lib  # noqa: E402

DATA = os.path.join(HERE, "data")


# ------------------------------------------------------------------ trace --

def test_reduce_events_by_hand():
    # two devices; window 0-100 ns; host spans step 0-60, submit 60-100
    host = [(0, 100, "bench.window"), (0, 60, "bench.step"),
            (60, 100, "bench.submit")]
    dev0 = [(10, 30, "%qmatmul.1 = f32[8,896] custom-call()"),
            (20, 40, "%fusion.7 = f32[8] fusion()"),
            (70, 80, "%qmatmul.2 = f32[8,896] custom-call()")]
    dev1 = [(0, 50, "%while.3 = (s32[]) while()"),
            (5, 15, "%fusion.9 = f32[8] fusion()")]
    r = trace_lib.reduce_events([dev0, dev1], host)
    assert r["window_s"] == pytest.approx(100e-9)
    # dev0 busy 10-40 and 70-80 = 40; dev1 busy 0-50 = 50; mean 45
    assert r["busy_s"] == pytest.approx(45e-9)
    # containers do not count as op time
    assert r["ops_s"] == pytest.approx({"qmatmul": 15e-9, "fusion": 15e-9})
    # dev0 idle 0-10, 40-60 under step, 60-70, 80-100 under submit;
    # dev1 idle 50-60 under step, 60-100 under submit
    assert r["idle_s"]["bench.step"] == pytest.approx(20e-9)
    assert r["idle_s"]["bench.submit"] == pytest.approx(35e-9)
    b = trace_lib.breakdown(r)
    assert [n for n, _ in b["idle_gaps"]] == ["bench.submit", "bench.step"]


def test_reduce_recorded_v5e_trace():
    """40 calls of a jitted 2048 x 2048 matmul-and-tanh on one v5e, each
    in a ``bench.step`` span inside ``bench.window``, recorded with the
    harness's profiler options.  The device plane's clock runs about a
    millisecond ahead of the host's, so the first two calls' ops fall
    before the window."""
    r = trace_lib.reduce(os.path.join(DATA, "v5e_small.xplane.pb"))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.02706646, rel=1e-9)
    assert r["calls"] == {"copy-start": 38, "copy-done": 38, "fusion": 38}
    assert r["ops_s"]["fusion"] == pytest.approx(0.003539259, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.003539891, rel=1e-6)
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert set(r["idle_s"]) == {"bench.step", "bench.none"}


def test_op_family():
    assert trace_lib.family(
        '%qmatmul.51 = f32[8,896]{1,0} custom-call(f32[8,1024] %pad.206)'
    ) == "qmatmul"
    assert trace_lib.family("%dynamic-slice_bitcast_fusion.12 = s8[] x"
                            ) == "dynamic-slice_bitcast_fusion"
    assert trace_lib.family("%copy = f32[] copy()") == "copy"


# ------------------------------------------------------------------- work --

def test_qmatmul_work_by_hand():
    # x [8, 896] @ 4-bit w [896, 4864]: 2 m k n FLOPs; the weight at half
    # a byte each, x, out and the per-column scale in f32
    f, b = work.qmatmul(8, 896, 4864, 4)
    assert f == 2 * 8 * 896 * 4864
    assert b == 896 * 4864 / 2 + 4 * (8 * 896 + 8 * 4864 + 4864)


def test_kv_attention_work_by_hand():
    # two query rows attending to 3 and 5 tokens; 14 heads over 2 kv
    # heads of 64 at 4 bits: per token per kv head 32 mantissa bytes
    # plus 1 exponent byte, for k and for v
    f, b = work.kv_attention([3, 5], 14, 2, 64, 4)
    assert f == 4 * 14 * 64 * (3 + 5)
    assert b == (3 + 5) * 2 * 2 * (32 + 1) + 2 * 2 * 4 * 14 * 64


def _toy_model(L, d, H, KV, hd, ff, V):
    layer = {f"layers/{k}/kernel": kn for k, kn in (
        ("attn/wq", (d, H * hd)), ("attn/wk", (d, KV * hd)),
        ("attn/wv", (d, KV * hd)), ("attn/wo", (H * hd, d)),
        ("mlp/gate", (d, ff)), ("mlp/up", (d, ff)), ("mlp/down", (ff, d)))}
    return {"L": L, "layer": layer, "head": ("embed/table", d, V),
            "qmatmul": set(layer) | {"embed/table"},
            "attention": (H, KV, hd), "wkv": None}


def test_serve_work_counts_every_call():
    m = _toy_model(2, 8, 2, 1, 4, 16, 32)
    widths = {k: 8 for k in list(m["layer"]) + ["embed/table"]}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    w = work.serve_work(m, widths, 4, [[5, 9]], [37], 16, peaks)
    # one tick and a 37-token prompt in chunks 16, 16, 4, 1: 5 calls, each
    # 7 projections per layer plus the head, and one attention per layer
    assert w["qmatmul"]["calls"] == 5 * (7 * 2 + 1)
    assert w["kv_attention"]["calls"] == 5 * 2
    assert w["qmatmul"]["memory_bound"] == w["qmatmul"]["calls"]
    assert list(work.prefill_chunks(37, 16)) == [(0, 16), (16, 16),
                                                 (32, 4), (36, 1)]
    f, b = work.qmatmul(2, 8, 32, 8)
    assert w["qmatmul"]["least_s"] > b / 1e9
    # a recurrent model: no attention calls, XLA matmuls not counted
    r = dict(m, attention=None, wkv=(2, 4),
             qmatmul={"layers/attn/wq/kernel"})
    w = work.serve_work(r, widths, None, [[5, 9]], [], 16, peaks)
    assert w["qmatmul"]["calls"] == 2 and w["kv_attention"]["calls"] == 0
    assert work.decode_flops(r, [5, 9]) == \
        2 * 2 * work.matmul_params(r) + 2 * 2 * 7 * 2 * 4 * 4


def test_train_flops_by_hand():
    m = _toy_model(1, 4, 2, 1, 2, 8, 10)
    params = (4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8) + 4 * 10
    assert work.matmul_params(m) == params
    # batch 1, seq 3: 6 pairs; 12 FLOPs per head-dim per pair in training
    assert work.train_flops(m, 1, 3) == 6 * params * 3 + 12 * 2 * 2 * 6


# ---------------------------------------------------------------- traffic --

MIX = {"rate_per_s": 5.0,
       "prompt": {"median": 100, "sigma": 0.8, "min": 16, "max": 1024},
       "output": {"median": 200, "sigma": 0.6, "min": 32, "max": 1024}}


def test_schedule_is_a_function_of_the_seed():
    a = traffic.schedule(MIX, 4000000007, 0.0, 20.0, 1000, 2)
    b = traffic.schedule(MIX, 4000000007, 0.0, 20.0, 1000, 2)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]


def test_every_seed_gets_the_same_work():
    a = traffic.schedule(MIX, 1, 0.0, 20.0, 1000, 2)
    b = traffic.schedule(MIX, 2, 0.0, 20.0, 1000, 2)
    assert len(a) == len(b) == 100
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert len({x.max_new for x in a}) > 10
    assert all(0.0 <= x.due < 20.0 for x in a + b)


def test_traffic_base_chain(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps({"driver": "serve",
                                                 "rate_per_s": 1.0}))
    (tmp_path / "b.json").write_text(json.dumps({"base": "a",
                                                 "rate_per_s": 3.0}))
    assert traffic.load("b", str(tmp_path)) == {"driver": "serve",
                                                "rate_per_s": 3.0}


# ---------------------------------------------------------------- drivers --

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return smoke.make_checkout(str(tmp_path_factory.mktemp("bench")))


def drive(root, cell, seed, **kw):
    h = smoke.harness(root, cell, seed, **kw)
    drv = load_module(h.driver_path, "bench_driver")
    return h, drv.run(h)


def _passes(res):
    return all(v <= lim for _, v, lim in res["checks"])


def test_train_driver_end_to_end(checkout):
    h, res = drive(checkout, "qwen2-0.5b.train", 4100000001)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["train_tokens_per_s"] > 0
    assert res["metrics"]["setup_s"] > 0
    assert _passes(res), res["checks"]


def test_serve_driver_end_to_end(checkout):
    h, res = drive(checkout, "qwen2-0.5b.serve.decode-heavy", 4100000002)
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["ttft_p90_ms"] > 0 and m["tpot_p99_ms"] > 0
    assert m["serve_tokens_per_s"] > 0
    assert _passes(res), res["checks"]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen2-0.5b.train", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_added_as_files_alone(tmp_path):
    """A new traffic mix and a new per-layer metric, as new files plus
    entries in BENCHMARK.json, run with no existing file edited."""
    root = smoke.make_checkout(str(tmp_path))
    before = _digest(os.path.join(root, "bench"))
    tdir = os.path.join(root, "bench", "traffic")
    with open(os.path.join(tdir, "serve.decode-heavy.slow.json"),
              "w") as fh:
        json.dump({"base": "serve.decode-heavy", "rate_per_s": 2.0}, fh)
    with open(os.path.join(root, "bench", "metrics",
                           "engine.ticks.py"), "w") as fh:
        fh.write("def read(rec):\n"
                 "    return float(len(rec['step_s'])) or None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = "qwen2-0.5b.serve.decode-heavy.slow"
    bench["workloads"].append({
        "name": cell, "config": "qwen2-0.5b",
        "traffic": "serve.decode-heavy.slow", "chips": 1,
        "why": "the decode-heavy mix at a lower rate"})
    bench["per_layer"].append({
        "name": "engine.ticks", "unit": "ticks", "better": "higher",
        "source": "host_clock", "layer": "engine scheduler",
        "moves": "tpot_p99_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    h, res = drive(root, cell, 4100000003)
    assert h.mix["rate_per_s"] == 2.0
    assert _passes(res), res["checks"]
    reader = load_module(os.path.join(root, "bench", "metrics",
                                      "engine.ticks.py"), "m")
    assert reader.read(res["record"]) > 0
    after = _digest(os.path.join(root, "bench"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert math.isfinite(res["metrics"]["ttft_p90_ms"])
