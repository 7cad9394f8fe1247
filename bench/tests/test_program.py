"""The reduction of the program's own spans (``bench/program.py``) and
the per-layer metrics that read it, on the CPU:

    python -m pytest bench/tests/test_program.py

Hand-built events, the trace recorded on a v5e, a profile of the SMOKE
engine taken here, and each reader on a synthetic record."""
from __future__ import annotations

import glob
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import smoke  # noqa: E402

BENCH = smoke.BENCH
sys.path.insert(0, BENCH)
import program  # noqa: E402
import work  # noqa: E402
from harness import load_module, trace_lib  # noqa: E402

DATA = os.path.join(HERE, "data")
V5E = os.path.join(DATA, "v5e_small.xplane.pb")
SERVE = "qwen2-0.5b.serve.decode-heavy"
TRAIN = "qwen2-0.5b.train"
PEAKS = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       "bench_metric")


def _host(a, b, name, **stats):
    return (a, b, name, stats, 0)


def _events(host, ops, modules, enqueue=None, callbacks=None):
    return {"devices": [{"ops": ops, "modules": modules}], "host": host,
            "enqueue": enqueue or {}, "callbacks": callbacks or {}}


# ------------------------------------------------------------ reduction --

def test_idle_goes_to_the_innermost_program_span():
    # window 0-100; the benchmark's step 0-60 holds the engine's step
    # 10-50 with its inputs 10-20 and sync 30-50; a submit 60-100 holds a
    # prefill chunk 70-80
    host = [_host(0, 100, "bench.window"), _host(0, 60, "bench.step"),
            _host(10, 50, "engine.step", active=2),
            _host(10, 20, "engine.step.inputs"),
            _host(20, 30, "engine.step.dispatch"),
            _host(30, 50, "engine.step.sync"),
            _host(60, 100, "bench.submit"),
            _host(70, 80, "engine.prefill_chunk", start=16, n=8)]
    # device busy 25-45 (decode), 75-85 (prefill); offset 0
    ops = [(25, 45), (75, 85)]
    modules = [(25, 45, "engine_decode", 1), (75, 85, "engine_prefill", 2)]
    r = program.reduce_events(_events(host, ops, modules,
                                      enqueue={1: 20, 2: 75}))
    assert r["clock_offset_ms"] == pytest.approx(0.0)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    # idle 0-10 step (bench), 10-20 inputs, 20-25 dispatch, 45-50 sync,
    # 50-60 step (bench), 60-70 submit, 70-75 prefill chunk, 85-100
    # submit
    assert r["idle_by_span"] == pytest.approx({
        "bench.step": 20e-9, "engine.step.inputs": 10e-9,
        "engine.step.dispatch": 5e-9, "engine.step.sync": 5e-9,
        "bench.submit": 25e-9, "engine.prefill_chunk": 5e-9})
    assert r["idle_s"] == pytest.approx(70e-9)
    assert r["programs"] == {
        "engine_decode": {"s": pytest.approx(20e-9), "calls": 1},
        "engine_prefill": {"s": pytest.approx(10e-9), "calls": 1}}
    assert r["tick_host_s"] == [pytest.approx(20e-9)]
    assert r["prefill_chunks"] == [(16, 8)]
    # engine spans but the sync ones: inputs, dispatch, chunk
    assert program.idle_share(r, "engine.", but=".sync") == \
        pytest.approx(20.0)


def test_no_program_span_falls_back_to_bench_then_none():
    host = [_host(0, 100, "bench.window"), _host(20, 40, "bench.step")]
    r = program.reduce_events(_events(host, [(40, 60)], []))
    assert r["idle_by_span"] == pytest.approx({"bench.none": 60e-9,
                                               "bench.step": 20e-9})
    assert r["program_spans"] == 0
    assert program.of({"kind": "serve", "program": r}) is None


def test_clock_offset_from_enqueues_and_callbacks():
    # the device clock reads about 1000 below the host's
    modules = [(-890, -850, "engine_decode", 7),
               (-700, -600, "engine_decode", 8)]
    enqueue = {7: 100, 8: 250}          # the tightest: -890 - 100
    callbacks = {7: 160, 8: 420}        # the tightest: -850 - 160
    off, lo, hi, pairs = program.clock_offset(
        [{"ops": [], "modules": modules}], enqueue, callbacks)
    assert (off, lo, hi, pairs) == (-990, -1010, -990, 2)
    # after the shift no module starts before its enqueue, and none ends
    # after its callback
    assert all(a - off >= enqueue[c] for a, _, _, c in modules)
    assert all(b - off <= callbacks[c] for _, b, _, c in modules)
    assert program.clock_offset([{"ops": [], "modules": modules}], {},
                                callbacks)[0] == -1010
    assert program.clock_offset([], {}, {})[0] == 0.0


def test_program_names():
    assert program.program_name("jit_engine_prefill(1712223262632082)") \
        == "engine_prefill"
    assert program.program_name("jit_train_step") == "train_step"
    assert program.program_name("jit__lambda(5)") == "_lambda"


def test_recorded_v5e_trace_on_one_clock():
    """The recorded trace (see ``test_bench.py``): the device clock runs
    about a millisecond apart from the host's; shifted, every call's
    module starts after its enqueue, all 40 fall in the window, and the
    idle time by span sums to the idle time."""
    ev = program.load_events(V5E)
    r = program.reduce_events(ev)
    assert r["clock_pairs"] == 40
    assert -2.0 < r["clock_offset_ms"] < -0.5
    assert r["clock_offset_lo_ms"] <= r["clock_offset_hi_ms"]
    off = r["clock_offset_ms"] * 1e6
    mods = ev["devices"][0]["modules"]
    assert all(a - off >= ev["enqueue"][c] for a, _, _, c in mods)
    assert r["programs"]["_lambda"]["calls"] == 40
    assert r["window_s"] == trace_lib.reduce(V5E)["window_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["idle_s"], rel=1e-6)
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"],
                                        rel=1e-6)
    assert set(r["idle_by_span"]) == {"bench.step", "bench.none"}


def test_finds_this_runs_trace(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    run = root / "chiprun_out" / "bench" / SERVE / "seed5" / "plugins"
    run.mkdir(parents=True)
    shutil.copy(V5E, run / "host.xplane.pb")
    monkeypatch.setattr(program, "ROOT", str(root))
    monkeypatch.setattr(program, "_CACHE", {})
    t = trace_lib.reduce(V5E)
    # the recorded trace has no program spans, as a program without them
    assert program.of({"kind": "serve", "trace": t}) is None
    red = program._from_trace(t)
    assert red["workload"] == SERVE
    assert red["window_s"] == t["window_s"]
    # another run's window: not this trace
    assert program._from_trace(dict(t, window_s=1.0)) is None
    assert program._from_trace(dict(t, devices=0)) is None


# ------------------------------------------------- the program's spans --

def test_engine_spans_reach_the_reduction(tmp_path):
    """A profile of the SMOKE engine taken here: its spans and their
    attributes reach the host plane, and each tick's host time is its
    ``engine.step`` less its sync."""
    import jax
    from repro.configs import get
    from repro.models import model_for
    from repro.serving import Engine, Request
    cfg = get("qwen2-0.5b", smoke=True)
    M = model_for(cfg)
    p, q = M.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(M, p, q, cfg, batch_slots=2, max_len=32, prefill_chunk=4)
    eng.run([Request(prompt=[3] * 6, max_new=2)])        # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
            eng.run([Request(prompt=[5] * 7, max_new=3),
                     Request(prompt=[9] * 4, max_new=2)])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = program.load_events(path)
    names = [s[2] for s in ev["host"]]
    assert names.count("engine.submit") == 2
    assert names.count("engine.step") == 2
    assert sorted((s[3]["start"], s[3]["n"]) for s in ev["host"]
                  if s[2] == "engine.prefill_chunk") == [
        (0, 4), (0, 4), (4, 2), (6, 1)]
    # the CPU has no device plane: lend the reduction an idle device
    ev["devices"] = [{"ops": [], "modules": []}]
    r = program.reduce_events(ev)
    steps = [s for s in ev["host"] if s[2] == "engine.step"]
    syncs = [s for s in ev["host"] if s[2] == "engine.step.sync"]
    assert r["tick_host_s"] == pytest.approx(
        [(a[1] - a[0] - b[1] + b[0]) * 1e-9 for a, b in zip(steps, syncs)])
    assert r["prefill_chunks"] == [(0, 4), (4, 2), (6, 1), (0, 4)]
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"])
    assert {k for k in r["idle_by_span"] if k.startswith("engine.")} >= {
        "engine.submit", "engine.step", "engine.step.inputs",
        "engine.step.dispatch", "engine.step.sync", "engine.step.record"}


# -------------------------------------------------------------- readers --

def _serve_record():
    host = [_host(0, 1000, "bench.window"), _host(0, 600, "bench.step"),
            _host(100, 500, "engine.step", active=2),
            _host(100, 200, "engine.step.inputs"),
            _host(200, 250, "engine.step.dispatch"),
            _host(250, 450, "engine.step.sync"),
            _host(450, 500, "engine.step.record"),
            _host(600, 1000, "bench.submit"),
            _host(650, 1000, "engine.submit", rid=1, prompt=20, slot=0),
            _host(650, 700, "engine.prefill_chunk", start=0, n=16),
            _host(700, 760, "engine.prefill_chunk", start=16, n=4)]
    ops = [(240, 440), (680, 720), (750, 790)]
    modules = [(240, 440, "engine_decode", 1),
               (680, 720, "engine_prefill", 2),
               (750, 790, "engine_prefill", 3)]
    red = program.reduce_events(_events(host, ops, modules))
    red["workload"] = SERVE
    return {"kind": "serve", "program": red, "peaks": PEAKS}, red


def test_serve_readers_on_a_synthetic_record():
    rec, red = _serve_record()
    # tick: step 400 less sync 200
    assert reader("engine.tick_host_ms").read(rec) == pytest.approx(200e-6)
    # idle under engine spans but sync: inputs 100-200, dispatch
    # 200-240, record 450-500, submit 650-680 and 790-1000, chunks
    # 720-750 -> 100 + 40 + 50 + 30 + 210 + 30 = 460 of 1000
    assert reader("idle_share.serve.engine").read(rec) == \
        pytest.approx(46.0)
    m = program.model_of(SERVE)
    P, (H, _, hd), L = work.matmul_params(m), m["attention"], m["L"]
    flops = 2.0 * P * 20 + L * 4.0 * H * hd * sum(range(1, 21))
    assert reader("prefill.mfu").read(rec) == pytest.approx(
        100.0 * flops / (80e-9 * PEAKS["bf16_flops_per_s"]))
    for name in ("train.dispatch_ms", "idle_share.train.step"):
        assert reader(name).read(rec) is None


def test_train_readers_on_a_synthetic_record():
    host = [_host(0, 1000, "bench.window")]
    for a in (0, 500):
        host += [_host(a, a + 300, "bench.train_step"),
                 _host(a + 10, a + 300, "train.step"),
                 _host(a + 10, a + 50, "train.batch"),
                 _host(a + 50, a + 300, "train.dispatch")]
    ops = [(60, 480), (560, 900)]
    red = program.reduce_events(_events(
        host, ops, [(60, 480, "train_step", 1), (560, 900, "train_step", 2)]))
    rec = {"kind": "train", "program": red, "peaks": PEAKS}
    assert reader("train.dispatch_ms").read(rec) == pytest.approx(250e-6)
    # idle 0-60: 0-10 bench, 10-50 batch, 50-60 dispatch; 480-560: 480-500
    # bench.none, 500-510 bench, 510-550 batch, 550-560 dispatch; 900-1000
    # bench.none
    assert red["idle_by_span"] == pytest.approx({
        "bench.train_step": 20e-9, "train.batch": 80e-9,
        "train.dispatch": 20e-9, "bench.none": 120e-9})
    assert reader("idle_share.train.step").read(rec) == pytest.approx(10.0)
    for name in ("engine.tick_host_ms", "idle_share.serve.engine",
                 "prefill.mfu"):
        assert reader(name).read(rec) is None


def test_readers_silent_without_program_spans():
    """A traced run of a program without spans: the readers stay silent."""
    t = trace_lib.reduce(V5E)
    red = program.reduce_events(program.load_events(V5E))
    for kind, names in (("serve", ("engine.tick_host_ms",
                                   "idle_share.serve.engine",
                                   "prefill.mfu")),
                        ("train", ("train.dispatch_ms",
                                   "idle_share.train.step"))):
        for rec in ({"kind": kind, "trace": None, "peaks": PEAKS},
                    {"kind": kind, "trace": t, "program": red,
                     "peaks": PEAKS}):
            for name in names:
                assert reader(name).read(rec) is None
