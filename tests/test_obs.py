"""The span and counter recorder (``repro.obs``) and the program's spans:
off, it records nothing and never enters a profiler annotation; on, it
links each span to its parent and counts work at the same boundaries,
without changing what the engine serves."""
import dataclasses
import glob
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import RunSpec, build
from repro.configs import get
from repro.models import model_for
from repro.serving import Engine, Request, SamplingConfig

KEY = jax.random.PRNGKey(7)
STEP_CHILDREN = ["engine.step.inputs", "engine.step.dispatch",
                 "engine.step.sync", "engine.step.record"]


@pytest.fixture(autouse=True)
def recorder_off():
    """Every test starts and ends with the recorder off."""
    obs.stop()
    yield
    obs.stop()


class CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts entries."""
    entered = []
    is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        CountingAnnotation.entered.append((self.name, self.attrs))

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    CountingAnnotation.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    return CountingAnnotation.entered


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = get("qwen2-0.5b", smoke=True)
    M = model_for(cfg)
    p, q = M.init(KEY, cfg)
    return M, p, q, cfg


def _requests(vocab, lens, max_news, sampled=()):
    out = []
    for i, (n, mn) in enumerate(zip(lens, max_news)):
        toks = jax.random.randint(jax.random.fold_in(KEY, i), (n,), 0, vocab)
        sc = SamplingConfig(temperature=0.8, top_k=5) if i in sampled \
            else None
        out.append(Request(prompt=[int(t) for t in toks], max_new=mn,
                           sampling=sc))
    return out


def _chunks(plen, chunk):
    """The engine's chunking: whole chunks, then powers of two."""
    full, rest = divmod(plen, chunk)
    return full + bin(rest).count("1")


def _serve(smoke_lm, reqs, **kw):
    M, p, q, cfg = smoke_lm
    eng = Engine(M, p, q, cfg, batch_slots=2, max_len=48, prefill_chunk=8,
                 seed=11, **kw)
    handles = []
    pending = list(reqs)
    while pending or any(r is not None for r in eng.slot_req):
        while pending and eng._free_slot() is not None:
            handles.append(eng.submit(pending.pop(0)))
        eng.step()
    return handles


# ------------------------------------------------------------- recorder --

def test_off_is_one_shared_noop(annotations):
    assert obs.span("a", x=1) is obs.span("b")
    with obs.span("engine.step", active=3):
        obs.count("engine.ticks")
    assert annotations == []
    assert obs.stop() == obs.Records()


def test_on_links_parents_attrs_and_counters(annotations):
    obs.start()
    with pytest.raises(RuntimeError):
        obs.start()
    with obs.span("outer", rid=4):
        obs.count("n")
        with obs.span("inner", start=0, n=8):
            obs.count("n", 2)
            obs.count("tokens", 8)
        with obs.span("inner2"):
            pass
    with obs.span("after"):
        obs.count("f", 0.5)
    rec = obs.stop()
    assert [(s.name, s.parent, s.attrs) for s in rec.spans] == [
        ("outer", -1, {"rid": 4}), ("inner", 0, {"start": 0, "n": 8}),
        ("inner2", 0, {}), ("after", -1, {})]
    assert rec.counters == {"n": 3, "tokens": 8, "f": 0.5}
    outer, inner, inner2, after = rec.spans
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= inner2.start_ns <= inner2.end_ns <= outer.end_ns \
        <= after.start_ns <= after.end_ns
    assert [s.name for s in rec.children(0)] == ["inner", "inner2"]
    # every span also entered the profiler's annotation, with its attrs
    assert annotations == [("outer", {"rid": 4}),
                           ("inner", {"start": 0, "n": 8}),
                           ("inner2", {}), ("after", {})]
    # stop() cleared them
    assert obs.stop() == obs.Records()
    with obs.span("late"):
        obs.count("n")
    assert obs.stop() == obs.Records()


def test_span_closes_on_error():
    obs.start()
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError
    with obs.span("next"):
        pass
    rec = obs.stop()
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("outer", -1), ("inner", 0), ("next", -1)]
    assert all(s.end_ns >= s.start_ns for s in rec.spans)


def test_spans_reach_a_profile_with_the_recorder_off(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: x * 2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("engine.prefill_chunk", start=16, n=8):
            f(jnp.ones(3)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert obs.stop() == obs.Records()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with warnings.catch_warnings():
        # the profile reader's stats type warns as it is first built
        warnings.simplefilter("ignore", DeprecationWarning)
        found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name == "engine.prefill_chunk"]
    assert found == [{"start": 16, "n": 8}]


def test_compile_counter_names_the_span():
    f = jax.jit(lambda x: x + 1)
    obs.start()
    with obs.span("first"):
        f(jnp.ones(5))
    with obs.span("again"):
        f(jnp.ones(5))
    c = obs.stop().counters
    assert c["jax.compiles"] >= 1 and c["jax.compile_s"] > 0
    assert c["jax.compiles@first"] >= 1
    assert "jax.compiles@again" not in c


# -------------------------------------------------------------- serving --

def test_engine_off_records_nothing(smoke_lm, annotations):
    M, p, q, cfg = smoke_lm
    handles = _serve(smoke_lm, _requests(cfg.vocab, [5, 9], [3, 4]))
    assert all(h.done for h in handles)
    assert annotations == []
    assert obs.stop() == obs.Records()


def test_engine_spans_and_counters(smoke_lm):
    M, p, q, cfg = smoke_lm
    lens, max_news = [5, 16, 23, 1, 12], [3, 6, 2, 4, 1]
    obs.start()
    handles = _serve(smoke_lm, _requests(cfg.vocab, lens, max_news))
    rec = obs.stop()
    spans = rec.spans
    submits = [i for i, s in enumerate(spans) if s.name == "engine.submit"]
    assert [spans[i].attrs["prompt"] for i in submits] == lens
    assert [spans[i].attrs["rid"] for i in submits] == [1, 2, 3, 4, 5]
    assert [h.rid for h in handles] == [1, 2, 3, 4, 5]
    for i, plen in zip(submits, lens):
        kids = rec.children(i)
        chunks = [s for s in kids if s.name == "engine.prefill_chunk"]
        assert len(chunks) == _chunks(plen, 8)
        assert sum(s.attrs["n"] for s in chunks) == plen
        assert [s.attrs["start"] for s in chunks] == [
            sum(c.attrs["n"] for c in chunks[:k])
            for k in range(len(chunks))]
        assert [s.name for s in kids[len(chunks):]] == [
            "engine.splice", "engine.first_token"]
    steps = [i for i, s in enumerate(spans) if s.name == "engine.step"]
    for i in steps:
        assert spans[i].parent == -1
        assert [s.name for s in rec.children(i)] == STEP_CHILDREN
    c = rec.counters
    assert c["engine.ticks"] == len(steps) > 0
    assert c["engine.slot_ticks"] == sum(spans[i].attrs["active"]
                                         for i in steps)
    # every token after the first comes from one slot's tick
    assert c["engine.slot_ticks"] == sum(max_news) - len(lens)
    assert c["engine.admitted"] == c["engine.finished"] == len(lens)
    assert c["engine.prefill_chunks"] == sum(_chunks(n, 8) for n in lens)
    assert c["engine.prefill_tokens"] == sum(lens)
    assert "engine.prefix_hits" not in c


def test_engine_counts_prefix_hits(smoke_lm):
    M, p, q, cfg = smoke_lm
    reqs = _requests(cfg.vocab, [6, 6], [2, 2])
    reqs[1].prompt = list(reqs[0].prompt)
    obs.start()
    _serve(smoke_lm, reqs, prefix_reuse=True)
    rec = obs.stop()
    assert rec.counters["engine.prefix_hits"] == 1
    # the second admission prefilled nothing
    assert rec.counters["engine.prefill_tokens"] == 6


def test_engine_serves_the_same_tokens_recorded(smoke_lm):
    M, p, q, cfg = smoke_lm
    lens, max_news = [5, 16, 23, 3], [4, 6, 3, 5]
    off = _serve(smoke_lm, _requests(cfg.vocab, lens, max_news,
                                     sampled={1, 3}))
    obs.start()
    on = _serve(smoke_lm, _requests(cfg.vocab, lens, max_news,
                                    sampled={1, 3}))
    assert obs.stop().counters["engine.admitted"] == len(lens)
    assert [h.out for h in on] == [h.out for h in off]


# ------------------------------------------------------------- training --

def _train_setup():
    spec = RunSpec()
    spec = dataclasses.replace(
        spec, train=dataclasses.replace(spec.train, steps=3),
        data=dataclasses.replace(spec.data, batch=2, seq=8))
    ctx = build(spec)
    return ctx, ctx.init_training()


def test_train_step_spans_and_compiles():
    ctx, setup = _train_setup()
    obs.start()
    with ctx.mesh:
        setup.step(0)
    first = obs.stop()
    assert [(s.name, s.parent) for s in first.spans] == [
        ("train.step", -1), ("train.batch", 0), ("train.dispatch", 0)]
    assert first.counters["train.steps"] == 1
    assert first.counters["jax.compiles@train.dispatch"] >= 1
    obs.start()
    with ctx.mesh:
        jax.block_until_ready(setup.step(1))
    second = obs.stop()
    assert [s.name for s in second.spans] == [
        "train.step", "train.batch", "train.dispatch"]
    assert "jax.compiles" not in second.counters


def test_train_step_off_records_nothing(annotations):
    ctx, setup = _train_setup()
    with ctx.mesh:
        setup.step(0)
    assert annotations == []
    assert obs.stop() == obs.Records()


def test_programs_have_stable_names(smoke_lm):
    M, p, q, cfg = smoke_lm
    eng = Engine(M, p, q, cfg, batch_slots=2, max_len=16)
    assert [f.__name__ for f in (eng._decode, eng._prefill, eng._write_slot,
                                 eng._sample1)] == [
        "engine_decode", "engine_prefill", "engine_splice",
        "engine_first_token"]
    _, hlo = eng.decode_program()
    assert hlo.startswith("HloModule jit_engine_decode")
    ctx, setup = _train_setup()
    assert setup.jitted.__name__ == "train_step"
