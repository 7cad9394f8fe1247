"""Serving driver: open-loop traffic through the continuous-batching
engine, one process, one thread.

Set-up builds the engine of the configuration (``RunContext.make_engine``)
over the benchmark's weights, warms every shape the traffic uses (decode
tick, prefill chunks of every power of two up to the chunk, the read of
the last chunk's logits at each of those sizes, the slot splice, the
first-token sample), then serves the mix's own traffic for ``warmup_s``
so that the window opens on busy slots.

The loop issues each request when it falls due (``Engine.submit``, which
prefills it and returns its first token) while a slot is free, and
otherwise ticks the engine (``Engine.step``).  Latencies count from the
due time, so a stall delays every request behind it.  After the window
it serves on until every request due in the window has its first token.

End-to-end: ``ttft_p90_ms`` over every request due in the window;
``tpot_p99_ms`` over every gap between successive tokens of a request
that ends inside the window; ``serve_tokens_per_s`` over the tokens
emitted inside the window.

Checks: a sample drawn from the seed of the finished requests, the
longest among them, holds at least ``check_tokens`` served tokens.  The
plain reference runs once over each prompt with its served tokens.  A
token's gap is how far its logit lies below the reference's best at
that position, in the row's logit standard deviations;
``max_gap_std`` is the widest (an altered token reads about 5) and
``mean_gap_std`` the mean over the sample (the control reads several
times the program).
"""
from __future__ import annotations

import collections
import time
from typing import List

import numpy as np


class Stream:
    """One request as the client sees it."""

    def __init__(self, arrival):
        self.arrival = arrival
        self.issued = None
        self.req = None
        self.times: List[float] = []        # emit time of each token

    @property
    def due(self):
        return self.arrival.due


def build_engine(h):
    """(context, engine) of the cell's configuration over the
    benchmark's weights."""
    import jax
    from repro.api import build
    ctx = build(h.runspec())
    h.check_sizes(ctx.cfg)
    params, qstate = h.weights(ctx)
    eng = ctx.make_engine(params, qstate, max_len=h.mix["max_len"],
                          prefill_chunk=h.config["engine"]["prefill_chunk"],
                          seed=h.seed % (1 << 31))
    del params
    jax.block_until_ready(eng.p)
    return ctx, eng


def warm_shapes(eng) -> None:
    """Compile (or load) every program the traffic calls: prompts of C,
    then of each power of two below C, tokens prefill in every chunk size
    the engine uses and end on each, so the first token is read from the
    logits of every chunk size."""
    from repro.serving import Request
    C = eng.prefill_chunk
    for n in [C] + [1 << k for k in range(C.bit_length()) if 1 << k < C]:
        eng.submit(Request(prompt=[1] * n, max_new=2))
        while any(r is not None for r in eng.slot_req):
            eng.step()


def serve(h, eng, arrivals, origin: float, t_end: float,
          trace_s: float = 0.0):
    """Serve ``arrivals`` (due times relative to ``origin`` on the host
    clock) until the window ``[0, t_end)`` has closed and every request
    due in it has its first token; with ``--trace 1`` profile its first
    ``trace_s`` seconds.  Returns the streams."""
    from repro.serving import Request
    clock = time.perf_counter
    streams = [Stream(a) for a in arrivals]
    pending = collections.deque()
    active: List[Stream] = []
    i = 0
    while True:
        now = clock() - origin
        if now >= 0.0:
            if now < trace_s:
                h.trace_start()
            else:
                h.trace_stop()
        while i < len(streams) and streams[i].due <= now:
            streams[i].issued = now
            pending.append(streams[i])
            i += 1
        while pending and len(active) < eng.slots:
            s = pending.popleft()
            s.req = Request(prompt=s.arrival.prompt,
                            max_new=s.arrival.max_new)
            with h.spans.span("bench.submit", len(s.req.prompt)):
                eng.submit(s.req)
            s.times.append(clock() - origin)
            if not s.req.done:
                active.append(s)
        if now >= t_end and not pending and all(
                s.times for s in streams if s.due < t_end):
            break
        if active:
            ctx = [len(s.req.prompt) + len(s.req.out) for s in active]
            with h.spans.span("bench.step", ctx):
                eng.step()
            t = clock() - origin
            for s in active:
                s.times += [t] * (len(s.req.out) - len(s.times))
            active = [s for s in active if not s.req.done]
        elif not pending:
            if i >= len(streams):
                if now >= t_end:
                    break
                time.sleep(min(0.001, t_end - now))
                continue
            time.sleep(max(0.0, min(streams[i].due - now, 0.001)))
    h.trace_stop()
    return streams


def window_metrics(streams, t_end: float):
    """End-to-end metrics and the generator's report over ``[0, t_end)``."""
    from harness import percentile
    due = [s for s in streams if 0.0 <= s.due < t_end]
    ttft = [(s.times[0] - s.due) * 1e3 for s in due]
    gaps = [(b - a) * 1e3 for s in streams
            for a, b in zip(s.times, s.times[1:]) if 0.0 <= b < t_end]
    tokens = sum(1 for s in streams for t in s.times if 0.0 <= t < t_end)
    late = [(s.issued - s.due) * 1e3 for s in due]
    return {
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p99_ms": percentile(gaps, 99) if gaps else None,
        "serve_tokens_per_s": tokens / t_end,
    }, {
        "requests_due": len(due), "first_tokens": len(ttft),
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p80_ms": percentile(ttft, 80),
        "gaps": len(gaps), "tokens": tokens,
        "late_p50_ms": percentile(late, 50), "late_p99_ms":
            percentile(late, 99), "late_max_ms": max(late),
    }


def sample(streams, seed: int, want_tokens: int):
    """The finished requests to check: the longest, then others in an
    order drawn from the seed, until ``want_tokens`` served tokens."""
    import traffic
    done = [s for s in streams if s.req is not None and s.req.done]
    done.sort(key=lambda s: -len(s.req.out))
    pick, n = [done[0]], len(done[0].req.out)
    rng = traffic.seed_rng(seed, 9)
    for j in rng.permutation(np.arange(1, len(done))):
        if n >= want_tokens:
            break
        pick.append(done[j])
        n += len(done[j].req.out)
    return [(s.req.prompt, list(s.req.out)) for s in pick]


def reference_gaps(h, reqs, control=None):
    """Per request, the gap of each served token below the reference's
    best logit in row standard deviations.  With ``control`` (a matmul
    operand dtype) the token checked at each position is the one the
    reference at that precision ranks first instead."""
    import jax
    import jax.numpy as jnp
    import traffic
    ref = h.reference()
    cfg = h.config
    plan = cfg["runspec"]["plan"]
    T = h.mix["max_len"]
    p = jax.jit(lambda k: ref.make_weights(k, cfg)[0])(
        traffic.seed_key(h.seed, 0))
    f32 = ref.serve_logits_fn(cfg, plan)
    low = None if control is None else \
        ref.serve_logits_fn(cfg, plan, control)

    @jax.jit
    def gaps(p, toks, pos, served):
        lg = f32(p, toks)[pos]
        top = jnp.max(lg, -1)
        if low is not None:
            served = jnp.argmax(low(p, toks)[pos], -1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        return (top - got) / jnp.std(lg, -1)

    R = max(len(out) for _, out in reqs)
    out = []
    for prompt, served in reqs:
        seq = prompt + served[:-1]
        toks = np.zeros((1, T), np.int32)
        toks[0, :len(seq)] = seq
        P, n = len(prompt), len(served)
        pos = np.full(R, P - 1, np.int32)
        pos[:n] = np.arange(P - 1, P - 1 + n)
        tgt = np.zeros(R, np.int32)
        tgt[:n] = served
        g = gaps(p, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tgt))
        out.append(np.asarray(g)[:n])
    return out


def run(h):
    import traffic
    import work

    mix = h.mix
    ctx, eng = build_engine(h)
    warm_shapes(eng)
    vocab = ctx.cfg.vocab
    warm = mix["warmup_s"]
    T = h.seconds
    arrivals = (traffic.schedule(mix, h.seed, -warm, warm, vocab, 1)
                + traffic.schedule(mix, h.seed, 0.0, T, vocab, 2))
    origin = time.perf_counter() + warm
    h.say(f"serve: {len(arrivals)} requests at {mix['rate_per_s']} req/s, "
          f"{warm} s of warm-up traffic, then {T} s measured")
    setup_s = origin - h.t_start
    streams = serve(h, eng, arrivals, origin, T,
                    min(T, mix["trace_seconds"]))
    metrics, gen = window_metrics(streams, T)
    metrics["setup_s"] = setup_s
    h.say("generator: " + " ".join(f"{k}={v!r}" for k, v in gen.items()))

    # host spans of the window (with --trace 1, of its traced part), and
    # the work of the calls in them
    w0, w1 = h.trace_window or (origin, origin + T)
    steps = h.spans.between("bench.step", w0, w1)
    subs = h.spans.between("bench.submit", w0, w1)
    ref = h.reference()
    m = ref.model(h.config)
    rec = {"kind": "serve", "window_s": w1 - w0,
           "step_s": [b - a for _, a, b, _ in steps],
           "submit_s": [b - a for _, a, b, _ in subs],
           "decode_flops": sum(work.decode_flops(m, c)
                               for _, _, _, c in steps)}
    if h.trace_window is not None:
        plan = h.config["runspec"]["plan"]
        width = ref.plan_widths(plan)
        rec["work"] = work.serve_work(
            m, {n: width(n) for n in list(m["layer"]) + [m["head"][0]]},
            ref.kv_width(plan), [c for _, _, _, c in steps],
            [c for _, _, _, c in subs], eng.prefill_chunk, h.peaks)

    h.read_memory()
    reqs = sample(streams, h.seed, mix["check_tokens"])
    del eng, ctx
    h.free()
    t_ref = time.perf_counter()
    g = np.concatenate(reference_gaps(h, reqs))
    h.say(f"serve: reference over {len(reqs)} requests, {len(g)} tokens, "
          f"took {time.perf_counter() - t_ref:.1f} s; "
          f"{int((g > 0).sum())} tokens below its best")
    lim = mix["limits"]
    return {"metrics": metrics,
            "attempted": gen["requests_due"],
            "failed": gen["requests_due"] - gen["first_tokens"],
            "checks": [("max_gap_std", float(g.max()), lim["max_gap_std"]),
                       ("mean_gap_std", float(g.mean()),
                        lim["mean_gap_std"])],
            "record": rec}

