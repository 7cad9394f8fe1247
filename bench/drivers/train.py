"""Training driver: the HGQ train step, back to back on fresh batches.

Set-up builds the program's ``TrainSetup`` (``RunContext.init_training``),
puts the benchmark's weights from the seed into it, feeds it the
benchmark's batches and runs steps 0-2 through its own ``step``: they
compile (or load) the step and give the readings that the reference
checks.  The window then continues the same object from step 3 for
``--seconds``; the host reads the loss every ``train.log_every`` steps
of the configuration, as a job does, and the window ends at
``block_until_ready`` of the last step.  With ``--trace 1`` the steps of
the mix's ``trace_seconds`` are profiled first and the trace written;
the window starts after that.

Once the window has closed the program's state is freed and the plain
reference repeats steps 0-2 from the same weights and batches.  Checks,
each against its limit in the mix:

* ``grad_gap``: the first clipped gradient as the optimizer got it (the
  first moment after one step over 1 - b1), by the worst leaf: the gap
  of the two norms over the larger of the reference's leaf norm and its
  median leaf norm;
* ``change_gap``: the parameters' change over the three steps, the same
  way, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (Adam moves them by round-off alone);
* ``grad_gap_median``, ``change_gap_median``: the same gaps of the
  median leaf instead of the worst.

Numbers the mix gives no limit are printed and not compared, as is
``loss_gap``, the largest relative gap of the three steps' losses.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time

ADAM_B1 = 0.9


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for a in jax.tree.leaves(t)])(tree)]


def _diff_norms(a, b):
    import jax
    return _leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


def _leaf_gaps(prog, ref, keep=None):
    med = statistics.median(ref)
    idx = range(len(ref)) if keep is None else keep
    return {i: abs(prog[i] - ref[i]) / max(ref[i], med, 1e-30) for i in idx}


def gaps(run, ref_run):
    """The numbers compared between two runs of steps 0-2, each (losses,
    first-gradient leaf norms, change leaf norms): per kept leaf |prog -
    ref| / max(ref, median(ref)), of the worst leaf and of the median."""
    (losses, g0, dp), (r_losses, r_g0, r_dp) = run, ref_run
    med = statistics.median(r_g0)
    keep = [i for i, g in enumerate(r_g0) if g >= 1e-3 * med]
    grad = list(_leaf_gaps(g0, r_g0).values())
    change = list(_leaf_gaps(dp, r_dp, keep).values())
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, r_losses)),
            "grad_gap": max(grad), "change_gap": max(change),
            "grad_gap_median": statistics.median(grad),
            "change_gap_median": statistics.median(change),
            "leaves_left_out": len(r_g0) - len(keep)}


def worst_leaf(prog, ref, keep=None) -> int:
    gaps = _leaf_gaps(prog, ref, keep)
    return max(gaps, key=gaps.get)


def run(h):
    import jax
    import jax.numpy as jnp
    import traffic
    import work
    from repro.api import build
    from repro.data.synthetic import DataSpec

    mix = h.mix
    B, S = mix["batch"], mix["seq"]
    log_every = h.config["runspec"]["train"]["log_every"]
    ref = h.reference()
    spec = h.runspec()
    spec = dataclasses.replace(spec, data=DataSpec(
        kind="lm", batch=B, seq=S, vocab=0, seed=spec.seed))
    ctx = build(spec)
    h.check_sizes(ctx.cfg)
    vocab = ctx.cfg.vocab

    setup = ctx.init_training()
    params, qstate = h.weights(ctx)
    setup.params = jax.device_put(params, jax.tree.map(
        lambda a: a.sharding, setup.params))
    setup.qstate = jax.device_put(qstate, jax.tree.map(
        lambda a: a.sharding, setup.qstate))
    del params, qstate
    key = traffic.seed_key(h.seed, 1)
    feed = jax.jit(lambda k, s: {"tokens": traffic.train_batch(
        k, s, B, S, vocab)})
    setup.pipeline = lambda step: feed(key, jnp.int32(step))

    # steps 0-2: compile, then the readings the reference checks
    losses = [float(setup.step(0)["loss"])]
    g0 = [n / (1.0 - ADAM_B1) for n in _leaf_norms(setup.opt.mu)]
    losses += [float(setup.step(s)["loss"]) for s in (1, 2)]
    p0, _ = h.weights(ctx)
    dp = _diff_norms(setup.params, p0)
    del p0
    jax.block_until_ready(setup.params)

    def steps_for(seconds, step, last):
        """Steps from ``step`` until ``seconds`` have passed: the last
        step's metrics, the steps run, the seconds to the end of the
        last, and the last loss logged."""
        t0, n = time.perf_counter(), 0
        while True:
            with h.spans.span("bench.train_step"):
                m = setup.step(step + n)
            n += 1
            if (step + n) % log_every == 0:
                last = float(m["loss"])
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(setup.params)
        return m, n, time.perf_counter() - t0, last

    step, last = 3, None
    if h.trace:                 # the traced steps, then the trace written
        h.trace_start()
        m, n, _, last = steps_for(mix["trace_seconds"], step, last)
        h.trace_stop()
        step += n
    setup_s = time.perf_counter() - h.t_start
    m, n, window_s, last = steps_for(h.seconds, step, last)
    final = float(m["loss"])
    tokens_per_s = n * B * S / window_s
    h.say(f"train: {n} steps of {B} x {S} in {window_s:.3f} s; loss "
          f"{losses[0]:.4f} -> {final:.4f} (last logged {last})")
    h.read_memory()
    del setup, m
    h.free()

    # the reference: steps 0-2 from the same weights and batches
    cfg = h.config
    tcfg = dict(cfg["runspec"]["train"])
    p0 = jax.jit(lambda k: ref.make_weights(k, cfg)[0])(
        traffic.seed_key(h.seed, 0))
    batches = [feed(key, jnp.int32(s))["tokens"] for s in range(3)]
    t_ref = time.perf_counter()
    r_losses, r_g0n, r_dp = ref.train_steps(p0, batches, cfg, tcfg)
    h.say(f"train: reference took {time.perf_counter() - t_ref:.1f} s")
    got = gaps((losses, g0, dp), (r_losses, r_g0n, r_dp))
    lim = mix["limits"]
    h.say(f"train: losses {losses} reference {r_losses}; " + " ".join(
        f"{k}={v!r}" for k, v in got.items() if k not in lim))
    med_g = statistics.median(r_g0n)
    keep = [i for i, g in enumerate(r_g0n) if g >= 1e-3 * med_g]
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    for what, prog, refn, idx in (("grad", g0, r_g0n, None),
                                  ("change", dp, r_dp, keep)):
        i = worst_leaf(prog, refn, idx)
        h.say(f"train: worst {what} leaf {names[i]}: program "
              f"{prog[i]!r} reference {refn[i]!r} (median leaf "
              f"{statistics.median(refn)!r})")
    finite = all(math.isfinite(x) for x in losses + [final])
    return {
        "metrics": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "attempted": n, "failed": 0 if finite else n,
        "checks": [(k, got[k], lim[k]) for k in lim],
        "record": {"kind": "train", "window_s": window_s, "steps": n,
                   "step_flops": work.train_flops(ref.model(h.config),
                                                  B, S)},
    }
