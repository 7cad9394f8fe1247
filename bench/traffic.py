"""Traffic: the one generator that reads every mix under ``bench/traffic``.

A mix is a JSON file of parameters.  ``"base"`` names another mix whose
keys it starts from, so two cells can share a mix at different rates.

Serving mixes are open-loop schedules.  Every seed gets the same
inter-arrival gaps, prompt lengths and output lengths (quantiles of the
mix's distributions) in the same order, with token ids drawn from the
seed: a request lives for up to a window, so an order drawn from the
seed would change which work falls inside it.
Training mixes give the batch shape; the batches come from
``train_batch``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")


def load(name: str, traffic_dir: str = TRAFFIC_DIR) -> Dict:
    """The mix ``name`` with its ``base`` chain resolved."""
    with open(os.path.join(traffic_dir, f"{name}.json")) as fh:
        mix = json.load(fh)
    base = mix.pop("base", None)
    if base is None:
        return mix
    out = load(base, traffic_dir)
    out.update(mix)
    return out


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of ``seed`` (any non-negative int)."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def seed_key(seed: int, stream: int):
    """A JAX PRNG key for one use of ``seed`` (any int below 2**64)."""
    import jax
    key = jax.random.PRNGKey(stream)
    return jax.random.fold_in(jax.random.fold_in(key, seed & 0xFFFFFFFF),
                              seed >> 32)


@dataclasses.dataclass
class Arrival:
    due: float           # seconds from the start of the window
    prompt: List[int]
    max_new: int


def _quantiles(n: int, inv_cdf) -> np.ndarray:
    return np.array([inv_cdf((i + 0.5) / n) for i in range(n)])


def _lognormal(n: int, spec: Dict) -> np.ndarray:
    nd = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    x = np.exp(_quantiles(n, nd.inv_cdf))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(mix: Dict, seed: int, start: float, span: float, vocab: int,
             stream: int = 0) -> List[Arrival]:
    """Requests due in ``[start, start + span)`` at ``mix["rate_per_s"]``.

    ``round(rate * span)`` requests; Poisson gaps as exponential
    quantiles scaled to fill the span, lognormal prompt and output
    lengths as quantiles clipped to the mix's bounds; each of the three
    sets shuffled on its own, the same way for every seed, and the
    prompts' token ids drawn from the seed.  Greedy requests only."""
    if mix.get("arrivals", "poisson") != "poisson" or \
            mix.get("sampling", "greedy") != "greedy":
        raise ValueError(f"the generator makes greedy Poisson traffic, not "
                         f"{mix.get('arrivals')} / {mix.get('sampling')}")
    n = max(1, round(mix["rate_per_s"] * span))
    order = np.random.default_rng(stream)
    gaps = _quantiles(n, lambda u: -math.log(1.0 - u))
    gaps = order.permutation(gaps * (span / gaps.sum()))
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    plens = order.permutation(_lognormal(n, mix["prompt"]))
    olens = order.permutation(_lognormal(n, mix["output"]))
    rng = seed_rng(seed, stream)
    return [Arrival(float(t), rng.integers(0, vocab, int(p)).tolist(), int(o))
            for t, p, o in zip(due, plens, olens)]


def train_batch(key, step, batch: int, seq: int, vocab: int):
    """One training batch, a pure function of (key, step): a Markov-ish
    token stream whose next token follows the current one 70% of the
    time, so the loss can fall below log(vocab).  Traced by jit; the
    key is ``seed_key(seed, 1)``."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.fold_in(key, step))
    base = jax.random.randint(k1, (batch, seq), 0, vocab)
    shifted = jnp.roll(base, 1, axis=1) * 31 % vocab
    use_rule = jax.random.bernoulli(k2, 0.7, (batch, seq))
    return jnp.where(use_rule, shifted, base).astype(jnp.int32)
