"""A checkout of the benchmark at the SMOKE sizes, for runs on the CPU.

``make_checkout(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` to
``tmp/checkout``, with the qwen2-0.5b configuration cut to the program's
SMOKE model and short mixes; ``harness(tmp, cell, seed)`` gives the
drivers a harness on the CPU with the v5e's peaks."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

PUBLISHED = {"hidden_size": 896, "num_attention_heads": 14,
             "num_key_value_heads": 2, "intermediate_size": 4864,
             "rope_theta": 1000000.0}
SMOKE = {"num_hidden_layers": 2, "hidden_size": 56,
         "num_attention_heads": 14, "num_key_value_heads": 2,
         "intermediate_size": 112, "vocab_size": 256, "rope_theta": 10000.0}


def make_checkout(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cdir = os.path.join(root, "bench", "configs")
    with open(os.path.join(cdir, "qwen2-0.5b.json")) as fh:
        cfg = json.load(fh)
    cfg.update(SMOKE)
    cfg["runspec"]["full"] = False
    cfg["runspec"]["serving"]["slots"] = 4
    cfg["runspec"]["train"]["log_every"] = 2
    with open(os.path.join(cdir, "qwen2-0.5b.json"), "w") as fh:
        json.dump(cfg, fh)
    tdir = os.path.join(root, "bench", "traffic")
    _edit(os.path.join(tdir, "train.b8s512.json"),
          batch=4, seq=32, trace_seconds=1)
    _edit(os.path.join(tdir, "serve.decode-heavy.json"), rate_per_s=4.0,
          prompt={"median": 12, "sigma": 0.5, "min": 4, "max": 40},
          output={"median": 8, "sigma": 0.5, "min": 4, "max": 20},
          max_len=64, warmup_s=1, trace_seconds=1, check_tokens=40)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def _edit(path: str, **kw) -> None:
    with open(path) as fh:
        mix = json.load(fh)
    mix.update(kw)
    with open(path, "w") as fh:
        json.dump(mix, fh)


def harness(root: str, cell: str, seed: int, seconds: float = 2.0,
            trace: bool = False):
    """A harness on the CPU: the drivers' view of one run, minus the
    refusal to run without a TPU."""
    import jax
    sys.path.insert(0, os.path.join(root, "bench"))
    from harness import Harness
    h = Harness(root, cell, seed, seconds, trace, time.perf_counter())
    h.devices = jax.devices()[:1]
    with open(os.path.join(root, "bench", "peaks",
                           "TPU_v5_lite.json")) as fh:
        h.peaks = json.load(fh)
    return h
