"""What every driver shares: the cell's files, the device, the program's
model, the benchmark's weights, host spans, the traced window and the
result line.

A cell is found by name: ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``, with its plain reference beside it)
and its traffic (``bench/traffic/<traffic>.json``), whose ``driver`` key
names ``bench/drivers/<driver>.py``.  Per-layer metrics are read by
``bench/metrics/<metric>.py``; peaks come from
``bench/peaks/<device_kind>.json``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# by path: the standard library has a module of the same name
trace_lib = load_module(os.path.join(HERE, "trace.py"), "bench_trace")


def file_name(name: str) -> str:
    """A name as it appears in a file name (letters, digits, _ . -)."""
    return "".join(c if c.isalnum() or c in "_.-" else "_" for c in name)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


class Spans:
    """Host spans of the benchmark's own calls into the program, timed by
    the host clock and mirrored into the profiler's trace."""

    def __init__(self):
        self.items: List[tuple] = []          # (name, start, end, info)

    @contextlib.contextmanager
    def span(self, name: str, info=None):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter(), info))

    def between(self, name: str, t0: float, t1: float) -> List[tuple]:
        return [s for s in self.items if s[0] == name and t0 <= s[1] < t1]


class Harness:
    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, t_start: float):
        self.root, self.seed, self.seconds = root, seed, seconds
        self.trace, self.t_start = trace, t_start
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            self.bench = json.load(fh)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"bench: no workload {workload!r} in "
                             f"BENCHMARK.json")
        self.cell = cells[workload]
        bdir = os.path.join(root, "bench")
        with open(os.path.join(bdir, "configs",
                               f"{self.cell['config']}.json")) as fh:
            self.config = json.load(fh)
        self.ref_path = os.path.join(bdir, "configs",
                                     self.config["reference"])
        sys.path.insert(0, bdir)
        import traffic
        self.mix = traffic.load(self.cell["traffic"],
                                os.path.join(bdir, "traffic"))
        self.driver_path = os.path.join(bdir, "drivers",
                                        f"{self.mix['driver']}.py")
        self.spans = Spans()
        self.out_dir = os.path.join(root, "chiprun_out", "bench",
                                    workload, f"seed{seed}")
        self.devices = None
        self.peaks = None
        self.memory_peak = None
        self.trace_red = None
        self.trace_window = None              # (t0, t1) host clock
        self._annotation = None
        self._t_trace = None

    # ------------------------------------------------------------ device --
    def require_chips(self) -> None:
        """Exit non-zero, before any work, unless JAX sees the cell's TPU
        chips; then load the device kind's peaks (none is an error)."""
        import jax
        devs = jax.devices()
        need = self.cell["chips"]
        if devs[0].platform != "tpu" or len(devs) < need:
            print(f"bench: {self.cell['name']} needs {need} TPU chip(s); "
                  f"JAX sees {len(devs)} {devs[0].platform} device(s)",
                  file=sys.stderr)
            sys.exit(3)
        self.devices = devs[:need]
        kind = devs[0].device_kind
        path = os.path.join(self.root, "bench", "peaks",
                            f"{file_name(kind)}.json")
        if not os.path.exists(path):
            print(f"bench: no peaks for device kind {kind!r} ({path})",
                  file=sys.stderr)
            sys.exit(3)
        with open(path) as fh:
            self.peaks = json.load(fh)

    def device_record(self) -> Dict:
        d = self.devices[0]
        rec = {"platform": d.platform, "kind": d.device_kind,
               "count": len(self.devices),
               "memory_peak_bytes": self.memory_peak}
        if self.trace_red is not None:
            rec["busy_s"] = self.trace_red["busy_s"]
            rec["window_s"] = self.trace_red["window_s"]
        return rec

    def read_memory(self) -> None:
        """The peak on the fullest chip; read once the window has closed,
        before the reference runs."""
        stats = [d.memory_stats() for d in self.devices]
        if all(stats):                      # the CPU reports none
            self.memory_peak = max(s["peak_bytes_in_use"] for s in stats)

    @staticmethod
    def free() -> None:
        import jax
        gc.collect()
        jax.clear_caches()

    # ------------------------------------------------------------- model --
    def reference(self):
        return load_module(self.ref_path, "bench_reference")

    def runspec(self):
        """The configuration's RunSpec, seeded by ``--seed``."""
        from repro.api import RunSpec
        d = dict(self.config["runspec"])
        d["seed"] = self.seed % (1 << 31)
        return RunSpec.from_dict(d)

    def weights(self, ctx):
        """The benchmark's weights for the program's model, made on the
        device in one jitted call from the seed, in the program's tree."""
        import jax
        import traffic
        ref = self.reference()
        cfg = self.config
        p, q = jax.jit(lambda k: ref.make_weights(k, cfg))(
            traffic.seed_key(self.seed, 0))
        want_p, want_q = jax.eval_shape(
            lambda: ctx.model.init(jax.random.PRNGKey(0), ctx.cfg))
        for mine, want, what in ((p, want_p, "params"), (q, want_q,
                                                         "qstate")):
            a = [(x.shape, x.dtype) for x in jax.tree.leaves(mine)]
            b = [(x.shape, x.dtype) for x in jax.tree.leaves(want)]
            if a != b:
                raise RuntimeError(f"bench: the program's {what} tree "
                                   f"differs from the benchmark's")
        return (jax.tree.unflatten(jax.tree.structure(want_p),
                                   jax.tree.leaves(p)),
                jax.tree.unflatten(jax.tree.structure(want_q),
                                   jax.tree.leaves(q)))

    def check_sizes(self, mcfg) -> None:
        """The program's model config matches the configuration file."""
        want = self.reference().program_config(self.config)
        got = {k: getattr(mcfg, k) for k in want}
        if got != want:
            raise RuntimeError(f"bench: program config {got} is not the "
                               f"configuration's {want}")

    # ------------------------------------------------------------- trace --
    def trace_start(self) -> None:
        """Start profiling the window (``--trace 1`` only)."""
        if not self.trace or self._annotation is not None:
            return
        import jax
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
        self._annotation.__enter__()
        self._t_trace = time.perf_counter()

    def trace_stop(self) -> None:
        """Stop profiling; the traced part of the window ends here."""
        if self._annotation is None or self.trace_window is not None:
            return
        import jax
        self._annotation.__exit__(None, None, None)
        self.trace_window = (self._t_trace, time.perf_counter())
        jax.profiler.stop_trace()

    def reduce_trace(self) -> None:
        if not self.trace:
            return
        path = trace_lib.newest_xplane(self.out_dir)
        if path is not None:
            self.trace_red = trace_lib.reduce(path)

    # ------------------------------------------------------------ output --
    def say(self, msg: str) -> None:
        print(f"bench: {msg}", flush=True)
