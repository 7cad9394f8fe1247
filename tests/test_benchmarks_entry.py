"""benchmarks/run.py — the paper-tables entry point (``python -m
benchmarks.run --only ...``).

It imports every module it drives whatever ``--only`` names, so a name
that one of them imports from ``repro`` and that is renamed or removed
there breaks every table at import time.  These tests import each such
module, and check the structural numbers ``kernel_bench`` reports."""
import importlib
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("name", ["run", "kernel_bench", "paper_tables",
                                  "roofline_table"])
def test_benchmark_module_imports(name):
    importlib.import_module(f"benchmarks.{name}")


@pytest.mark.parametrize("M,K,N", [(8, 2048, 7168), (2048, 2048, 2048),
                                   (1, 896, 4864), (16, 200, 48)])
def test_kernel_bench_qmatmul_vmem_tile(M, K, N):
    """The VMEM tile ``kernel_bench`` reports is that of the tiles
    ``qmatmul_any`` picks: at least one row of x and a 128-wide weight
    block, and within a v5e core's 16 MiB of VMEM."""
    from benchmarks.kernel_bench import qmatmul_vmem_tile_bytes
    from repro.kernels.qmatmul.ops import PAD_BLOCK_K, qmatmul_tiles
    bm, bn, bk = qmatmul_tiles(M, K, N, False)
    vmem = qmatmul_vmem_tile_bytes(M, K, N)
    assert min(N, 128) * min(K, bk or PAD_BLOCK_K) < vmem <= 16 << 20
