"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (correctness
only; microseconds are meaningless for TPU).  We therefore time the XLA
reference path (what the kernel replaces) for the CSV and report the
kernel's *structural* numbers — VMEM working set per tile and bytes moved —
which is what the TPU perf model consumes (EXPERIMENTS.md SSRoofline)."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.kernels.hgq_quantize.kernel import DEFAULT_BLOCK_ROWS, LANE
from repro.kernels.hgq_quantize.ref import hgq_quantize_ref
from repro.kernels.qmatmul.ops import PAD_BLOCK_K, qmatmul_tiles
from repro.kernels.qmatmul.ref import pack_ref, qmatmul_ref

from .common import emit, time_call


def qmatmul_vmem_tile_bytes(M: int, K: int, N: int) -> int:
    """VMEM working set of one grid step of the int8 ``qmatmul`` at these
    shapes, with the tiles ``qmatmul_any`` picks: the f32 x block, the
    int8 weight block and the f32 output/accumulator block."""
    bm, bn, bk = qmatmul_tiles(M, K, N, False)
    bk = bk or PAD_BLOCK_K   # no fitting divisor: qmatmul_any pads K
    return bm * bk * 4 + bk * bn + bm * bn * 4


def bench_kernels() -> List[str]:
    lines = []
    key = jax.random.PRNGKey(0)
    # hgq_quantize: weight-sized and activation-sized operands
    for name, shape in (("weight_4kx4k", (4096, 4096)),
                        ("act_16x4096x896", (16 * 4096, 896))):
        x = jax.random.normal(key, shape)
        f = jnp.full((shape[-1],), 6.0)
        fn = jax.jit(lambda x, f: hgq_quantize_ref(x, f[None, :]))
        us = time_call(fn, x, f)
        vmem_tile = DEFAULT_BLOCK_ROWS * ((shape[-1] + LANE - 1) // LANE
                                          ) * LANE * 4 * 2
        lines.append(emit(f"kernel.hgq_quantize.{name}", us,
                          f"bytes={x.size * 8};vmem_tile_bytes={vmem_tile};"
                          f"xla_ref_timing=True"))
    # qmatmul: decode-like (M small) and prefill-like (M big)
    for name, (M, K, N) in (("decode_8x2048x7168", (8, 2048, 7168)),
                            ("prefill_2048x2048x2048", (2048, 2048, 2048))):
        x = jax.random.normal(key, (M, K), jnp.float32)
        w = jax.random.normal(key, (K, N)) * 0.05
        wi, s = pack_ref(w, jnp.full((N,), 6.0))
        fn = jax.jit(qmatmul_ref)
        us = time_call(fn, x, wi, s)
        flops = 2.0 * M * K * N
        int8_bytes = K * N + 4 * N
        bf16_bytes = 2 * K * N
        vmem = qmatmul_vmem_tile_bytes(M, K, N)
        lines.append(emit(
            f"kernel.qmatmul.{name}", us,
            f"flops={flops:.3g};weight_bytes_int8={int8_bytes};"
            f"weight_bytes_bf16={bf16_bytes};"
            f"hbm_saving={bf16_bytes / int8_bytes:.2f}x;"
            f"vmem_tile_bytes={vmem}"))
    return lines
