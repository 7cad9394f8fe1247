"""Operations and bytes that each kernel call and each model step need,
from logical shapes and the widths the configuration stores.

These count the work the algorithm requires, not what an implementation
moves: padding, inactive batch rows, a nibble unpack or a transposed
copy add nothing.  So a faster implementation of the same work reads as
a higher roofline share, and no correct one can read above 100%.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple


def qmatmul(m: int, k: int, n: int, bits: int) -> Tuple[float, float]:
    """x [m, k] f32 @ packed w [k, n] with a per-column f32 scale: (FLOPs,
    bytes).  The weight is read at its stored width."""
    return 2.0 * m * k * n, k * n * bits / 8.0 + 4.0 * (m * k + m * n + n)


def kv_attention(ctx: Iterable[int], n_heads: int, n_kv: int, hd: int,
                 kv_bits: int) -> Tuple[float, float]:
    """One layer's attention read over a quantized KV cache for query rows
    whose causal contexts are ``ctx`` (tokens each row attends to):
    (FLOPs, bytes).  Each kv head's context is read once per row, as
    mantissas of ``kv_bits`` plus one exponent byte per row of k and v;
    queries and outputs are f32."""
    ctx = list(ctx)
    row_bytes = 2 * n_kv * (hd * kv_bits / 8.0 + 1.0)
    flops = sum(4.0 * n_heads * hd * c for c in ctx)
    byts = sum(c * row_bytes for c in ctx) + len(ctx) * 8.0 * n_heads * hd
    return flops, byts


def least_time(flops: float, byts: float, peaks: Dict
               ) -> Tuple[float, str]:
    """(seconds, which bound binds) of the roofline: the larger of
    operations over the bf16 peak (the MXU's rate for the kernels'
    operands) and bytes over HBM bandwidth."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# A model is described by its plain reference (``model(cfg)``): ``L``
# blocks; ``layer``, each block's matmul weights as plan path -> (k, n);
# ``head``, (path, k, n); ``qmatmul``, the paths the packed kernel runs
# (the others are XLA matmuls); ``attention``, (heads, kv heads, head
# dim) or None; ``wkv``, (heads, head dim) of a WKV recurrence or None.

def matmul_params(m: Dict) -> int:
    """Weights that multiply activations, a tied head counted once."""
    return (m["L"] * sum(k * n for k, n in m["layer"].values())
            + m["head"][1] * m["head"][2])


def train_flops(m: Dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 per matmul weight per token,
    plus causal attention's score and value products (forward 2 x 2 x hd
    per head per attended pair, backward twice that).  No recompute."""
    H, _, hd = m["attention"]
    pairs = batch * seq * (seq + 1) / 2.0
    attn = 3.0 * 4.0 * H * hd * pairs * m["L"]
    return 6.0 * matmul_params(m) * batch * seq + attn


def mixer_flops(m: Dict, ctx: int) -> float:
    """FLOPs of one block's sequence mixer for one token that attends to
    ``ctx`` tokens: attention's score and value products, or the WKV
    step (outer product, bonus, read-out and decayed update: 7 per state
    entry)."""
    if m["attention"] is not None:
        H, _, hd = m["attention"]
        return 4.0 * H * hd * ctx
    H, N = m["wkv"]
    return 7.0 * H * N * N


def decode_flops(m: Dict, ctx: Iterable[int]) -> float:
    """Model FLOPs of generating one token per row, rows attending to
    ``ctx`` tokens each."""
    ctx = list(ctx)
    return (2.0 * matmul_params(m) * len(ctx)
            + sum(mixer_flops(m, c) for c in ctx) * m["L"])


def kernel_calls(m: Dict, widths: Dict[str, int], rows: int
                 ) -> Iterable[Tuple[int, int, int, int]]:
    """The packed matmuls of one forward over ``rows`` tokens, as (m, k,
    n, bits)."""
    for path, (k, n) in m["layer"].items():
        if path in m["qmatmul"]:
            for _ in range(m["L"]):
                yield rows, k, n, widths[path]
    path, k, n = m["head"]
    if path in m["qmatmul"]:
        yield rows, k, n, widths[path]


def prefill_chunks(plen: int, chunk: int) -> Iterable[Tuple[int, int]]:
    """The engine's prefill calls for one prompt, as (start, rows): whole
    chunks, then power-of-two tails."""
    start = 0
    while start < plen:
        n = chunk if plen - start >= chunk else \
            1 << ((plen - start).bit_length() - 1)
        yield start, n
        start += n


def serve_work(m: Dict, widths: Dict[str, int], kv_bits: Optional[int],
               ticks: Iterable[Iterable[int]], prompts: Iterable[int],
               chunk: int, peaks: Dict) -> Dict[str, Dict[str, float]]:
    """Per kernel, the FLOPs, bytes, calls and summed least time (each
    call's own roofline bound, bf16 peak for the MXU) of ``qmatmul`` and
    ``kv_attention`` (attention over a cache of ``kv_bits``, where the
    model has one) over decode ticks (each the list of its active rows'
    contexts) and prompt prefills (each a prompt length, run in the
    engine's chunks; token i attends to i + 1).  A call reads its
    weights once; ``memory_bound`` counts the calls bound by HBM."""
    out = {k: {"flops": 0.0, "bytes": 0.0, "least_s": 0.0, "calls": 0,
               "memory_bound": 0} for k in ("qmatmul", "kv_attention")}

    def count(kernel, f, by):
        t, bound = least_time(f, by, peaks)
        o = out[kernel]
        o["flops"] += f
        o["bytes"] += by
        o["least_s"] += t
        o["calls"] += 1
        o["memory_bound"] += bound == "memory"

    def add(rows, ctx):
        for r, k, n, b in kernel_calls(m, widths, rows):
            count("qmatmul", *qmatmul(r, k, n, b))
        if m["attention"] is None or kv_bits is None:
            return
        f, by = kv_attention(ctx, *m["attention"], kv_bits)
        for _ in range(m["L"]):
            count("kv_attention", f, by)

    for ctx in ticks:
        ctx = list(ctx)
        if ctx:
            add(len(ctx), ctx)
    for p in prompts:
        for start, n in prefill_chunks(p, chunk):
            add(n, range(start + 1, start + n + 1))
    return out
