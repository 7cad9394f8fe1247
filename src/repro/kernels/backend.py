"""Backend dispatch shared by every Pallas kernel family.

On TPU the compiled kernels are the fast path.  Elsewhere each family's
jnp reference is: XLA already fuses those elementwise chains on CPU/GPU,
where interpret-mode Pallas would only add overhead.  The overrides
exist so tests can force the kernel route (interpreted) and pin it
against the reference on any backend.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def use_fused_kernel() -> bool:
    """True when the compiled Pallas fast path should run (TPU)."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas execution mode for the current backend: compiled on TPU,
    interpreted elsewhere (the kernels use TPU VMEM/SMEM semantics)."""
    return not use_fused_kernel()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> :func:`default_interpret`; a bool overrides."""
    return default_interpret() if interpret is None else interpret


def resolve(use_kernel: Optional[bool], interpret: Optional[bool]
            ) -> Tuple[bool, bool]:
    """(use the kernel?, interpret it?) with ``None`` resolved per
    backend."""
    if use_kernel is None:
        use_kernel = use_fused_kernel()
    return use_kernel, resolve_interpret(interpret)
