"""Pallas kernel layer: decode attention, MoE grouped GEMM, SSM scan.

OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY for compute
hot-spots the paper itself optimizes with a custom kernel; every kernel has
a pure-jnp oracle in ref.py and is validated in interpret mode on CPU
(tests/test_kernels.py). ops.py routes through the kernel catalog so SAVE
archives the lowered artifacts (core/kernel_catalog.py).

Every kernel takes ``interpret=None``: compiled for the chip on a TPU
backend, the Pallas interpreter on any other backend.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret exactly when the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
