"""Shared helpers for the Pallas TPU kernels.

All kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling) and
validated on CPU with ``interpret=True``, which executes the kernel body in
Python. Both choices below are made when a call is traced, from the backend
JAX is running on — never once at import, where a TPU that failed to come up
would leave every kernel silently interpreted.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: bool | None) -> bool:
    """The ``interpret`` flag for a kernel traced now. Never interpret on a
    TPU backend. Elsewhere the Pallas interpreter runs the kernel, unless the
    caller passes ``False`` to compile it for a described TPU (the chip
    compile tests)."""
    if on_tpu():
        return False
    return True if interpret is None else interpret


def use_kernels(use_kernel: bool | None) -> bool:
    """The one place the serving/scan layers pick the Pallas kernels or
    their jnp references: an explicit choice wins; otherwise the kernels on
    a TPU backend, and the references elsewhere (on the CPU they are the
    test oracle, and interpret mode would loop every grid in Python)."""
    return on_tpu() if use_kernel is None else bool(use_kernel)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pick_block(dim: int, preferred: int, align: int = 128) -> int:
    """Largest hardware-aligned block ≤ preferred that does not exceed dim
    (padded up to ``align`` when dim itself is small)."""
    if dim <= preferred:
        return round_up(dim, align) if dim % align else dim
    b = preferred - (preferred % align) or align
    return b
