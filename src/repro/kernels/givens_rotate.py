"""Pallas TPU kernel: apply n/2 disjoint Givens rotations to paired planes.

TPU adaptation of the paper's "sparse matmul" rotation application (DESIGN.md
§2): the caller permutes pair columns adjacent (cheap XLA gather), after which
the commuting block update is a pure elementwise combine of two column planes

    ye = c⊙xe + s⊙xo        yo = c⊙xo − s⊙xe

with cos/sin broadcast down the rows. This is memory-roofline optimal:
4 plane reads + 2 plane writes, zero matmuls, no MXU dependency.

Tiling: grid (m/bm, p/bp); each step holds a (bm, bp) tile of both planes and
a (1, bp) strip of cos/sin in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_mode


def _kernel(c_ref, s_ref, xe_ref, xo_ref, ye_ref, yo_ref):
    c = c_ref[...].astype(jnp.float32)  # (1, bp)
    s = s_ref[...].astype(jnp.float32)
    xe = xe_ref[...].astype(jnp.float32)
    xo = xo_ref[...].astype(jnp.float32)
    ye_ref[...] = (c * xe + s * xo).astype(ye_ref.dtype)
    yo_ref[...] = (c * xo - s * xe).astype(yo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_p", "interpret"))
def givens_rotate(
    xe: jax.Array,
    xo: jax.Array,
    c: jax.Array,
    s: jax.Array,
    *,
    block_m: int = 256,
    block_p: int = 256,
    interpret: bool | None = None,
):
    """xe/xo: (m, p) paired column planes; c/s: (p,) cos/sin. -> (ye, yo)."""
    m, p = xe.shape
    bm, bp = min(block_m, m), min(block_p, p)
    grid = (cdiv(m, bm), cdiv(p, bp))
    c2 = c.reshape(1, p)
    s2 = s.reshape(1, p)
    out_shape = (
        jax.ShapeDtypeStruct((m, p), xe.dtype),
        jax.ShapeDtypeStruct((m, p), xo.dtype),
    )
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bp), lambda i, j: (0, j)),   # cos strip
            pl.BlockSpec((1, bp), lambda i, j: (0, j)),   # sin strip
            pl.BlockSpec((bm, bp), lambda i, j: (i, j)),  # xe tile
            pl.BlockSpec((bm, bp), lambda i, j: (i, j)),  # xo tile
        ],
        out_specs=(
            pl.BlockSpec((bm, bp), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bp), lambda i, j: (i, j)),
        ),
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(c2, s2, xe, xo)
