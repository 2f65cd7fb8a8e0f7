"""Pallas TPU kernel: fused GCD directional-derivative matrix A = M − Mᵀ,
M = GᵀR (paper Algorithm 2 line 3).

Computing M then transposing costs two n² passes over HBM; this kernel
computes, for each output tile (I, J), BOTH partial products

    acc   += G[k-block, I]ᵀ · R[k-block, J]      (tile of M)
    accT  += G[k-block, J]ᵀ · R[k-block, I]      (tile of Mᵀ, pre-transpose)

on the MXU and writes A[I, J] = acc − accTᵀ in one shot — M is never
materialized. Grid (I, J, K) with K innermost so the accumulators live in
VMEM scratch across the contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_mode


def _kernel(gi_ref, gj_ref, ri_ref, rj_ref, out_ref, acc_ref, accT_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accT_ref[...] = jnp.zeros_like(accT_ref)

    gi = gi_ref[...].astype(jnp.float32)  # (bk, bi)
    gj = gj_ref[...].astype(jnp.float32)  # (bk, bj)
    ri = ri_ref[...].astype(jnp.float32)  # (bk, bi)
    rj = rj_ref[...].astype(jnp.float32)  # (bk, bj)
    acc_ref[...] += jax.lax.dot_general(
        gi, rj, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    accT_ref[...] += jax.lax.dot_general(
        gj, ri, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = (acc_ref[...] - accT_ref[...].T).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "block_k", "interpret"))
def gcd_score(
    G: jax.Array,
    R: jax.Array,
    *,
    block: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """A = GᵀR − RᵀG for G, R (n, n). Returns float32 (n, n) antisymmetric."""
    n = G.shape[0]
    b = min(block, n)
    bk = min(block_k, n)
    nk = cdiv(n, bk)
    grid = (cdiv(n, b), cdiv(n, b), nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, b), lambda i, j, k: (k, i)),  # G[:, I]
            pl.BlockSpec((bk, b), lambda i, j, k: (k, j)),  # G[:, J]
            pl.BlockSpec((bk, b), lambda i, j, k: (k, i)),  # R[:, I]
            pl.BlockSpec((bk, b), lambda i, j, k: (k, j)),  # R[:, J]
        ],
        out_specs=pl.BlockSpec((b, b), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((b, b), jnp.float32),  # M tile accumulator
            pltpu.VMEM((b, b), jnp.float32),  # Mᵀ tile accumulator
        ],
        interpret=interpret_mode(interpret),
    )(G, G, R, R)
