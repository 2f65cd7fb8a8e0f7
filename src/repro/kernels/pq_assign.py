"""Pallas TPU kernel: fused PQ codeword assignment (nearest-centroid search).

For each subspace d, each row x of the (m, sub) slice is assigned
argmin_k ‖x − C[d,k]‖² = argmin_k (‖C[d,k]‖² − 2⟨x, C[d,k]⟩). The kernel
fuses the MXU distance matmul with the argmin epilogue so the (bm, K) score
tile never leaves VMEM — the XLA fallback materializes all (m, D, K) scores
in HBM.

Grid (D, m/bm): one subspace × one row tile per step; the full (K, sub)
codebook slice for that subspace rides along in VMEM (K ≤ 256, sub ≤ 128 →
≤128 KiB). The codes leave as a lane-dense (1, 1, bm) block of a (D, 1, m)
array — a (bm, 1) column block would break the TPU's (8, 128) tiling rule —
and are transposed to (m, D) outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_mode


def _kernel(x_ref, cb_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)          # (bm, sub)
    cb = cb_ref[0].astype(jnp.float32)        # (K, sub)
    dots = jax.lax.dot_general(
        cb, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (K, bm): rows on lanes
    cn = jnp.sum(jnp.square(cb), axis=-1, keepdims=True)  # (K, 1)
    d2 = cn - 2.0 * dots
    # argmin over K (first index of the minimum, as jnp.argmin) from two
    # min-reductions, so the codes come out lane-major: (1, bm)
    best = jnp.min(d2, axis=0, keepdims=True)
    k = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    codes = jnp.min(jnp.where(d2 == best, k, d2.shape[0]), axis=0,
                    keepdims=True)
    out_ref[...] = codes[None]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def pq_assign(
    X: jax.Array,
    codebooks: jax.Array,
    *,
    block_m: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """X (m, n), codebooks (D, K, sub) with n = D·sub  ->  codes (m, D) int32."""
    m, n = X.shape
    D, K, sub = codebooks.shape
    assert n == D * sub
    bm = min(block_m, m)
    Xs = X.reshape(m, D, sub).transpose(1, 0, 2)  # (D, m, sub): subspace-major
    grid = (D, cdiv(m, bm))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, sub), lambda d, i: (d, i, 0)),
            pl.BlockSpec((1, K, sub), lambda d, i: (d, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm), lambda d, i: (d, 0, i)),
        out_shape=jax.ShapeDtypeStruct((D, 1, m), jnp.int32),
        interpret=interpret_mode(interpret),
    )(Xs, codebooks)
    return out[:, 0, :].T
