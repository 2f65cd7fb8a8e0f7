"""Pallas TPU kernel: rotation-fused ADC-LUT build.

The serving hot path rebuilds per-query LUTs on every request, and after a
live ``refresh(delta)`` the naive pipeline would *also* re-rotate corpus
state (XR on the exact path, codebooks + cached LUTs on the ADC paths).
This kernel moves the whole rotation story to the query side: the composed
query transform ``qdelta = R₀·Δ·Wᵀ`` (see search.flat fused refresh — R₀ the
frozen index rotation, Δ the accumulated delta, W its within-subspace part)
is applied to the query block *inside the tile body*, and the LUT is built
against the frozen flattened codebooks. Refresh then only swaps one (n, n)
matrix; corpus-side buffers are never touched and cached LUTs stay valid
whenever the delta is purely within-subspace.

``colmap`` (Dp, D) is a one-hot column map from code column → query
subspace: identity for PQ, and for a depth-M level-major RQ the column
l·D + d maps to subspace d. Keeping it an explicit argument lets one kernel
serve every quantizer layout — the Dp axis of the codebooks is the true
code-column axis, so per-column int8 scale groups stay correct for RQ.

Grid (b/bb,): each step holds one query block, and a loop over the code
columns p rotates it by column p's slice of the query transform and
contracts the result against column p's codebook on the MXU. The wrapper
lays the operands out so the kernel needs no reshape: the transform
as (Dp, sub, n) per-column row slices (the colmap gather happens there, on
an (n, n) matrix, never on corpus state), the codebooks as (Dp, sub, K),
and the output as (Dp, b, K), transposed back to (b, Dp, K) outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_mode

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(q_ref, qcol_ref, cbt_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)                      # (bb, n)

    def column(p, carry):
        # rotate the query block into column p's subspace: (bb, sub)
        ql = jax.lax.dot_general(
            q, qcol_ref[p].astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
        # contract against column p's codewords: (bb, K)
        out_ref[p] = jnp.dot(
            ql, cbt_ref[p].astype(jnp.float32), precision=_HIGHEST,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, qcol_ref.shape[0], column, 0)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_lut(
    Q: jax.Array,
    qdelta: jax.Array,
    cb_flat: jax.Array,
    colmap: jax.Array,
    *,
    block_b: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Q (b, n) raw queries, qdelta (n, n), cb_flat (Dp, K, sub) frozen
    flattened codebooks, colmap (Dp, D) one-hot column map
    ->  lut (b, Dp, K) float32 with
    lut[b, p, k] = ⟨(Q·qdelta) subspace of column p, cb_flat[p, k]⟩."""
    b, n = Q.shape
    Dp, K, sub = cb_flat.shape
    D = colmap.shape[1]
    bb = min(block_b, b)
    bpad = cdiv(b, bb) * bb
    if bpad != b:
        Q = jnp.pad(Q, ((0, bpad - b), (0, 0)))
    # qcol[p, s, i] = qdelta[i, d(p)·sub + s] with d(p) column p's subspace
    qdt = qdelta.astype(jnp.float32).T.reshape(D, sub, n)
    qcol = qdt[jnp.argmax(colmap, axis=1)]                  # (Dp, sub, n)
    cbt = jnp.swapaxes(cb_flat.astype(jnp.float32), 1, 2)   # (Dp, sub, K)
    out = pl.pallas_call(
        _kernel,
        grid=(bpad // bb,),
        in_specs=[
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
            pl.BlockSpec((Dp, sub, n), lambda i: (0, 0, 0)),
            pl.BlockSpec((Dp, sub, K), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((Dp, bb, K), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Dp, bpad, K), jnp.float32),
        interpret=interpret_mode(interpret),
    )(Q, qcol, cbt)
    return jnp.swapaxes(out, 0, 1)[:b]
