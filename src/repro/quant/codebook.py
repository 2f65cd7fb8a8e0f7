"""Codebook substrate shared by every quantizer (moved here from core/pq.py).

Pure-jnp primitives over per-subspace codebooks ``(D, K, sub)`` — split/merge,
nearest-codeword assignment, decode, the straight-through estimator, the
distortion objective, and ADC lookup tables. Multi-level (residual) schemes
stack a leading level axis ``(M, D, K, sub)`` and flatten it into the
``code_width = M·D`` column axis before touching the shared kernels.

The non-differentiable argmin is bridged by the gradient straight-through
estimator (Bengio et al. 2013), exactly as in the paper / Zhang et al. 2021.

Codebooks: (D, K, sub) float. Codes: (m, D) int32 (uint8 in storage).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.common import use_kernels


def split(X: jax.Array, D: int) -> jax.Array:
    """(..., n) -> (..., D, n/D)."""
    *lead, n = X.shape
    assert n % D == 0, f"n={n} not divisible by D={D}"
    return X.reshape(*lead, D, n // D)


def merge(Xs: jax.Array) -> jax.Array:
    """(..., D, sub) -> (..., D*sub)."""
    *lead, D, sub = Xs.shape
    return Xs.reshape(*lead, D * sub)


def assign(X: jax.Array, codebooks: jax.Array) -> jax.Array:
    """Nearest codeword per subspace. (m, n) -> (m, D) int32.

    Uses ‖x−c‖² = ‖x‖² − 2⟨x,c⟩ + ‖c‖² with the ‖x‖² term dropped (constant
    in the argmin) — so the hot op is one einsum on the MXU.
    """
    D = codebooks.shape[0]
    Xs = split(X, D)  # (m, D, sub)
    dots = jnp.einsum("mds,dks->mdk", Xs, codebooks)
    cn = jnp.sum(jnp.square(codebooks), axis=-1)  # (D, K)
    d2 = cn[None, :, :] - 2.0 * dots
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def decode(codes: jax.Array, codebooks: jax.Array) -> jax.Array:
    """(m, D) codes -> (m, n) reconstruction (differentiable wrt codebooks)."""
    D = codebooks.shape[0]
    gathered = codebooks[jnp.arange(D)[None, :], codes]  # (m, D, sub)
    return merge(gathered)


def quantize(X: jax.Array, codebooks: jax.Array) -> jax.Array:
    """φ(X): hard quantization, no gradient bridging."""
    return decode(assign(X, codebooks), codebooks)


def quantize_ste(X: jax.Array, codebooks: jax.Array) -> jax.Array:
    """φ(X) with straight-through estimator: forward = quantized value,
    backward = identity wrt X (codebooks receive no grad through this path —
    they are trained by the distortion loss)."""
    q = decode(jax.lax.stop_gradient(assign(X, codebooks)), codebooks)
    return X + jax.lax.stop_gradient(q - X)


def distortion(X: jax.Array, codebooks: jax.Array,
               codes: jax.Array | None = None) -> jax.Array:
    """(1/m)‖X − φ(X)‖²_F — the paper's quantization-distortion metric/loss.

    Differentiable wrt both X and codebooks (assignment is stop-gradiented).
    """
    if codes is None:
        codes = jax.lax.stop_gradient(assign(X, codebooks))
    q = decode(codes, codebooks)
    return jnp.mean(jnp.sum(jnp.square(X - q), axis=-1))


def adc_lut(q: jax.Array, codebooks: jax.Array) -> jax.Array:
    """Asymmetric-distance lookup table for a query batch.

    For inner-product / cosine retrieval the score of item with codes c is
    Σ_d LUT[d, c_d] with LUT[d, k] = ⟨q_d, C[d, k]⟩.  (b, n) -> (b, D, K).
    """
    D = codebooks.shape[0]
    qs = split(q, D)  # (b, D, sub)
    return jnp.einsum("bds,dks->bdk", qs, codebooks)


def adc_score(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Sum LUT entries over subspaces: (b, D, K) × (N, D) -> (b, N).

    Pure-jnp gather formulation — the small-N oracle. The serving paths go
    through ``adc_score_tables`` below (shared Pallas kernel family).
    """
    D = lut.shape[1]
    gathered = lut[:, jnp.arange(D)[None, :], codes]  # (b, N, D)
    return jnp.sum(gathered, axis=-1)


def adc_score_tables(tables: jax.Array, codes: jax.Array, *,
                     use_kernel: bool | None = None) -> jax.Array:
    """Score PQ/RQ codes against protocol-shaped ADC tables.

    ``tables (b, code_width, K)`` (any Quantizer.adc_tables output — residual
    depth is already flattened into ``code_width``) × ``codes
    (N, code_width)`` -> (b, N). Dispatches to the fused Pallas flat-scan
    kernel (kernels/adc_lookup.py) or its jnp oracle; ``use_kernel=None``
    picks by platform (``kernels.common.use_kernels``).
    """
    return kops.adc_lookup(tables, codes, use_kernel=use_kernels(use_kernel))


def rotate_codebooks(codebooks: jax.Array, pi: jax.Array, pj: jax.Array,
                     theta: jax.Array) -> jax.Array:
    """Absorb disjoint Givens plane rotations ∏ℓ R_{pi[ℓ],pj[ℓ]}(θℓ) of the
    *full* n-dim space into per-subspace codebooks.

    ``codebooks (..., D, K, sub)`` (optional leading level axes). In the
    full-dim layout, codeword slot k's column d·sub+t holds
    codebooks[..., d, k, t]; within-subspace pairs only mix columns inside
    one subspace slice, so one pair-rotation call refreshes all D (and all
    levels of) codebooks at once. Callers must zero θ for cross-subspace
    pairs — those cannot be absorbed into a product codebook (the zeroed
    rotation is the identity).
    """
    from repro.core import givens  # function-level: core imports quant shims

    *lead, D, K, sub = codebooks.shape
    cw = jnp.moveaxis(codebooks, -2, -3).reshape(-1, D * sub)  # (lead·K, n)
    cw = givens.apply_pair_rotations(cw, pi, pj, theta)
    return jnp.moveaxis(cw.reshape(*lead, K, D, sub), -2, -3)
