"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the rest of the system calls. Each wrapper:
  * reshapes/permutes into the kernel's preferred layout,
  * dispatches to the Pallas kernel (interpret=True off-TPU),
  * exposes a ``use_kernel=False`` escape hatch to the pure-jnp oracle in
    ref.py (also used by the allclose tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import adc_batch as _adcb
from repro.kernels import adc_lookup as _adc
from repro.kernels import embedding_bag as _bag
from repro.kernels import flash_attention as _fa
from repro.kernels import gcd_score as _score
from repro.kernels import givens_rotate as _rot
from repro.kernels import ivf_adc as _ivf
from repro.kernels import lut_build as _lut
from repro.kernels import pq_assign as _assign
from repro.kernels import ref
from repro.kernels.adc_common import (LUT_DTYPES, dequantize_luts,
                                      quantize_luts)

__all__ = [
    "apply_pair_rotations", "gcd_score", "pq_assign", "adc_lookup",
    "adc_batch", "ivf_adc", "fused_lut", "embedding_bag", "topk_merge",
    "flash_attention", "quantize_luts", "dequantize_luts", "LUT_DTYPES",
]


def _apply_impl(pi, pj, X, theta, use_kernel: bool):
    c = jnp.cos(theta)
    s = jnp.sin(theta)
    lead = X.shape[:-1]
    n = X.shape[-1]
    Xf = X.reshape(-1, n)
    xe = jnp.take(Xf, pi, axis=1)
    xo = jnp.take(Xf, pj, axis=1)
    if use_kernel:
        ye, yo = _rot.givens_rotate(xe, xo, c, s)
    else:
        ye, yo = ref.givens_rotate_ref(xe, xo, c, s)
    Yf = Xf.at[:, pi].set(ye.astype(X.dtype)).at[:, pj].set(yo.astype(X.dtype))
    return Yf.reshape(*lead, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _apply_pair_rotations(X, theta, pi, pj, use_kernel):
    return _apply_impl(pi, pj, X, theta, use_kernel)


def _apply_fwd(X, theta, pi, pj, use_kernel):
    return _apply_impl(pi, pj, X, theta, use_kernel), (X, theta, pi, pj)


def _apply_bwd(use_kernel, res, dY):
    """Pallas calls don't autodiff; the rotation is linear & orthogonal so
    dX = dY rotated by −θ, and dθ_ℓ = Σ rows ⟨dY, ∂Y/∂θ_ℓ⟩ (plane-local)."""
    X, theta, pi, pj = res
    dX = _apply_impl(pi, pj, dY, -theta, use_kernel)
    c = jnp.cos(theta).astype(X.dtype)
    s = jnp.sin(theta).astype(X.dtype)
    xe = jnp.take(X, pi, axis=-1)
    xo = jnp.take(X, pj, axis=-1)
    dye = jnp.take(dY, pi, axis=-1)
    dyo = jnp.take(dY, pj, axis=-1)
    # y_e = c·x_e + s·x_o ; y_o = c·x_o − s·x_e
    dtheta = jnp.sum(
        (dye * (-s * xe + c * xo) + dyo * (-s * xo - c * xe)).astype(jnp.float32),
        axis=tuple(range(X.ndim - 1)),
    ).astype(theta.dtype)
    f0 = lambda a: jnp.zeros(a.shape, jax.dtypes.float0)
    return dX, dtheta, f0(pi), f0(pj)


_apply_pair_rotations.defvjp(_apply_fwd, _apply_bwd)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def apply_pair_rotations(X, pi, pj, theta, *, use_kernel: bool = True):
    """Drop-in for core.givens.apply_pair_rotations backed by the Pallas
    plane-rotation kernel: permute pair columns adjacent, rotate the even/odd
    planes in VMEM, scatter back. Differentiable via custom_vjp."""
    return _apply_pair_rotations(X, theta, pi, pj, use_kernel)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def gcd_score(G, R, *, use_kernel: bool = True):
    """A = GᵀR − RᵀG (fused; float32)."""
    if use_kernel:
        return _score.gcd_score(G, R)
    return ref.gcd_score_ref(G, R)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def pq_assign(X, codebooks, *, use_kernel: bool = True):
    """Nearest-codeword assignment (m, n) -> (m, D) int32."""
    if use_kernel:
        return _assign.pq_assign(X, codebooks)
    return ref.pq_assign_ref(X, codebooks)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def adc_lookup(lut, codes, scales=None, ids=None, *, use_kernel: bool = True):
    """Flat ADC scores (b, Dp, K) × (N, Dp) -> (b, N). Residual depth is the
    Dp column dimension (Dp = M·D for a depth-M RQ). With ``scales``
    (b, Dp, 2) the lut is an int8/uint8 ``quantize_luts`` pack, dequantized
    in the tile body. With ``ids`` (N,) rows with id < 0 (holes/tombstones)
    score −inf inside the tile body."""
    if use_kernel:
        return _adc.adc_lookup(lut, codes, scales, ids)
    return ref.adc_lookup_ref(lut, codes, scales, ids)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def adc_batch(lut, codes, scales=None, *, use_kernel: bool = True):
    """Grouped ADC scores (g, r, Dp, K) × (g, S, Dp) -> (g, r, S) — the
    KV-cache decode scorer (group = one (batch, kv-head) pair, r = GQA
    repetition). ``scales`` (g, r, Dp, 2): quantized-LUT pack."""
    if use_kernel:
        return _adcb.adc_batch(lut, codes, scales)
    return ref.adc_batch_ref(lut, codes, scales)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "hole_block", "use_kernel"))
def ivf_adc(lut, codes, block_idx, block_query, scales=None, ids=None, *,
            block_size: int = 128, hole_block: int | None = None,
            use_kernel: bool = True):
    """Selected-block IVF-ADC scan: (b, D, K) LUTs × (cap, D) CSR codes ×
    (S,) block schedule -> (S, block_size) scores. ``scales`` (b, D, 2):
    quantized-LUT pack, the per-step LUT-row DMA shrinks 4×. ``ids`` (cap,):
    tombstone mask — rows with id < 0 score −inf inside the tile body.
    ``hole_block``: steps scheduled on this (all-hole) block score −inf and
    the kernel skips their tile work."""
    if use_kernel:
        return _ivf.ivf_adc(lut, codes, block_idx, block_query, scales, ids,
                            block_size=block_size, hole_block=hole_block)
    return ref.ivf_adc_ref(lut, codes, block_idx, block_query,
                           block_size=block_size, scales=scales, ids=ids,
                           hole_block=hole_block)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def fused_lut(Q, qdelta, cb_flat, colmap, *, use_kernel: bool = True):
    """Rotation-fused ADC-LUT build: raw queries (b, n) × composed query
    transform (n, n) × frozen flattened codebooks (Dp, K, sub) × one-hot
    column map (Dp, D) -> (b, Dp, K) tables. The delta is applied to the
    query block inside the tile body, so refresh never touches corpus-side
    buffers (see kernels/lut_build.py)."""
    if use_kernel:
        return _lut.fused_lut(Q, qdelta, cb_flat, colmap)
    return ref.fused_lut_ref(Q, qdelta, cb_flat, colmap)


@functools.partial(jax.jit, static_argnames=("num_bags", "use_kernel"))
def embedding_bag(table, indices, bag_ids, num_bags: int, weights=None, *,
                  use_kernel: bool = True):
    """EmbeddingBag(sum) -> (num_bags, dim) float32. bag_ids must be sorted."""
    if use_kernel:
        return _bag.embedding_bag(table, indices, bag_ids, num_bags, weights)
    return ref.embedding_bag_ref(table, indices, bag_ids, num_bags, weights)


@functools.partial(jax.jit, static_argnames=("q_chunk", "causal", "scale",
                                             "use_kernel"))
def flash_attention(q, k, v, *, scale: float | None = None, q_chunk: int = 256,
                    causal: bool = True, use_kernel: bool = True):
    """Attention q (B, S, Hq, dk), k (B, S, Hkv, dk), v (B, S, Hkv, dv) ->
    (B, S, Hq, dv), softmax(scale · q kᵀ) with ``scale`` defaulting to
    dk^-0.5 (GQA: query head h reads kv head h // (Hq/Hkv)). The kernel is
    causal and runs head-major with blocks from the sequence length, which
    must tile into 128-row blocks (else ValueError); ``q_chunk`` and
    ``causal=False`` belong to the chunked oracle."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    if not use_kernel:
        return ref.flash_attention_ref(q, k, v, q_chunk=q_chunk, causal=causal,
                                       scale=scale)
    if not causal:
        raise ValueError("the flash-attention kernel is causal only")
    heads_major = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return heads_major(_fa.flash_attention(
        heads_major(q), heads_major(k), heads_major(v), scale=scale))


@functools.partial(jax.jit, static_argnames=("k",))
def topk_merge(scores, ids, k: int):
    """Cross-shard local-k merge: (b, C) gathered per-shard top-k runs ->
    (b, k) global top-k under the −inf/−1 padding contract. The reduce step
    of the sharded searcher family (search/sharded.py): each shard scans
    its local CSR rows, emits a padded local top-k, and the all_gather'd
    (b, shards·k) runs merge here. Ties are deterministic — equal scores
    rank by ascending id (lexicographic two-key sort), so results are
    identical regardless of shard/tile order or which serve batch a
    request was grouped into. Pure XLA sort — already optimal at these
    widths, so there is no Pallas variant (the ref IS the
    implementation)."""
    return ref.topk_merge_ref(scores, ids, k)
