"""Shared transformer layer primitives: norms, RoPE, activations, attention.

Attention is implemented blockwise (scan over query chunks, f32 softmax per
chunk) so the (B, H, S, S) score tensor is never materialized — the pure-JAX
equivalent of flash attention, sized so the per-chunk score tile stays within
a few GB/device at the production shapes (see DESIGN.md §3).
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # large-negative instead of -inf: keeps fully-masked rows finite


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def _f32_sumsq_lastdim(x: jax.Array) -> jax.Array:
    """Σ x² over the last axis with f32 ACCUMULATION but bf16 operands.

    An ``x.astype(f32)`` here is the first consumer of every saved layer
    boundary; XLA hoists that convert out of the backward while-loop and
    materializes an f32 copy of the whole (L, B, S, d) residual stack
    (measured ~5.6 GiB/device on the 340B arch). A batched self-dot with
    preferred_element_type keeps the stats in f32 without any f32 copy of x.
    """
    nb = x.ndim - 1
    dims = (((nb,), (nb,)), (tuple(range(nb)), tuple(range(nb))))
    return jax.lax.dot_general(x, x, dims, preferred_element_type=jnp.float32)


def _f32_sum_lastdim(x: jax.Array) -> jax.Array:
    ones = jnp.ones((x.shape[-1],), x.dtype)
    nb = x.ndim - 1
    dims = (((nb,), (0,)), ((), ()))
    return jax.lax.dot_general(x, ones, dims,
                               preferred_element_type=jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    d = x.shape[-1]
    ss = _f32_sumsq_lastdim(x)[..., None] / d          # f32 stats
    inv = jax.lax.rsqrt(ss + eps).astype(x.dtype)      # bf16 apply
    out = x * inv
    if scale is not None:
        out = out * scale.astype(x.dtype)
    return out


def nonparam_layer_norm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo-style non-parametric LayerNorm (no scale, no bias). f32 stats,
    model-dtype application (see _f32_sumsq_lastdim)."""
    d = x.shape[-1]
    mu = (_f32_sum_lastdim(x) / d)[..., None]
    ss = (_f32_sumsq_lastdim(x) / d)[..., None]
    var = jnp.maximum(ss - jnp.square(mu), 0.0)
    inv = jax.lax.rsqrt(var + eps)
    return ((x - mu.astype(x.dtype)) * inv.astype(x.dtype))


def apply_norm(x: jax.Array, scale: jax.Array | None, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, scale)
    if kind == "layernorm_nonparam":
        return nonparam_layer_norm(x)
    raise ValueError(f"unknown norm {kind!r}")


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":  # squared ReLU (Nemotron-4 / Primer)
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2's yarn_get_mscale)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (Peng et al. 2023, as DeepSeek-V2 sets them):
    the fast dimensions keep base frequencies, the slow ones are divided by
    ``factor``, with a linear ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` rotations. ``factor`` 1 is plain RoPE."""
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return base.astype(np.float32)

    def corr(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp              # 1 where the base frequency is kept
    freqs = base / factor * (1.0 - keep) + base * keep
    return freqs.astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               *, freqs=None, mscale: float = 1.0) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32. ``freqs`` (hd/2,) replaces
    the plain frequencies of ``theta`` (YaRN); cos and sin are scaled by
    ``mscale``."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = (jnp.cos(angles) * mscale)[:, :, None, :]
    sin = (jnp.sin(angles) * mscale)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise causal attention (training / prefill)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("q_chunk", "causal", "scale"))
def blockwise_attention(
    q: jax.Array,  # (B, S, Hq, hd)
    k: jax.Array,  # (B, S, Hkv, hd)
    v: jax.Array,  # (B, S, Hkv, hv)
    *,
    q_chunk: int = 256,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Causal GQA attention, scanned over query chunks with f32 softmax.

    Peak temp is (B, Hkv, rep, q_chunk, S) f32 per chunk instead of the full
    (B, H, S, S) score tensor. ``scale`` defaults to hd^-0.5; values may
    have their own width hv (MLA). Returns (B, S, Hq, hv).
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    hv = v.shape[-1]
    rep = Hq // Hkv
    scale = hd**-0.5 if scale is None else scale
    q_chunk = min(q_chunk, S)
    assert S % q_chunk == 0, f"seq {S} must be divisible by q_chunk {q_chunk}"
    nq = S // q_chunk

    qg = q.reshape(B, S, Hkv, rep, hd).transpose(0, 2, 3, 1, 4)  # (B,Hkv,rep,S,hd)
    kg = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, hd)
    vg = v.transpose(0, 2, 1, 3)
    qg = qg.reshape(B, Hkv, rep, nq, q_chunk, hd).transpose(3, 0, 1, 2, 4, 5)
    kv_pos = jnp.arange(S)

    # jax.checkpoint per chunk: without it the q-chunk scan stacks each
    # chunk's softmax residuals, resurrecting the full (B,H,S,S) tensor in
    # the backward pass (measured: 213 GiB/dev at the 4k-train shape). With
    # it, the bwd recomputes scores chunk-by-chunk — flash-attention
    # semantics in pure JAX.
    @jax.checkpoint
    def one_chunk(ci, qc):
        # qc: (B, Hkv, rep, q_chunk, hd)
        scores = jnp.einsum(
            "bhrqd,bhsd->bhrqs", qc.astype(jnp.float32), kg.astype(jnp.float32)
        ) * scale
        if causal:
            q_pos = ci * q_chunk + jnp.arange(q_chunk)
            mask = q_pos[:, None] >= kv_pos[None, :]  # (q_chunk, S)
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhrqs,bhsd->bhrqd", w, vg.astype(jnp.float32))
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: one_chunk(*args), (jnp.arange(nq), qg))
    # (nq, B, Hkv, rep, q_chunk, hd) -> (B, S, Hq, hd)
    outs = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hkv, rep, S, hv)
    return outs.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, hv)


def decode_attention(
    q: jax.Array,        # (B, Hq, hd) single step
    k_cache: jax.Array,  # (B, Hkv, S, hd)
    v_cache: jax.Array,  # (B, Hkv, S, hd)
    length: jax.Array,   # (B,) valid prefix length (new token already written)
) -> jax.Array:
    B, Hq, hd = q.shape
    Hkv = k_cache.shape[1]
    rep = Hq // Hkv
    S = k_cache.shape[2]
    scale = hd**-0.5
    qg = q.reshape(B, Hkv, rep, hd)
    scores = jnp.einsum(
        "bhrd,bhsd->bhrs", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    mask = jnp.arange(S)[None, :] < length[:, None]  # (B, S)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrs,bhsd->bhrd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked softmax cross-entropy (vocab can be huge and sharded)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def softmax_xent_chunked(
    x: jax.Array,        # (T, d) final hidden states
    head: jax.Array,     # (V, d) output projection (logits = x @ headᵀ)
    labels: jax.Array,   # (T,) int32
    *,
    chunk: int = 8192,
) -> jax.Array:
    """Mean cross-entropy without materializing all (T, V) logits at once.

    Scans over token chunks; each chunk's logits are recomputed in the
    backward pass (jax.checkpoint), so peak memory is O(chunk · V).
    """
    T, d = x.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, f"tokens {T} must be divisible by chunk {chunk}"
    nc = T // chunk
    xc = x.reshape(nc, chunk, d)
    lc = labels.reshape(nc, chunk)

    @jax.checkpoint
    def chunk_loss(xi, li):
        # The logits matmul runs in the model dtype and is cast to f32 AFTER:
        # (a) an .astype(f32) copy of the head forces an involuntary SPMD
        # rematerialization per chunk; (b) preferred_element_type=f32 is
        # worse — its transpose rule makes the residual-stream cotangent
        # f32, poisoning the whole backward into f32 weight-stack copies
        # (measured +8 GiB/dev on nemotron). The f32 cast after the dot
        # keeps logsumexp numerics in f32 while its transpose returns the
        # cotangent to the model dtype at the boundary.
        logits = jax.lax.dot_general(
            xi, head, (((1,), (1,)), ((), ()))).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    def body(carry, inp):
        xi, li = inp
        return carry + chunk_loss(xi, li), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, lc))
    return total / T
