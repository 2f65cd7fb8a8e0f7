"""Pallas TPU kernel: causal flash attention, forward and backward, for GQA
and for MLA's unequal widths (q/k 192, v 128).

Layout is head-major: q (B, Hq, S, dk), k (B, Hkv, S, dk), v (B, Hkv, S,
dv); query head h reads kv head h // (Hq // Hkv) through the index maps.

Square blocks of ``blocks(S)`` rows on both axes. Forward, grid (B, Hq,
S/b, S/b) with the kv axis innermost: each (q block × kv block) score tile
lives in VMEM only, the running row max and sum are float32 (online
softmax), and only the output and a per-row log-sum-exp go back to HBM.
Backward recomputes the tiles from those residuals: one kernel accumulates
dq over kv blocks, the other dk and dv over the q blocks (and the query
heads) that read a kv block.

Causal skipping: kv block j of q block i does no work where j > i — its
grid step is predicated off, and the index maps repeat the last block that
is needed, so the pipeline issues no DMA for it either. Only the diagonal
tile (j == i) builds the mask.

Both contractions take the operands in their own dtype (bf16 on the
training path) and accumulate in float32; ``p`` and ``ds`` are cast to the
value/key dtype before their matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

F32 = jnp.float32
LANES = 128
# finite, so a masked entry gives exp(MASK - m) = 0 and never
# exp(-inf + inf)
MASK = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))   # a @ bᵀ
_NN = (((1,), (0,)), ((), ()))   # a @ b
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def blocks(seq_len: int) -> int | None:
    """The block size (rows of q and of kv alike) for a sequence length, or
    None where it does not tile into the 128-row blocks the TPU needs."""
    for b in (1024, 512, 256, 128):
        if seq_len % b == 0:
            return b
    return None


def kv_tiles(seq_len: int, block: int) -> tuple[int, int]:
    """(kv tiles computed, kv tiles in the grid) per (batch, head) of one
    forward call: the causal skip's reach, from the static grid alone."""
    n = seq_len // block
    return n * (n + 1) // 2, n * n


def _causal(s, transposed: bool):
    """A diagonal tile with the entries where the key comes after the query
    set to MASK; rows index queries (or keys, ``transposed``)."""
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows <= cols if transposed else cols <= rows
    return jnp.where(keep, s, MASK)


def _lanes(x, n):
    """A (rows, 128) lane-replicated statistic widened or cut to n lanes."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return pltpu.repeat(x, n // LANES, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _live_steps(i, j, step):
    """Run ``step(masked)`` for q block i and kv block j: unmasked below
    the diagonal, masked on it, not at all above it."""
    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale):
    i, j = pl.program_id(2), pl.program_id(3)
    dv = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def step(masked):
        v = v_ref[...]
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=F32) * scale
        if masked:
            s = _causal(s, transposed=False)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * _lanes(alpha, dv) + lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=F32)

    _live_steps(i, j, step)

    @pl.when(j == i)
    def _done():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, dv)).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)            # (blk, 128), lanes equal
        lse_ref[...] = lse.T[:1, :]             # a row: (1, blk)


def _kv_map(rep):
    """kv block j of q block i, for query head h: the blocks above the
    diagonal repeat block i (no DMA)."""
    return lambda b, h, i, j: (b, h // rep, jnp.minimum(j, i), 0)


def _forward(q, k, v, scale, blk, interpret):
    B, Hq, S, dk = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    kv_map, n = _kv_map(Hq // Hkv), S // blk

    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(B, Hq, n, n),
        in_specs=[
            pl.BlockSpec((None, None, blk, dk), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, blk, dk), kv_map),
            pl.BlockSpec((None, None, blk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, blk, dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, 1, blk), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, S, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, S), F32)],
        scratch_shapes=[pltpu.VMEM((blk, LANES), F32),    # running max
                        pltpu.VMEM((blk, LANES), F32),    # running sum
                        pltpu.VMEM((blk, dv), F32)],      # output accumulator
        compiler_params=_PARAMS,
        interpret=interpret_mode(interpret),
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_sc,
               *, scale):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def step(masked):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, _NT,
                            preferred_element_type=F32) * scale
        if masked:
            s = _causal(s, transposed=False)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=F32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                       preferred_element_type=F32)

    _live_steps(i, j, step)

    @pl.when(j == i)
    def _done():
        dq_ref[...] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, nq):
    # the inner axis t walks (query head of this kv head, q block)
    j, t = pl.program_id(2), pl.program_id(3)
    i = t % nq

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, F32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, F32)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        # transposed tiles, keys × queries: the row statistics broadcast
        # over sublanes and both gradient matmuls are plain a @ b
        s = lax.dot_general(k_ref[...], q, _NT,
                            preferred_element_type=F32) * scale
        if masked:
            s = _causal(s, transposed=True)
        p = jnp.exp(s - lse_ref[...])
        dv_sc[...] += lax.dot_general(p.astype(do.dtype), do, _NN,
                                      preferred_element_type=F32)
        dp = lax.dot_general(v_ref[...], do, _NT, preferred_element_type=F32)
        ds = p * (dp - di_ref[...])
        dk_sc[...] += lax.dot_general(ds.astype(q.dtype), q, _NN,
                                      preferred_element_type=F32)

    _live_steps(i, j, step)

    @pl.when(t == pl.num_programs(3) - 1)
    def _done():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, scale, blk, interpret):
    B, Hq, S, dk = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    rep, nq = Hq // Hkv, S // blk
    kv_map = _kv_map(rep)
    # di = rowsum(o · do), the softmax's share of dp; a row per head
    di = jnp.sum(o.astype(F32) * do.astype(F32), axis=-1)[:, :, None, :]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(B, Hq, nq, nq),
        in_specs=[
            pl.BlockSpec((None, None, blk, dk), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, blk, dk), kv_map),
            pl.BlockSpec((None, None, blk, dv), kv_map),
            pl.BlockSpec((None, None, blk, dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, 1, blk), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((None, None, 1, blk), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, None, blk, dk),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, dk), F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(interpret),
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)

    def q_row(h, j, t):
        """(query head, q block) of inner step t; the q blocks before
        block j, which see none of kv block j, repeat block j (no DMA)."""
        return h * rep + t // nq, jnp.maximum(t % nq, j)

    def q_map(b, h, j, t):
        hq, i = q_row(h, j, t)
        return b, hq, i, 0

    def stat_map(b, h, j, t):
        hq, i = q_row(h, j, t)
        return b, hq, 0, i

    def kv_own(b, h, j, t):
        return b, h, j, 0

    dk_, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, nq=nq),
        grid=(B, Hkv, nq, rep * nq),
        in_specs=[
            pl.BlockSpec((None, None, blk, dk), q_map),
            pl.BlockSpec((None, None, blk, dk), kv_own),
            pl.BlockSpec((None, None, blk, dv), kv_own),
            pl.BlockSpec((None, None, blk, dv), q_map),
            pl.BlockSpec((None, None, 1, blk), stat_map),
            pl.BlockSpec((None, None, 1, blk), stat_map),
        ],
        out_specs=[pl.BlockSpec((None, None, blk, dk), kv_own),
                   pl.BlockSpec((None, None, blk, dv), kv_own)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, dk), F32), pltpu.VMEM((blk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(interpret),
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk_, dv_


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, block, interpret):
    return _forward(q, k, v, scale, block, interpret)[0]


def _attention_fwd(q, k, v, scale, block, interpret):
    o, lse = _forward(q, k, v, scale, block, interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, block, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, scale, block, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, scale: float, interpret: bool | None = None):
    """Causal softmax(scale · q kᵀ) v over head-major (B, H, S, d) arrays;
    differentiable in q, k and v. S must tile into ``blocks(S)``."""
    block = blocks(q.shape[2])
    if block is None:
        raise ValueError(f"sequence length {q.shape[2]} does not tile into "
                         "the kernel's 128-row blocks")
    return _attention(q, k, v, float(scale), block, interpret)
