"""Pallas TPU kernel: fused IVF-ADC scan over selected inverted-list blocks.

Extends the one-hot-matmul ADC trick of ``adc_lookup.py`` from "score every
item" to "score exactly the blocks the coarse probe selected". The search
layer turns (query, probed list) pairs into a flat schedule of
``block_size``-row tiles of the CSR codes array:

    block_idx[s]   — which codes tile step s scans (tile units, not rows)
    block_query[s] — which query's LUT scores it

Both ride in as **scalar-prefetch** operands (PrefetchScalarGridSpec), so the
BlockSpec index_map can steer the automatic HBM→VMEM pipeline straight at the
selected tiles: codes reach VMEM as sequential tile DMAs — gather-free, same
HBM traffic as a dense scan of the *selected* rows only. In VMEM the tile is
one-hot expanded over K and contracted against the query's (D·K) LUT row on
the MXU, exactly like the flat kernel.

Grid: one step per selected (query, block) pair; out[s] = scores of the
``block_size`` items of that tile, stored as a lane-dense (1, 1, bs) block
of an (S, 1, bs) array (a (1, bs) block of (S, bs) would break the TPU's
(8, 128) tiling rule). With an ``ids`` operand the tile's id row
is DMA'd alongside its codes (steered by the same ``block_idx`` index_map)
and rows with id < 0 — CSR padding holes and tombstoned deletes — score
−inf inside the tile body, so a delete is one id write and masked rows can
never surface downstream (the caller's added coarse term is finite).
One LUT row per step keeps the schedule fully general (any query mix); batch
efficiency comes from the ~100× fewer tiles the probe selects, not from
sharing tiles between queries.

Hole steps: the search gives every (query, list) pair the same number of
steps, and a short list's surplus steps point at the index's all-hole
sentinel block. With ``hole_block`` (that block's static index) such a step
is scheduled but not computed: it stores −inf and skips the one-hot build
and the contraction. Consecutive hole steps name the same input blocks, so
the pipeline issues no new DMA for them either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.adc_common import adc_tile_scores
from repro.kernels.common import interpret_mode


def _make_kernel(quantized: bool, masked: bool, hole_block: int | None):
    """Tile body of one scheduled step. Operands after the two prefetched
    schedule arrays: codes tile, LUT row, then the (1, Dp, 2) scale row of
    a quantized LUT and the (1, 1, bs) id tile, where present."""

    def kernel(bi_ref, bq_ref, codes_ref, lut_ref, *refs):
        out_ref = refs[-1]
        scales_ref = refs[0] if quantized else None
        ids_ref = refs[-2] if masked else None

        def score():
            # shared family body with b = 1 (this step's query LUT): (1, bs);
            # a quantized LUT row is dequantized in VMEM
            scores = adc_tile_scores(
                codes_ref[...], lut_ref[...],
                scales_ref[...] if quantized else None)[None]
            if masked:
                # (1, 1, bs) id tile of this codes block: holes and
                # tombstones → −inf
                scores = jnp.where(ids_ref[...] >= 0, scores, -jnp.inf)
            out_ref[...] = scores.astype(out_ref.dtype)

        if hole_block is None:
            score()
            return
        # a step scheduled on the all-hole block scores −inf without the
        # one-hot build or the contraction
        hole = bi_ref[pl.program_id(0)] == hole_block
        pl.when(jnp.logical_not(hole))(score)

        @pl.when(hole)
        def _():
            out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    return kernel


#: schedule steps one pallas_call takes: its two int32 schedule arrays then
#: use 256 KiB of SMEM. Hole steps count here too: they are scheduled, only
#: their tile work is skipped
SCHEDULE_STEPS = 32768


@functools.partial(jax.jit,
                   static_argnames=("block_size", "hole_block", "interpret"))
def ivf_adc(
    lut: jax.Array,
    codes: jax.Array,
    block_idx: jax.Array,
    block_query: jax.Array,
    scales: jax.Array | None = None,
    ids: jax.Array | None = None,
    *,
    block_size: int = 128,
    hole_block: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """lut (b, Dp, K) float, codes (cap, Dp) int (cap % block_size == 0),
    block_idx / block_query (S,) int32  ->  scores (S, block_size) float32.

    Residual depth rides in the Dp column dimension (Dp = M·D for RQ).
    With ``scales`` (b, Dp, 2) the lut is an int8/uint8 quantize_luts pack —
    the per-step LUT-row DMA moves 4× fewer bytes. With ``ids`` (cap,) the
    tombstone mask applies inside the tile body (rows with id < 0 → −inf).
    Steps whose tile is block ``hole_block`` (the index's all-hole sentinel)
    score −inf and skip the tile work."""
    b, Dp, K = lut.shape
    S = block_idx.shape[0]
    in_specs = [
        pl.BlockSpec((block_size, Dp), lambda i, bi, bq: (bi[i], 0)),
        pl.BlockSpec((1, Dp, K), lambda i, bi, bq: (bq[i], 0, 0)),
    ]
    operands = [codes, lut]
    kernel = _make_kernel(scales is not None, ids is not None, hole_block)
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, Dp, 2), lambda i, bi, bq: (bq[i], 0, 0)))
        operands.append(scales)
    if ids is not None:
        # the id column folded to (cap/bs, 1, bs) tiles so the SAME
        # block_idx prefetch steers its DMA as steers the codes tile; the
        # unit middle axis keeps each block's last two dims TPU-tileable
        in_specs.append(pl.BlockSpec((1, 1, block_size),
                                     lambda i, bi, bq: (bi[i], 0, 0)))
        operands.append(
            ids.reshape(codes.shape[0] // block_size, 1, block_size)
            .astype(jnp.int32))
    out_spec = pl.BlockSpec((1, 1, block_size), lambda i, bi, bq: (i, 0, 0))

    def scan(bi: jax.Array, bq: jax.Array) -> jax.Array:
        steps = bi.shape[0]
        # codes stay in their storage dtype (uint8 for K ≤ 256) all the way
        # to VMEM — the kernel widens per tile; widening here would
        # materialize a 4× int32 copy of the whole corpus per call.
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(steps,), in_specs=in_specs,
                out_specs=out_spec),
            out_shape=jax.ShapeDtypeStruct((steps, 1, block_size),
                                           jnp.float32),
            interpret=interpret_mode(interpret),
        )(bi, bq, *operands)

    # the two prefetched schedule arrays live in SMEM (1 MiB on a v5e): a
    # longer schedule is scanned in pieces of SCHEDULE_STEPS
    bi, bq = block_idx.astype(jnp.int32), block_query.astype(jnp.int32)
    out = jnp.concatenate([
        scan(bi[s:s + SCHEDULE_STEPS], bq[s:s + SCHEDULE_STEPS])
        for s in range(0, S, SCHEDULE_STEPS)])
    return out.reshape(S, block_size)
