"""Pallas TPU kernel: flat ADC (asymmetric distance computation) score scan.

Scores a query batch against N PQ/RQ-coded items:
out[b, n] = Σ_d LUT[b, d, c_nd]. CPU/GPU implementations use SIMD gathers
(André et al. 2015); this kernel scores each item tile with the shared
one-hot-MXU body (adc_common.adc_tile_scores) — HBM traffic stays at
O(N·Dp + N·b). Residual depth rides in the Dp column dimension.

Tombstone masking lives INSIDE the tile body: with an ``ids`` operand the
per-row id row rides the same HBM→VMEM pipeline as the codes and rows
with id < 0 (holes/deletes) score −inf before the tile is written back —
deletes are O(1) id writes that never reshape the scan.

Grid (N/bn,): each step scores one item tile against all b queries and
writes a lane-dense (b, bn) tile of the (b, N) output. ``bn`` comes from
the one-hot VMEM budget (``adc_common.scan_block_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.adc_common import adc_tile_scores, scan_block_rows
from repro.kernels.common import cdiv, interpret_mode


def _kernel(codes_ref, lut_ref, out_ref):
    scores = adc_tile_scores(codes_ref[...], lut_ref[...])  # (b, bn)
    out_ref[...] = scores.astype(out_ref.dtype)


def _kernel_q(codes_ref, lut_ref, scales_ref, out_ref):
    # quantized path: int8/uint8 LUT bytes cross HBM, dequant happens here
    scores = adc_tile_scores(codes_ref[...], lut_ref[...], scales_ref[...])
    out_ref[...] = scores.astype(out_ref.dtype)


def _kernel_m(codes_ref, lut_ref, ids_ref, out_ref):
    # masked path: the (1, bn) id row broadcasts over the query axis
    scores = adc_tile_scores(codes_ref[...], lut_ref[...])
    scores = jnp.where(ids_ref[...] >= 0, scores, -jnp.inf)
    out_ref[...] = scores.astype(out_ref.dtype)


def _kernel_qm(codes_ref, lut_ref, scales_ref, ids_ref, out_ref):
    scores = adc_tile_scores(codes_ref[...], lut_ref[...], scales_ref[...])
    scores = jnp.where(ids_ref[...] >= 0, scores, -jnp.inf)
    out_ref[...] = scores.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def adc_lookup(
    lut: jax.Array,
    codes: jax.Array,
    scales: jax.Array | None = None,
    ids: jax.Array | None = None,
    *,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """lut (b, Dp, K) float, codes (N, Dp) integer  ->  scores (b, N) float32.

    With ``scales`` (b, Dp, 2) the lut is an int8/uint8 pack from
    ``adc_common.quantize_luts``; the tile body dequantizes in VMEM so the
    per-step LUT DMA moves 4× fewer bytes. With ``ids`` (N,) the tombstone
    mask applies in VMEM: rows with id < 0 come out −inf."""
    b, Dp, K = lut.shape
    N = codes.shape[0]
    bn = scan_block_rows(N, Dp, K, block_n)
    grid = (cdiv(N, bn),)
    in_specs = [
        pl.BlockSpec((bn, Dp), lambda i: (i, 0)),
        pl.BlockSpec((b, Dp, K), lambda i: (0, 0, 0)),
    ]
    operands = [codes, lut]
    kernel = {(False, False): _kernel, (True, False): _kernel_q,
              (False, True): _kernel_m, (True, True): _kernel_qm}[
        (scales is not None, ids is not None)]
    if scales is not None:
        in_specs.append(pl.BlockSpec((b, Dp, 2), lambda i: (0, 0, 0)))
        operands.append(scales)
    if ids is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i: (0, i)))
        operands.append(ids.reshape(1, N).astype(jnp.int32))
    # codes stay in their storage dtype (uint8 for K ≤ 256) all the way to
    # VMEM — the shared tile body widens per tile; widening here would
    # materialize a 4× int32 copy of the whole corpus per call.
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, N), jnp.float32),
        interpret=interpret_mode(interpret),
    )(*operands)
