"""Pallas TPU kernel: grouped ADC scan — the KV-cache member of the family.

Decode-time attention scores every query head against its *own* code
sequence: group g (one (batch, kv-head) pair) holds S coded vectors and r
query LUTs (the GQA repetition factor). This is the flat scan of
adc_lookup.py with one extra grid axis steering both the code tile and the
LUT block at the same group, sharing the one-hot-MXU tile body
(adc_common.adc_tile_scores).

Grid (g, S/bn): step (gi, i) scores tile i of group gi's codes against that
group's r LUTs. Residual depth rides in the Dp column dimension; ``bn``
comes from the one-hot VMEM budget (``adc_common.scan_block_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.adc_common import adc_tile_scores, scan_block_rows
from repro.kernels.common import cdiv, interpret_mode


def _kernel(codes_ref, lut_ref, out_ref):
    scores = adc_tile_scores(codes_ref[0], lut_ref[0])  # (r, bn)
    out_ref[...] = scores[None].astype(out_ref.dtype)


def _kernel_q(codes_ref, lut_ref, scales_ref, out_ref):
    # quantized path: the group's r LUTs ride in int8/uint8 + (r, Dp, 2)
    # scales; dequant happens in VMEM
    scores = adc_tile_scores(codes_ref[0], lut_ref[0], scales_ref[0])
    out_ref[...] = scores[None].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def adc_batch(
    lut: jax.Array,
    codes: jax.Array,
    scales: jax.Array | None = None,
    *,
    block_s: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """lut (g, r, Dp, K) float, codes (g, S, Dp) integer
    ->  scores (g, r, S) float32.

    With ``scales`` (g, r, Dp, 2) the lut is an int8/uint8 quantize_luts
    pack — the per-step LUT DMA moves 4× fewer bytes."""
    g, r, Dp, K = lut.shape
    S = codes.shape[1]
    bs = scan_block_rows(S, Dp, K, block_s)
    grid = (g, cdiv(S, bs))
    in_specs = [
        pl.BlockSpec((1, bs, Dp), lambda gi, i: (gi, i, 0)),
        pl.BlockSpec((1, r, Dp, K), lambda gi, i: (gi, 0, 0, 0)),
    ]
    operands = [codes, lut]
    kernel = _kernel
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, r, Dp, 2), lambda gi, i: (gi, 0, 0, 0)))
        operands.append(scales)
        kernel = _kernel_q
    # codes stay in their storage dtype (uint8 for K ≤ 256) all the way to
    # VMEM — the shared tile body widens per tile; widening here would
    # materialize a 4× int32 copy of the whole code cache per decode step.
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, r, bs), lambda gi, i: (gi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((g, r, S), jnp.float32),
        interpret=interpret_mode(interpret),
    )(*operands)
