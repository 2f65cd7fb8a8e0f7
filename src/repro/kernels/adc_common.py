"""Shared tile body of the ADC kernel family.

Every ADC scan in the system — the flat corpus scan (adc_lookup.py), the
IVF selected-block scan (ivf_adc.py), and the grouped KV-cache scorer
(adc_batch.py) — scores a VMEM tile of PQ/RQ codes against per-query lookup
tables with the same **one-hot matmul trick** (DESIGN.md §2): gathers are
lane-hostile on TPU, so the (bn, Dp·K) one-hot expansion of the code tile is
contracted against the reshaped LUT on the MXU. The one-hot tile lives only
in VMEM and is rebuilt per grid step, so its size (``scan_block_rows``)
sets how many code rows a step can take.

The family is parameterized by residual depth purely through the column
dimension: a depth-M residual quantizer presents ``Dp = M·D`` code columns
and a (b, M·D, K) LUT (quant.rq flattens the level axis), so multi-level
schemes reuse these kernels unchanged.

Quantized LUTs (the FAISS/ScaNN int8 trick): the scan is bandwidth-bound at
large batch, and the LUT is the only per-query operand streamed into every
tile, so storing it int8/uint8 with per-(query, column) scales divides that
HBM traffic by 4. ``quantize_luts`` produces the (qlut, scales) pack;
``adc_tile_scores`` dequantizes in VMEM right before the MXU contraction, so
the f32 tables never exist outside the tile body.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: LUT dtypes the scan kernels accept. "float32" means an unquantized plain
#: array; the integer dtypes mean a (qlut, scales) pack from quantize_luts.
LUT_DTYPES = ("float32", "int8", "uint8")


def quantize_luts(lut: jax.Array, dtype: str) -> tuple[jax.Array, jax.Array]:
    """Quantize ADC tables per (query, code-column) subspace.

    lut (..., Dp, K) float -> (qlut (..., Dp, K) int8|uint8,
    scales (..., Dp, 2) float32) where scales[..., 0] is the dequant scale
    and scales[..., 1] the offset: ``lut ≈ qlut * scale + offset``.

    int8 is symmetric (offset 0, scale = amax/127 — sign-preserving, the
    right choice for inner-product tables); uint8 is asymmetric affine over
    [min, max]. A constant column (amax or range 0) would produce scale 0
    and a divide-by-zero on the encode side, so scale is clamped to 1 there;
    the column dequantizes exactly via the offset.
    """
    lut = lut.astype(jnp.float32)
    if dtype == "int8":
        amax = jnp.max(jnp.abs(lut), axis=-1)
        scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
        offset = jnp.zeros_like(scale)
        q = jnp.clip(jnp.round(lut / scale[..., None]), -127, 127)
        qlut = q.astype(jnp.int8)
    elif dtype == "uint8":
        lo = jnp.min(lut, axis=-1)
        hi = jnp.max(lut, axis=-1)
        rng = hi - lo
        scale = jnp.where(rng == 0.0, 1.0, rng / 255.0)
        offset = lo
        q = jnp.clip(jnp.round((lut - lo[..., None]) / scale[..., None]),
                     0, 255)
        qlut = q.astype(jnp.uint8)
    else:
        raise ValueError(f"quantize_luts: dtype must be int8|uint8, "
                         f"got {dtype!r}")
    return qlut, jnp.stack([scale, offset], axis=-1)


def dequantize_luts(qlut: jax.Array, scales: jax.Array) -> jax.Array:
    """Invert quantize_luts: (..., Dp, K) int + (..., Dp, 2) -> f32 tables."""
    return (qlut.astype(jnp.float32) * scales[..., 0][..., None]
            + scales[..., 1][..., None])


#: VMEM bytes the f32 (rows, Dp, K) one-hot tile of one scan step may take.
#: The TPU compiler scopes a kernel's intermediates to 16 MiB by default.
ONEHOT_VMEM_BYTES = 8 << 20


def scan_block_rows(rows: int, Dp: int, K: int, preferred: int = 1024) -> int:
    """Code rows per scan step: the most that keep the one-hot tile within
    ``ONEHOT_VMEM_BYTES`` (so the block shrinks as RQ depth grows Dp), a
    multiple of 128 because the rows land on the lane axis of the scores,
    and all of them when there are fewer."""
    fit = max(128, (ONEHOT_VMEM_BYTES // (Dp * K * 4)) // 128 * 128)
    bn = min(preferred, fit)
    return rows if rows <= bn else bn


def adc_tile_scores(codes: jax.Array, lut: jax.Array,
                    scales: jax.Array | None = None) -> jax.Array:
    """Score one code tile against a LUT batch inside a kernel body.

    codes (bn, Dp) integer, lut (b, Dp, K) float -> (b, bn) float32 with
    out[q, n] = Σ_d lut[q, d, codes[n, d]] — rows on the lane axis, so every
    caller stores a lane-dense tile.

    With ``scales`` (b, Dp, 2) the lut is an integer table from
    quantize_luts and is dequantized here, in VMEM, after the cheap int
    load — the whole point: only the int8 bytes cross HBM. The contraction
    runs at full f32 precision: the one-hot side is exact, and the sums must
    match the gather reference, not a bf16 pass of it.
    """
    codes = codes.astype(jnp.int32)
    if scales is not None:
        lut = dequantize_luts(lut, scales)
    lut = lut.astype(jnp.float32)
    b, Dp, K = lut.shape
    bn = codes.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, Dp, K), 2)
    onehot = (iota == codes[:, :, None]).astype(jnp.float32)
    return jax.lax.dot_general(
        lut.reshape(b, Dp * K),
        onehot.reshape(bn, Dp * K),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (b, bn)
