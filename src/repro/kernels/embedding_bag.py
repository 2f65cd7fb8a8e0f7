"""Pallas TPU kernel: EmbeddingBag(sum) via scalar-prefetch gather.

JAX has no native EmbeddingBag; the recsys hot path is a ragged gather from a
huge HBM-resident table followed by a per-bag reduction. On TPU the idiomatic
implementation is a **scalar-prefetch** kernel: the flat index array is
prefetched into SMEM, and each grid step's BlockSpec index_map uses it to DMA
the 8-row table tile holding that row HBM→VMEM — no dense one-hot, no table
copy (a one-row block would break the TPU's (8, 128) tiling rule; the body
picks the row out of the tile).

Bag reduction uses output-block revisiting: ``bag_ids`` must be sorted
ascending; consecutive grid steps that map to the same 8-bag output tile
keep it resident in VMEM and accumulate into the bag's row, zeroing the
tile on first visit.

Grid (L,): one looked-up row per step. The jit wrapper in ops.py pads L and
handles per-sample weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

#: table rows / bags per block: the f32 sublane tile
_ROWS = 8


def _kernel(idx_ref, bag_ref, w_ref, rows_ref, out_ref):
    # padded slots are neutralized by the wrapper zeroing their weight
    l = pl.program_id(0)
    tile = bag_ref[l] // _ROWS
    first = jnp.where(
        l == 0, 1, (tile != bag_ref[l - 1] // _ROWS).astype(jnp.int32))

    @pl.when(first == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[l].astype(jnp.float32)
    row = rows_ref[pl.ds(idx_ref[l] % _ROWS, 1), :].astype(jnp.float32)
    at = pl.ds(bag_ref[l] % _ROWS, 1)
    out_ref[at, :] = out_ref[at, :] + w * row


@functools.partial(jax.jit, static_argnames=("num_bags", "interpret"))
def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    bag_ids: jax.Array,
    num_bags: int,
    weights: jax.Array | None = None,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """table (V, dim); indices (L,) int32 (−1 = padding); bag_ids (L,) int32
    sorted ascending; optional weights (L,). -> (num_bags, dim) float32."""
    L = indices.shape[0]
    V, dim = table.shape
    if weights is None:
        weights = jnp.ones((L,), jnp.float32)
    valid = indices >= 0
    safe_idx = jnp.maximum(indices, 0)  # keep DMA in-bounds for padded slots
    weights = jnp.where(valid, weights.astype(jnp.float32), 0.0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # safe_idx, bag_ids, weights
        grid=(L,),
        in_specs=[
            # the tile holding this step's row, chosen by the prefetched index
            pl.BlockSpec((_ROWS, dim),
                         lambda l, idx, bags, w: (idx[l] // _ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, dim),
                               lambda l, idx, bags, w: (bags[l] // _ROWS, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (-(-num_bags // _ROWS) * _ROWS, dim), jnp.float32),
        interpret=interpret_mode(interpret),
    )(safe_idx, bag_ids, weights, table)[:num_bags]
    # bags with no entries are never visited by the kernel: zero them.
    present = jax.ops.segment_max(
        jnp.ones_like(bag_ids, jnp.float32), bag_ids, num_segments=num_bags
    )
    return jnp.where(present[:, None] > 0, out, 0.0)
