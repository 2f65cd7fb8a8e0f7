"""Append buffers: the device-side staging lane of the churn subsystem.

A live index cannot afford a CSR repack per insert — ``ivf.pack`` is a
host-side relayout of the whole codes array. Instead, new rows land in a
fixed-capacity ``StagingBuffer``: already rotated + residual-encoded (so
they score through the exact same LUTs as the main CSR), tagged with their
target coarse list, and scanned by a small flat-ADC side pass whose padded
top-k merges into the main scan's result via ``kernels.ops.topk_merge`` —
the same −inf/−1 contract as the cross-shard merge. ``churn.ops.flush``
later folds staged rows into CSR holes; until then they are served from
here, so an add is visible to the very next query.

The buffer is a pytree with FIXED shapes: staging, serving, and flushing
never change the array shapes the compiled executables were traced with
(free slots carry id −1 and score −inf through the same in-kernel tombstone
mask as CSR holes), which is what keeps the Engine's compile cache warm
through sustained churn. Sharded states stack one buffer per shard on a
leading axis and each shard's side pass runs inside the shard_map local
body — staged rows never cross devices until a rebalance.

No ``repro.search`` imports here: this module sits below the searcher layer
(search/flat.py, search/ivf.py and search/sharded.py all call into it), so
it only speaks the ``index.search`` result/padding vocabulary.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.index import search as index_search
from repro.kernels import ops as kops
from repro.kernels.common import use_kernels


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StagingBuffer:
    """Fixed-capacity append buffer (one per index, or per shard stacked on
    a leading axis). A slot is free iff its id is −1; ``lists`` holds each
    staged row's coarse-list assignment so the side pass can add the same
    coarse term ⟨q·R, c_l⟩ the main scan adds per block."""

    codes: jax.Array  # (cap_b, Dp) residual codes — or (S, cap_b, Dp)
    ids: jax.Array    # (cap_b,) int32 item ids, −1 = free — or (S, cap_b)
    lists: jax.Array  # (cap_b,) int32 target coarse list — or (S, cap_b)

    @property
    def capacity(self) -> int:
        """Slots per buffer (per shard for stacked buffers)."""
        return self.ids.shape[-1]


def empty(capacity: int, code_width: int, code_dtype, *,
          shards: int | None = None) -> StagingBuffer:
    """An all-free buffer matching an index's code layout. ``shards``
    stacks one buffer per shard on a leading axis (placement is the
    caller's job — ``churn.ops.with_staging`` partitions it like the CSR)."""
    lead = () if shards is None else (shards,)
    return StagingBuffer(
        codes=jnp.zeros(lead + (capacity, code_width),
                        dtype=jnp.dtype(code_dtype)),
        ids=jnp.full(lead + (capacity,), -1, jnp.int32),
        lists=jnp.zeros(lead + (capacity,), jnp.int32),
    )


def staged_topk(buf: StagingBuffer, QR: jax.Array, lut, centroids, k: int, *,
                use_kernel: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """The flat-ADC side pass: score every staged row under the SAME LUT
    pack the main scan streams (staged rows are encoded against the same
    frozen quantizers, so one LUT build serves both lanes) and return a
    padded (b, k) top-k. Free slots mask to −inf inside the tile body via
    the ids operand — the buffer scans at fixed shape whatever its fill."""
    lut, scales = index_search.split_lut_pack(lut)
    res = kops.adc_lookup(lut, buf.codes, scales, buf.ids,
                          use_kernel=use_kernels(use_kernel))  # (b, cap_b)
    coarse = QR @ centroids.T                             # (b, L)
    scores = res + jnp.take(coarse, buf.lists, axis=1)
    return index_search.topk_padded(scores, buf.ids, k)


def merge_staged(res: index_search.SearchResult, buf: StagingBuffer,
                 QR: jax.Array, lut, centroids, k: int, *,
                 use_kernel: bool | None = None
                 ) -> index_search.SearchResult:
    """Fold the staging side pass into a main-scan result: concatenate the
    two padded top-k runs and re-top-k (``kernels.ops.topk_merge`` — the
    one merge the sharded searchers already use). ``scanned`` grows by the
    live staged rows, keeping the scan-work metric honest."""
    s, i = staged_topk(buf, QR, lut, centroids, k, use_kernel=use_kernel)
    scores, ids = kops.topk_merge(
        jnp.concatenate([res.scores, s], axis=1),
        jnp.concatenate([res.ids, i], axis=1), k)
    scanned = res.scanned + jnp.sum(buf.ids >= 0).astype(res.scanned.dtype)
    return index_search.SearchResult(scores=scores, ids=ids, scanned=scanned)
