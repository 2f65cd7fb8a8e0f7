"""Embedding-table substrate for the recsys family.

JAX has neither ``nn.EmbeddingBag`` nor a sharded embedding primitive; both
are built here (kernel_taxonomy §RecSys note — "this IS part of the system"):

  * ``bag_lookup``       EmbeddingBag(sum/mean) = take + segment_sum
                         (Pallas scalar-prefetch kernel on the hot path)
  * ``sharded_lookup``   row-sharded table lookup under shard_map: each shard
                         masks the ids it owns, gathers locally, and psums —
                         O(B·dim) collective instead of all-gathering the
                         (possibly multi-GB) table.

The naive path (``jnp.take`` on a sharded table, XLA inserts the all-gather)
is kept on purpose: it is the §Perf hillclimb baseline for the recsys cells.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops


def lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Plain gather; under pjit XLA typically all-gathers a sharded table."""
    return jnp.take(table, ids, axis=0)


def bag_lookup(table: jax.Array, ids: jax.Array, *, combiner: str = "mean",
               use_kernel: bool = False) -> jax.Array:
    """EmbeddingBag over the last axis of ids: (..., L) -> (..., dim).

    ids < 0 are padding. ``use_kernel=True`` routes through the Pallas
    scalar-prefetch kernel (single-host path).
    """
    lead = ids.shape[:-1]
    L = ids.shape[-1]
    flat = ids.reshape(-1, L)
    B = flat.shape[0]
    valid = flat >= 0
    if use_kernel:
        bag_ids = jnp.repeat(jnp.arange(B, dtype=jnp.int32), L)
        out = kops.embedding_bag(
            table, flat.reshape(-1).astype(jnp.int32), bag_ids, B
        )
    else:
        rows = jnp.take(table, jnp.maximum(flat, 0), axis=0)
        rows = jnp.where(valid[..., None], rows, 0.0)
        out = jnp.sum(rows, axis=1)
    if combiner == "mean":
        cnt = jnp.maximum(jnp.sum(valid, axis=1), 1)
        out = out / cnt[:, None].astype(out.dtype)
    return out.reshape(*lead, table.shape[-1])


def sharded_lookup(table: jax.Array, ids: jax.Array, mesh, axis: str = "model",
                   table_spec: P | None = None) -> jax.Array:
    """Row-sharded lookup: table (V, dim) sharded on rows over ``axis``;
    ids replicated (or batch-sharded). Returns embeddings with ids' sharding.

    Each shard owns rows [lo, hi); out-of-range ids contribute 0 and the psum
    reassembles the full rows — total collective traffic is one (B, dim)
    psum instead of a (V, dim) all-gather.
    """
    V, dim = table.shape
    n_shards = mesh.shape[axis]
    table_spec = table_spec if table_spec is not None else P(axis, None)
    ids_spec = P()  # replicated ids inside the region

    def local(table_l, ids_l):
        shard = jax.lax.axis_index(axis)
        rows_per = V // n_shards
        lo = shard * rows_per
        local_ids = ids_l - lo
        ok = (local_ids >= 0) & (local_ids < rows_per) & (ids_l >= 0)
        safe = jnp.clip(local_ids, 0, rows_per - 1)
        out = jnp.take(table_l, safe, axis=0)
        out = jnp.where(ok[..., None], out, 0.0)
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(table_spec, ids_spec), out_specs=P(),
        check_vma=False,
    )(table, ids)
