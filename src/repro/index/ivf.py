"""IVF index build: coarse quantizer + residual PQ/RQ in a CSR pytree.

The paper deploys T(X) = φ(XR)Rᵀ as an ANN index; a flat ADC scan touches
every item per query. This module adds the standard production refinement
(cf. Transformed Residual Quantization, arXiv:1512.06925): a ``quant.VQ``
coarse quantizer over the *rotated* vectors partitions the corpus into
``num_lists`` inverted lists, and a residual quantizer (``quant.PQ`` at
depth 1, ``quant.RQ`` above) encodes the **residual** x·R − c(x) instead of
the raw vector. Scores then decompose exactly as

    ⟨q·R, x·R⟩ ≈ ⟨q·R, c_l⟩  +  Σ_d LUT[d, code_d]      (coarse + residual)

so a query only scans the ``nprobe`` lists with the best coarse term. Both
quantizers are protocol objects from ``repro.quant``: the index is agnostic
to the residual scheme — codes are ``code_width`` integer columns and LUTs
are (b, code_width, K), whatever the depth.

Memory layout (the whole index is one jit-traceable pytree):

  * ``codes (cap, Dp)`` / ``ids (cap,)`` — all lists concatenated, CSR style
    (Dp = quantizer.code_width: D for PQ, M·D for depth-M RQ).
  * ``list_offsets (L+1,)`` — row ranges; every offset is a multiple of
    ``block_size`` so a list is an integer number of kernel tiles and the
    Pallas scan (kernels/ivf_adc.py) can DMA list blocks straight from HBM
    by block index — no gathers.
  * holes (padding rows and tombstones from ``maintain.remove``) carry
    ``id = −1`` and are masked out at score time; one all-hole sentinel
    block sits at the end of the array as the target for out-of-range
    block indices of shorter-than-max lists.

Rotations enter twice: ``build`` consumes the GCD-learned R, and
``maintain.refresh_rotation`` keeps the index servable across further GCD
steps without touching the stored codes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from typing import NamedTuple

from repro import quant


class IVFPQConfig(NamedTuple):
    """Static build parameters.

    ``num_lists``: coarse cells L (scan work per query ≈ nprobe/L of corpus).
    ``pq``: residual quantizer per-level config (D subspaces × K codewords).
    ``depth``: residual levels M — 1 builds a ``quant.PQ``, >1 a ``quant.RQ``
    (M·D code bytes/item for strictly lower distortion).
    ``block_size``: CSR alignment = Pallas tile rows; lists are padded to a
    multiple of it.
    ``lut_dtype``: ADC-table precision streamed by the scan kernels
    ("float32" | "int8" | "uint8"; integer dtypes carry per-subspace scales
    and dequantize in VMEM — 4× less LUT HBM traffic per tile).
    """

    num_lists: int
    pq: quant.PQConfig
    block_size: int = 128
    depth: int = 1
    lut_dtype: str = "float32"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IVFPQIndex:
    """Servable IVF index. Array/quantizer fields are pytree children;
    ``block_size`` is static aux data so jit specializes on the tile shape."""

    R: jax.Array              # (n, n) GCD-learned rotation
    coarse: quant.VQ          # coarse quantizer (L centroids, rotated space)
    quantizer: quant.Quantizer  # residual quantizer (quant.PQ or quant.RQ)
    codes: jax.Array          # (cap, Dp) residual codes, CSR by list
    #                           (uint8 when K ≤ 256, else int32 — see pack)
    ids: jax.Array            # (cap,) int32 item ids, −1 = hole/tombstone
    list_offsets: jax.Array   # (L+1,) int32, multiples of block_size
    block_size: int = 128

    def tree_flatten(self):
        children = (self.R, self.coarse, self.quantizer, self.codes,
                    self.ids, self.list_offsets)
        return children, self.block_size

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, block_size=aux)

    # -- compatibility views ----------------------------------------------
    @property
    def centroids(self) -> jax.Array:
        """(L, n) coarse centroids (the old pre-quant array field)."""
        return self.coarse.centroids

    @property
    def codebooks(self) -> jax.Array:
        """Residual codebooks: (D, K, sub) for PQ, (M, D, K, sub) for RQ."""
        return self.quantizer.codebooks

    # -- static shape facts ------------------------------------------------
    @property
    def num_lists(self) -> int:
        return self.coarse.num_centroids

    @property
    def dim(self) -> int:
        return self.coarse.dim

    @property
    def capacity(self) -> int:
        """Total CSR rows, including padding and the sentinel hole block."""
        return self.codes.shape[0]

    @property
    def sentinel_block(self) -> int:
        """Block index of the trailing all-hole block (see module doc)."""
        return self.capacity // self.block_size - 1

    def num_items(self) -> jax.Array:
        return jnp.sum(self.ids >= 0)

    def max_list_blocks(self) -> int:
        """Longest list measured in blocks — the static probe-window size
        for search. Host-sync on concrete offsets (pure numpy so it stays
        usable inside an outer jit trace closing over a concrete index)."""
        lens = np.diff(np.asarray(self.list_offsets))
        return max(int(lens.max()) // self.block_size, 1)


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------


def encode(XR: jax.Array, coarse: quant.VQ,
           quantizer: quant.Quantizer) -> tuple[jax.Array, jax.Array]:
    """Assign lists and residual-encode already-rotated vectors.

    Returns (list_ids (m,), codes (m, Dp)). Pure jnp — also the "full
    rebuild" oracle that ``maintain.refresh_rotation`` is tested against.
    """
    list_ids = coarse.assign(XR)
    residuals = XR - coarse.centroids[list_ids]
    return list_ids, quantizer.encode(residuals)


def pack(R: jax.Array, coarse: quant.VQ, quantizer: quant.Quantizer,
         codes: jax.Array, list_ids: jax.Array,
         ids: jax.Array, block_size: int = 128) -> IVFPQIndex:
    """Lay encoded items out in block-aligned CSR order (host-side; numpy).

    Each list is padded to a multiple of ``block_size`` with hole rows
    (id −1, code 0) and a sentinel all-hole block is appended.
    """
    list_ids = np.asarray(list_ids)
    codes = np.asarray(codes)
    ids = np.asarray(ids, dtype=np.int32)
    L = coarse.num_centroids
    Dp = codes.shape[1]

    counts = np.bincount(list_ids, minlength=L)
    padded = -(-counts // block_size) * block_size  # per-list rounded up
    offsets = np.zeros(L + 1, dtype=np.int32)
    np.cumsum(padded, out=offsets[1:])
    cap = int(offsets[-1]) + block_size  # + sentinel hole block

    codes_out = np.zeros((cap, Dp), dtype=np.dtype(quantizer.code_dtype))
    ids_out = np.full((cap,), -1, dtype=np.int32)

    order = np.argsort(list_ids, kind="stable")
    sorted_lists = list_ids[order]
    # rank of each item within its list = position − start of its run
    run_starts = np.zeros(L, dtype=np.int64)
    np.cumsum(counts[:-1], out=run_starts[1:])
    ranks = np.arange(len(order)) - run_starts[sorted_lists]
    dest = offsets[sorted_lists] + ranks
    codes_out[dest] = codes[order]
    ids_out[dest] = ids[order]

    return IVFPQIndex(
        R=jnp.asarray(R),
        coarse=jax.tree.map(jnp.asarray, coarse),
        quantizer=jax.tree.map(jnp.asarray, quantizer),
        codes=jnp.asarray(codes_out),
        ids=jnp.asarray(ids_out),
        list_offsets=jnp.asarray(offsets),
        block_size=block_size,
    )


def build(key: jax.Array, X: jax.Array, R: jax.Array, cfg: IVFPQConfig, *,
          ids: jax.Array | None = None, coarse_iters: int = 10,
          pq_iters: int = 10, train_size: int | None = None) -> IVFPQIndex:
    """End-to-end index build from raw vectors and a learned rotation.

    ``train_size`` caps the sample used for the k-means fits (the full
    corpus is always encoded). Host-side orchestration around jit'd pieces —
    build is offline; serving (search/maintain) is the jit'd hot path.
    """
    kc, kp = jax.random.split(key)
    XT = (X if train_size is None else X[:train_size]) @ R
    coarse = quant.VQ.fit(kc, XT, cfg.num_lists, iters=coarse_iters)
    train_lists = coarse.assign(XT)
    quantizer, _ = quant.fit_quantizer(
        kp, XT - coarse.centroids[train_lists], cfg.pq,
        depth=cfg.depth, iters=pq_iters,
    )
    del XT, train_lists
    # encode in row chunks: the (rows, L) coarse and (rows, D, K) residual
    # assignment scores of a whole corpus would not fit on one device
    parts = [_encode_rows(X[i:i + ENCODE_ROWS], R, coarse, quantizer)
             for i in range(0, X.shape[0], ENCODE_ROWS)]
    list_ids = np.concatenate([np.asarray(p[0]) for p in parts])
    codes = np.concatenate([np.asarray(p[1]) for p in parts])
    if ids is None:
        ids = jnp.arange(X.shape[0], dtype=jnp.int32)
    return pack(R, coarse, quantizer, codes, list_ids, ids,
                block_size=cfg.block_size)


#: corpus rows ``build`` rotates, assigns and encodes per device step.
ENCODE_ROWS = 16384


@jax.jit
def _encode_rows(X: jax.Array, R: jax.Array, coarse: quant.VQ,
                 quantizer: quant.Quantizer) -> tuple[jax.Array, jax.Array]:
    """``encode`` of one chunk of raw rows, codes in their storage dtype."""
    list_ids, codes = encode(X @ R, coarse, quantizer)
    return list_ids, codes.astype(quantizer.code_dtype)


# ---------------------------------------------------------------------------
# Partitioned build: the corpus lives sharded, each shard a local CSR
# ---------------------------------------------------------------------------


def shard_split(index: IVFPQIndex, num_shards: int) -> list[IVFPQIndex]:
    """Repartition a built index into ``num_shards`` per-shard CSRs.

    Items map to shards by contiguous id-rank range (shard s owns the
    s-th of S equal slices of the sorted live ids — balanced within one
    row for any id space); every shard keeps the SHARED R / coarse /
    residual quantizer and repacks only its own rows into block-aligned
    lists — codes are carried over, never re-encoded, so a shard's row
    scores are bit-identical to the source index's. This is the parity
    path of the ``repro.search`` ``*_sharded`` backends: attach the same
    single-device build, redistributed.
    """
    ids = np.asarray(index.ids)
    codes = np.asarray(index.codes)
    offsets = np.asarray(index.list_offsets)
    live = ids >= 0
    row_list = np.searchsorted(offsets, np.arange(len(ids)), side="right") - 1
    row_list = np.clip(row_list, 0, index.num_lists - 1)
    # Partition by id RANK, not id value: ranks are dense whatever the id
    # space (sparse external ids from build(ids=...)/maintain.add would
    # otherwise collapse onto one shard), so shards stay balanced within
    # one row, and for the common dense 0..N−1 ids rank == id — contiguous
    # ranges either way.
    live_ids = ids[live]
    rank = np.empty(live_ids.size, dtype=np.int64)
    rank[np.argsort(live_ids, kind="stable")] = np.arange(live_ids.size)
    shard_of = np.full(ids.shape, -1, dtype=np.int64)
    shard_of[live] = (rank * num_shards) // max(live_ids.size, 1)
    parts = []
    for s in range(num_shards):
        m = shard_of == s
        parts.append(pack(index.R, index.coarse, index.quantizer,
                          codes[m], row_list[m], ids[m],
                          block_size=index.block_size))
    return parts


def build_sharded(key: jax.Array, chunks, R: jax.Array, cfg: IVFPQConfig, *,
                  coarse_iters: int = 10, pq_iters: int = 10,
                  train_size: int | None = None, mesh=None,
                  axis: str = "data") -> list[IVFPQIndex]:
    """Host-sharded ingest: one local index per corpus chunk.

    ``chunks`` is a sequence of (rows_s, n) arrays — one per shard — that
    are rotated and encoded one at a time, so the full corpus never
    materializes on one device: the only cross-chunk state is the training
    sample (capped at ``train_size`` rows — default 65536, NEVER the full
    corpus, or the sample concat would defeat the chunked ingest) taken
    from the chunk heads, and the O(n² + L·n + D·K·sub) quantizers it
    fits. Item ids are global (chunk-order offsets). When ``mesh`` is
    given the coarse fit runs as a sharded k-means
    (``quant.kmeans.kmeans_sharded`` — per-shard assign + psum
    accumulate); the residual fit stays on the sample either way (it is
    already capped). Returns per-shard indexes for
    ``search.attach_shards``.
    """
    chunks = [jnp.asarray(c) for c in chunks]
    n_total = sum(int(c.shape[0]) for c in chunks)
    cap = min(65536 if train_size is None else train_size, n_total)
    R = jnp.asarray(R)

    # training sample: heads of the chunks, rotated chunk by chunk
    sample, have = [], 0
    for c in chunks:
        if have >= cap:
            break
        take = min(int(c.shape[0]), cap - have)
        sample.append(c[:take] @ R.astype(c.dtype))
        have += take
    XT = jnp.concatenate(sample) if len(sample) > 1 else sample[0]

    kc, kp = jax.random.split(key)
    if mesh is not None:
        centroids = quant.kmeans.vq_kmeans_sharded(
            kc, XT, cfg.num_lists, mesh=mesh, axis=axis, iters=coarse_iters)
        coarse = quant.VQ(centroids=centroids)
    else:
        coarse = quant.VQ.fit(kc, XT, cfg.num_lists, iters=coarse_iters)
    train_lists = coarse.assign(XT)
    quantizer, _ = quant.fit_quantizer(
        kp, XT - coarse.centroids[train_lists], cfg.pq,
        depth=cfg.depth, iters=pq_iters,
    )

    parts, start = [], 0
    for c in chunks:
        XRc = c @ R.astype(c.dtype)
        list_ids, codes = encode(XRc, coarse, quantizer)
        ids = jnp.arange(start, start + c.shape[0], dtype=jnp.int32)
        start += int(c.shape[0])
        parts.append(pack(R, coarse, quantizer, codes, list_ids, ids,
                          block_size=cfg.block_size))
    return parts
