"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``<name>_ref`` is the semantic ground truth: tests sweep shapes/dtypes
and assert the kernel output is allclose to these. They are also the XLA
fallback path used on hosts where Pallas lowering is unavailable.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.adc_common import dequantize_luts


def givens_rotate_ref(xe: jax.Array, xo: jax.Array, c: jax.Array, s: jax.Array):
    """Rotate paired column planes: (m, p) × 2, cos/sin (p,) -> (ye, yo).

    ye = c·xe + s·xo ;  yo = c·xo − s·xe   (column pairs already permuted
    adjacent by the caller — see core.givens.apply_pair_rotations).
    """
    c = c.astype(xe.dtype)[None, :]
    s = s.astype(xe.dtype)[None, :]
    return c * xe + s * xo, c * xo - s * xe


def gcd_score_ref(G: jax.Array, R: jax.Array) -> jax.Array:
    """A = M − Mᵀ with M = GᵀR (paper Algorithm 2 line 3)."""
    M = G.T.astype(jnp.float32) @ R.astype(jnp.float32)
    return (M - M.T).astype(R.dtype)


def pq_assign_ref(X: jax.Array, codebooks: jax.Array) -> jax.Array:
    """Nearest codeword per subspace. X (m, n), codebooks (D, K, sub) -> (m, D)."""
    D = codebooks.shape[0]
    m, n = X.shape
    Xs = X.reshape(m, D, n // D)
    dots = jnp.einsum("mds,dks->mdk", Xs, codebooks)
    cn = jnp.sum(jnp.square(codebooks), axis=-1)
    return jnp.argmin(cn[None] - 2.0 * dots, axis=-1).astype(jnp.int32)


def adc_lookup_ref(lut: jax.Array, codes: jax.Array,
                   scales: jax.Array | None = None,
                   ids: jax.Array | None = None) -> jax.Array:
    """ADC score sum. lut (b, D, K), codes (N, D) -> (b, N).

    With ``scales`` (b, D, 2) the lut is an int8/uint8 pack from
    ``adc_common.quantize_luts`` and is dequantized first (semantic ground
    truth for the in-VMEM dequant the kernels do). With ``ids`` (N,) the
    tombstone mask is applied inside the scan: rows with id < 0 (holes and
    deletes) score −inf, so a delete is an O(1) id write and never reshapes
    the scored array."""
    if scales is not None:
        lut = dequantize_luts(lut, scales)
    D = lut.shape[1]
    g = lut[:, jnp.arange(D)[None, :], codes.astype(jnp.int32)]  # (b, N, D)
    out = jnp.sum(g, axis=-1)
    if ids is not None:
        out = jnp.where(ids[None, :] >= 0, out, -jnp.inf)
    return out


def fused_lut_ref(Q: jax.Array, qdelta: jax.Array, cb_flat: jax.Array,
                  colmap: jax.Array) -> jax.Array:
    """Rotation-fused ADC-LUT build. Q (b, n) raw queries, qdelta (n, n)
    composed query-side transform (R₀·Δ·Wᵀ — see search.flat fused refresh),
    cb_flat (Dp, K, sub) frozen flattened codebooks, colmap (Dp, D) one-hot
    mapping code column → query subspace (identity for PQ; for a depth-M RQ
    the level-major column l·D+d maps to subspace d) -> (b, Dp, K) with
    lut[b, p, k] = ⟨(Q·qdelta) subspace of column p, cb_flat[p, k]⟩.

    This is the oracle for kernels/lut_build.py: the delta is applied to the
    query block inside the tile body, so refresh never rebuilds corpus-side
    state. Full f32 precision, as the kernel: on a TPU the default matmul
    precision would round the operands to bf16."""
    hi = jax.lax.Precision.HIGHEST
    QL = jnp.dot(Q.astype(jnp.float32), qdelta.astype(jnp.float32),
                 precision=hi)                                      # (b, n)
    b, n = QL.shape
    Dp, K, sub = cb_flat.shape
    D = colmap.shape[1]
    QLs = QL.reshape(b, D, sub)
    Qexp = jnp.einsum("pd,bds->bps", colmap.astype(jnp.float32), QLs,
                      precision=hi)
    return jnp.einsum("bps,pks->bpk", Qexp, cb_flat.astype(jnp.float32),
                      precision=hi)


def adc_batch_ref(lut: jax.Array, codes: jax.Array,
                  scales: jax.Array | None = None) -> jax.Array:
    """Grouped ADC score sum (KV-cache scoring). lut (g, r, Dp, K),
    codes (g, S, Dp) -> (g, r, S) with
    out[g, r, s] = Σ_d lut[g, r, d, codes[g, s, d]].

    Accumulated with a scan over the Dp columns so the peak gather buffer is
    O(g·r·S) instead of O(g·r·S·Dp) — at S=524288 decode shapes the all-Dp
    gather costs GiBs/device (the Pallas adc_batch kernel tiles a one-hot
    matmul instead; this is the XLA-safe reference path).

    ``scales`` (g, r, Dp, 2): quantized-LUT pack, dequantized up front.
    """
    if scales is not None:
        lut = dequantize_luts(lut, scales)
    g, r, Dp, K = lut.shape
    S = codes.shape[1]
    lut_d = jnp.moveaxis(lut.astype(jnp.float32), -2, 0)    # (Dp, g, r, K)
    codes_d = jnp.moveaxis(codes.astype(jnp.int32), -1, 0)  # (Dp, g, S)

    def add_one(acc, dl):
        l_d, c_d = dl  # (g, r, K), (g, S)
        return acc + jnp.take_along_axis(l_d, c_d[:, None, :], axis=-1), None

    acc0 = jnp.zeros((g, r, S), jnp.float32)
    out, _ = jax.lax.scan(add_one, acc0, (lut_d, codes_d))
    return out


#: selected blocks ``ivf_adc_ref`` scores per step
_REF_STEPS = 1024


def ivf_adc_ref(lut: jax.Array, codes: jax.Array, block_idx: jax.Array,
                block_query: jax.Array, *, block_size: int = 128,
                scales: jax.Array | None = None,
                ids: jax.Array | None = None,
                hole_block: int | None = None) -> jax.Array:
    """Selected-block ADC scan. lut (b, D, K), codes (cap, D),
    block_idx/block_query (S,) -> (S, block_size): the scores of tile
    ``block_idx[s]`` of the CSR codes array under query ``block_query[s]``'s
    LUT (gather formulation; the Pallas kernel must match).

    ``scales`` (b, D, 2): quantized-LUT pack, dequantized up front.
    ``ids`` (cap,): tombstone mask — rows with id < 0 score −inf inside the
    scan, so holes and deletes never surface however the caller post-
    processes (the added coarse term is finite and cannot resurrect them).
    ``hole_block``: every row of a step scheduled on this block scores
    −inf, whatever its codes and ids (the kernel skips such steps)."""
    if scales is not None:
        lut = dequantize_luts(lut, scales)

    def scan(sched):
        bi, bq = sched
        rows = bi[:, None] * block_size + jnp.arange(block_size)     # (s, bn)
        c = codes[rows].astype(jnp.int32)  # gather in storage dtype, widen
        l_sel = lut[bq.astype(jnp.int32)]                            # (s, D, K)
        g = jnp.take_along_axis(
            l_sel[:, None, :, :], c[..., None], axis=-1
        )[..., 0]                                                    # (s, bn, D)
        out = jnp.sum(g, axis=-1).astype(jnp.float32)
        if ids is not None:
            out = jnp.where(ids[rows] >= 0, out, -jnp.inf)
        if hole_block is not None:
            out = jnp.where((bi == hole_block)[:, None], -jnp.inf, out)
        return out

    # the schedule is scanned in steps of _REF_STEPS: the TPU lowers the
    # gather with an index array of (steps, bn, D, 3) int32 — 9.4 GB for
    # one 64-query batch at nprobe 32 over a 1.2M-row index if taken at once
    S = block_idx.shape[0]
    step = max(1, min(S, _REF_STEPS))
    pad = (-S) % step
    sched = tuple(jnp.pad(a.astype(jnp.int32), (0, pad)).reshape(-1, step)
                  for a in (block_idx, block_query))
    return jax.lax.map(scan, sched).reshape(-1, block_size)[:S]


def embedding_bag_ref(table: jax.Array, indices: jax.Array, bag_ids: jax.Array,
                      num_bags: int, weights: jax.Array | None = None) -> jax.Array:
    """EmbeddingBag(sum): table (V, dim), flat indices (L,), sorted bag_ids (L,)
    -> (num_bags, dim). JAX has no native EmbeddingBag — this is the
    take + segment_sum construction the system uses everywhere."""
    rows = jnp.take(table, indices, axis=0)
    if weights is not None:
        rows = rows * weights[:, None].astype(rows.dtype)
    return jax.ops.segment_sum(rows, bag_ids, num_segments=num_bags)


def topk_merge_ref(scores: jax.Array, ids: jax.Array,
                   k: int) -> tuple[jax.Array, jax.Array]:
    """Merge concatenated per-shard top-k runs into one global top-k.

    scores/ids (b, C) — C = shards·k after the sharded searcher's
    all_gather — under the SearchResult padding contract: slots past the
    candidate pool carry score −inf, and every −inf slot gets id −1 so
    padding survives the merge. Returns (b, k), padded the same way when
    k > C.

    Tie-breaking is deterministic: equal scores rank by ascending id
    (a lexicographic two-key sort, not ``top_k``'s positional tie-break),
    so the merged top-k is a pure function of the candidate SET — invariant
    to shard order, tile order, and whichever batch composition a serving
    request landed in (the repro.serve determinism contract).
    """
    b, C = scores.shape
    kk = min(k, C)
    # ascending (−score, id): equal scores break to the smaller id. −inf
    # slots sort last regardless of id and are re-padded to −1 below.
    neg_sorted, top_ids = jax.lax.sort(
        (-scores, ids.astype(jnp.int32)), dimension=1, num_keys=2)
    top_scores = -neg_sorted[:, :kk]
    top_ids = top_ids[:, :kk]
    top_ids = jnp.where(jnp.isfinite(top_scores), top_ids, -1)
    if kk < k:
        top_scores = jnp.pad(top_scores, ((0, 0), (0, k - kk)),
                             constant_values=-jnp.inf)
        top_ids = jnp.pad(top_ids, ((0, 0), (0, k - kk)),
                          constant_values=-1)
    return top_scores, top_ids


def streaming_topk_ref(tile_scores, tile_ids,
                       k: int) -> tuple[jax.Array, jax.Array]:
    """Incremental top-k merge over a stream of corpus tiles.

    tile_scores: sequence of (b, t_i) score blocks; tile_ids: matching
    (t_i,) global row ids (−1 = padding — masked to −inf here, exactly
    like the scan's merge body). Folds each tile into a (b, k) carry via
    topk_merge_ref — the semantic ground truth for the streaming exact
    scan in search/exact.py.

    With distinct scores the result is invariant to tile order and equal to
    a one-shot top_k over the full concatenation (the tile-order-invariance
    test in tests/test_kernels.py pins exactly that).
    """
    b = tile_scores[0].shape[0]
    acc_s = jnp.full((b, k), -jnp.inf, jnp.float32)
    acc_i = jnp.full((b, k), -1, jnp.int32)
    for s, ids in zip(tile_scores, tile_ids):
        ids = ids.astype(jnp.int32)
        s = jnp.where(ids[None, :] >= 0, s.astype(jnp.float32), -jnp.inf)
        cs = jnp.concatenate([acc_s, s], axis=1)
        ci = jnp.concatenate(
            [acc_i, jnp.broadcast_to(ids[None, :], s.shape)], axis=1)
        acc_s, acc_i = topk_merge_ref(cs, ci, k)
    return acc_s, acc_i
