"""The work a step needs, counted from shapes: FLOPs and HBM bytes.

These are the model's and the algorithm's work, not the implementation's:
padding rows, sentinel tiles, one-hot expansions and per-query re-reads of
a row that several queries probe are not counted. So an implementation that
skips holes or groups queries by list reads as the same work done faster,
and no share of a peak computed from these counts can pass 100% unless the
time measured leaves out part of the work.
"""
from __future__ import annotations

import math

import numpy as np

F32 = 4


def _mlp_flops(rows: int, dims) -> float:
    return sum(2.0 * rows * a * b + 2.0 * rows * b
               for a, b in zip(dims[:-1], dims[1:]))


def twotower_params(cfg: dict) -> int:
    e = cfg["embed_dim"]
    dims = (e, *cfg["tower_dims"])
    mlp = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    ix = cfg["index"]
    n = ix["dim"]
    return cfg["item_vocab"] * e + 2 * mlp + ix["num_codewords"] * n + n * n


def train_step(cfg: dict, batch: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one two-tower train step with the index layer:
    both towers and the index layer forward and backward, the in-batch
    hinge loss, the dense Adam update (reads param, grad, m, v and writes
    param, m, v for every parameter but R) and one GCD step on R."""
    B, L, e = batch, cfg["hist_len"], cfg["embed_dim"]
    dims = (e, *cfg["tower_dims"])
    ix = cfg["index"]
    n, K = ix["dim"], ix["num_codewords"]
    towers = 2 * _mlp_flops(B, dims)
    index = 2 * (2.0 * B * n * n)          # x·R and back through Rᵀ
    assign = 2.0 * B * n * K               # nearest codeword (no backward)
    scores = 2.0 * B * B * n + 6.0 * B * B
    fwd = B * L * e + towers + index + assign + scores + 8.0 * B * n
    bwd = 2 * (towers + index + scores) + B * (L + 1) * e + B * n
    P = twotower_params(cfg)
    adam = 12.0 * (P - n * n)
    gcd = 2.0 * n ** 3 + 4.0 * n * n
    flops = fwd + bwd + adam + gcd
    weights = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    bytes_ = (7 * F32 * (P - n * n)                 # Adam
              + 2 * B * (L + 1) * e * F32           # gathers, scatter-adds
              + 3 * 2 * weights * F32               # tower weights, f and b
              + 4 * n * n * F32)                    # R, its gradient
    return flops, float(bytes_)


def probe_lists(QR: np.ndarray, centroids: np.ndarray, nprobe: int):
    """The ``nprobe`` lists each rotated query probes (best coarse score),
    in plain ``jax.numpy``; (b, nprobe) int array on the host."""
    import jax
    import jax.numpy as jnp

    top = jax.lax.top_k(jnp.asarray(QR) @ jnp.asarray(centroids).T, nprobe)
    return np.asarray(top[1])


def live_rows_per_list(ids: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    live = np.concatenate([[0], np.cumsum(np.asarray(ids) >= 0)])
    off = np.asarray(offsets)
    return live[off[1:]] - live[off[:-1]]


def scan(lists: np.ndarray, live: np.ndarray, *, code_width: int,
         codewords: int, k: int) -> tuple[float, float]:
    """(FLOPs, bytes) of scanning one batch: every distinct live row of the
    probed lists read once with its id, the batch's LUTs, the top-k out;
    ``code_width`` additions per (query, live row)."""
    b = lists.shape[0]
    per_query = live[lists].sum(axis=1)
    distinct = live[np.unique(lists)].sum()
    flops = float(per_query.sum()) * code_width
    bytes_ = (float(distinct) * (code_width + 4)
              + b * code_width * codewords * F32 + b * k * 8)
    return flops, bytes_


def search_batch(lists: np.ndarray, live: np.ndarray, *, dim: int,
                 num_lists: int, code_width: int, codewords: int,
                 k: int) -> tuple[float, float]:
    """(FLOPs, bytes) of a whole search step on one batch: rotate, the
    query-side refresh transform, LUT build, coarse probe, scan, top-k."""
    b = lists.shape[0]
    f_scan, b_scan = scan(lists, live, code_width=code_width,
                          codewords=codewords, k=k)
    nprobe = lists.shape[1]
    flops = (2.0 * b * dim * dim * 2            # q·R0, then ·qdelta
             + 2.0 * b * dim * codewords        # LUT build
             + 2.0 * b * dim * num_lists        # coarse probe
             + f_scan
             + b * num_lists * math.log2(max(nprobe, 2)))
    bytes_ = (b_scan + b * dim * F32 + 2 * dim * dim * F32
              + num_lists * dim * F32 + codewords * dim * F32)
    return flops, bytes_
