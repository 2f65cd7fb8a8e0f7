"""Shared k-means machinery (extracted from core/pq.py and index/ivf.py).

One Lloyd's-iteration implementation serves every codebook fit in the repo:
per-subspace PQ codebooks, each level of a residual quantizer, and —
via ``vq_kmeans`` (a single-subspace special case) — the IVF coarse
quantizer's full-vector centroids. Streaming EMA updates (VQ-VAE style) live
here too as the alternative to gradient training of codebooks.

``kmeans_sharded`` is the distributed flavor: rows shard over a mesh axis,
each device assigns only its local rows, and the centroid accumulate is a
``psum`` — the same Lloyd update with a different summation order, so it
matches the single-device fit up to fp reordering (the distortion-parity
test in tests/test_distributed.py). This is what lets the sharded index
build (``index.ivf.build_sharded``) fit its coarse quantizer without ever
gathering the training rows onto one device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.quant.base import PQConfig
from repro.quant.codebook import assign, distortion, split


def kmeans_init(key: jax.Array, X: jax.Array, cfg: PQConfig) -> jax.Array:
    """Init codebooks by sampling K distinct rows per subspace."""
    m = X.shape[0]
    Xs = split(X, cfg.num_subspaces)  # (m, D, sub)
    idx = jax.random.choice(key, m, shape=(cfg.num_codewords,), replace=False)
    return jnp.transpose(Xs[idx], (1, 0, 2))  # (D, K, sub)


def kmeans_update(X: jax.Array, codebooks: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One Lloyd iteration over all D subspaces. Returns (codebooks, codes).

    Empty clusters keep their previous centroid.
    """
    D, K, _ = codebooks.shape
    codes = assign(X, codebooks)  # (m, D)
    Xs = split(X, D)  # (m, D, sub)

    def per_subspace(xd, cd):
        sums = jax.ops.segment_sum(xd, cd, num_segments=K)  # (K, sub)
        cnt = jax.ops.segment_sum(jnp.ones_like(cd, jnp.float32), cd, num_segments=K)
        return sums, cnt

    sums, cnt = jax.vmap(per_subspace, in_axes=(1, 1))(Xs, codes)  # (D, K, sub), (D, K)
    new = jnp.where(cnt[..., None] > 0, sums / jnp.maximum(cnt[..., None], 1.0), codebooks)
    return new, codes


@functools.partial(jax.jit, static_argnames=("cfg", "iters"))
def _kmeans_jit(key: jax.Array, X: jax.Array, cfg: PQConfig, iters: int):
    cb0 = kmeans_init(key, X, cfg)

    def body(cb, _):
        cb, codes = kmeans_update(X, cb)
        return cb, distortion(X, cb, codes)

    return jax.lax.scan(body, cb0, None, length=iters)


def kmeans(key: jax.Array, X: jax.Array, cfg: PQConfig, iters: int = 10):
    """Full k-means per subspace; returns (codebooks, distortion_trace).

    When the global ``repro.obs`` registry is enabled, each concrete fit
    records its per-iteration distortion trace (distribution
    ``kmeans.distortion`` + one ``kmeans_fit`` event carrying the whole
    trace) — the convergence signal behind every codebook in the repo.
    Calls traced under an outer jit skip the recording (tracers carry no
    values to record).
    """
    cb, trace = _kmeans_jit(key, X, cfg, iters)
    if obs.enabled() and not isinstance(trace, jax.core.Tracer):
        import numpy as np

        reg = obs.default_registry()
        t = np.asarray(trace, dtype=np.float64)
        dist = reg.distribution("kmeans.distortion",
                                subspaces=cfg.num_subspaces,
                                codewords=cfg.num_codewords)
        for v in t.tolist():
            dist.observe(v)
        reg.gauge("kmeans.final_distortion",
                  subspaces=cfg.num_subspaces,
                  codewords=cfg.num_codewords).set(float(t[-1]))
        reg.event("kmeans_fit", subspaces=cfg.num_subspaces,
                  codewords=cfg.num_codewords, iters=int(iters),
                  trace=t.tolist())
    return cb, trace


def vq_kmeans(key: jax.Array, X: jax.Array, num_centroids: int,
              iters: int = 10) -> jax.Array:
    """Full-vector k-means via the PQ machinery with a single subspace:
    PQConfig(1, L) codebooks (1, L, n) are exactly L centroids. Returns
    (L, n) centroids — the IVF coarse-quantizer fit."""
    cb, _ = kmeans(key, X, PQConfig(1, num_centroids), iters=iters)
    return cb[0]


# ---------------------------------------------------------------------------
# Sharded fit: per-shard assign + psum centroid accumulate under shard_map
# ---------------------------------------------------------------------------


def _sharded_lloyd_step(codebooks: jax.Array, Xs: jax.Array, w: jax.Array,
                        axis: str) -> jax.Array:
    """One Lloyd iteration over this shard's rows (runs inside shard_map).

    ``w`` is 1.0 for real rows, 0.0 for the padding that makes the row count
    divisible by the shard count — padded rows contribute nothing to either
    the sums or the counts. The cross-shard reduce is the two psums; the
    codebooks stay replicated (the same invariant the sharded searcher
    keeps: O(K) state replicated, O(N) state partitioned).
    """
    D, K, _ = codebooks.shape
    codes = assign(Xs, codebooks)                     # (m_local, D)
    Xss = split(Xs, D)                                # (m_local, D, sub)

    def per_subspace(xd, cd):
        sums = jax.ops.segment_sum(xd * w[:, None], cd, num_segments=K)
        cnt = jax.ops.segment_sum(w, cd, num_segments=K)
        return sums, cnt

    sums, cnt = jax.vmap(per_subspace, in_axes=(1, 1))(Xss, codes)
    sums = jax.lax.psum(sums, axis)
    cnt = jax.lax.psum(cnt, axis)
    return jnp.where(cnt[..., None] > 0,
                     sums / jnp.maximum(cnt[..., None], 1.0), codebooks)


def kmeans_sharded(key: jax.Array, X: jax.Array, cfg: PQConfig, *, mesh,
                   axis: str = "data", iters: int = 10) -> jax.Array:
    """Distributed ``kmeans``: rows of ``X`` shard over ``mesh``'s ``axis``.

    Init samples K rows exactly like the single-device fit (same key); each
    iteration assigns locally and accumulates centroids with a psum, so no
    device ever holds more than m/S training rows. Returns (D, K, sub)
    codebooks — numerically ≈ ``kmeans`` (identical update, shard-local
    partial sums reduce in a different order).
    """
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    m = X.shape[0]
    pad = (-m) % S
    cb = kmeans_init(key, X, cfg)
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    w = jnp.concatenate([jnp.ones((m,), jnp.float32),
                         jnp.zeros((pad,), jnp.float32)])

    step = jax.shard_map(
        functools.partial(_sharded_lloyd_step, axis=axis),
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    for _ in range(iters):
        cb = step(cb, Xp, w)
    return cb


def vq_kmeans_sharded(key: jax.Array, X: jax.Array, num_centroids: int, *,
                      mesh, axis: str = "data", iters: int = 10) -> jax.Array:
    """``vq_kmeans`` with the fit sharded over ``mesh``'s ``axis`` — the
    coarse-quantizer fit of the partitioned index build."""
    cb = kmeans_sharded(key, X, PQConfig(1, num_centroids),
                        mesh=mesh, axis=axis, iters=iters)
    return cb[0]


def codebook_ema_update(codebooks: jax.Array, X: jax.Array, codes: jax.Array,
                        decay: float = 0.99) -> jax.Array:
    """Streaming EMA codebook update (VQ-VAE style) — an alternative to
    gradient training of codebooks inside the end-to-end loop."""
    D, K, _ = codebooks.shape
    Xs = split(X, D)

    def per_subspace(xd, cd):
        sums = jax.ops.segment_sum(xd, cd, num_segments=K)
        cnt = jax.ops.segment_sum(jnp.ones_like(cd, jnp.float32), cd, num_segments=K)
        return sums, cnt

    sums, cnt = jax.vmap(per_subspace, in_axes=(1, 1))(Xs, codes)
    batch_mean = sums / jnp.maximum(cnt[..., None], 1.0)
    upd = decay * codebooks + (1.0 - decay) * batch_mean
    return jnp.where(cnt[..., None] > 0, upd, codebooks)
