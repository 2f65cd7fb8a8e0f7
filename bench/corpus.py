"""Seeded corpora and query sets for the serving cells, made on the device.

A copy of the repo's ``data.synthetic.sift_like`` generator (a Gaussian
mixture whose clusters each have a rotated, log-spaced anisotropic
covariance), changed in three ways for catalog-sized corpora:

* cluster masses are skewed (Zipf, exponent ``skew``), so inverted lists
  come out uneven as they do over a real catalog;
* the clusters draw their rotation from a small set of ``bases`` shared
  between them, so a row costs ``bases`` small matrix products instead of
  a gathered (dim, dim) basis of its own;
* rows are unit vectors (cosine retrieval), and the whole corpus is made in
  one jitted call that fills the output chunk by chunk (no second copy).

The same seed gives the same corpus. Queries are drawn from the same
mixture with their own key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 32768


def _mixture(key, dim: int, clusters: int, bases: int, skew: float,
             anisotropy: float):
    km, ks, kb = jax.random.split(key, 3)
    means = 4.0 * jax.random.normal(km, (clusters, dim))
    scales = jnp.exp(jnp.log(anisotropy) * jax.random.uniform(
        ks, (clusters, dim), minval=-0.5, maxval=0.5))
    qs, _ = jnp.linalg.qr(jax.random.normal(kb, (bases, dim, dim)))
    logits = -skew * jnp.log(jnp.arange(1, clusters + 1, dtype=jnp.float32))
    return means, scales, qs, logits


def _rows(key, n: int, mix):
    means, scales, qs, logits = mix
    kc, kz = jax.random.split(key)
    c = jax.random.categorical(kc, logits, shape=(n,))
    z = jax.random.normal(kz, (n, means.shape[1])) * scales[c]
    basis = c % qs.shape[0]
    x = sum(jnp.where((basis == b)[:, None], z @ qs[b], 0.0)
            for b in range(qs.shape[0]))
    x = x + means[c]
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "num", "dim", "clusters", "bases", "skew", "anisotropy"))
def corpus(key, *, num: int, dim: int, clusters: int, bases: int,
           skew: float, anisotropy: float):
    """(num, dim) float32 unit rows of the skewed mixture."""
    mix = _mixture(jax.random.fold_in(key, 0), dim, clusters, bases, skew,
                   anisotropy)
    steps, tail = divmod(num, CHUNK)
    out = jnp.zeros((num, dim), jnp.float32)

    def body(i, out):
        rows = _rows(jax.random.fold_in(key, i + 1), CHUNK, mix)
        return jax.lax.dynamic_update_slice(out, rows, (i * CHUNK, 0))

    if steps:
        out = jax.lax.fori_loop(0, steps, body, out)
    if tail:
        rows = _rows(jax.random.fold_in(key, steps + 1), tail, mix)
        out = jax.lax.dynamic_update_slice(out, rows, (steps * CHUNK, 0))
    return out


@functools.partial(jax.jit, static_argnames=(
    "num", "dim", "clusters", "bases", "skew", "anisotropy"))
def queries(key, corpus_key, *, num: int, dim: int, clusters: int,
            bases: int, skew: float, anisotropy: float):
    """(num, dim) unit queries from the corpus's mixture (``corpus_key``),
    drawn with their own ``key``."""
    mix = _mixture(jax.random.fold_in(corpus_key, 0), dim, clusters, bases,
                   skew, anisotropy)
    return _rows(key, num, mix)
