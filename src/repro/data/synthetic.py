"""Synthetic data generators (DESIGN.md §8: the paper's industrial click log,
MovieLens and Amazon-Books cannot ship, so every family gets a generator with
matched statistics — power-law popularity, anisotropic embeddings, etc.).

Everything is a pure function of (seed, step) so the pipeline is trivially
checkpointable and deterministic across restarts/elastic re-meshes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Fixed-embedding vectors (SIFT1M stand-in for §3.1 / Fig 2)
# ---------------------------------------------------------------------------

def sift_like(key: jax.Array, num: int, dim: int, num_clusters: int = 16,
              anisotropy: float = 8.0) -> jax.Array:
    """Gaussian mixture with per-cluster anisotropic covariance.

    Real SIFT has strongly correlated coordinates, which is exactly why OPQ
    rotations help; isotropic Gaussians would make the rotation a no-op. Each
    cluster gets a random rotation × log-spaced scales covariance.
    """
    kc, km, kr, ks, ka = jax.random.split(key, 5)
    means = 4.0 * jax.random.normal(km, (num_clusters, dim))
    scales = jnp.exp(
        jnp.log(anisotropy)
        * jax.random.uniform(ks, (num_clusters, dim), minval=-0.5, maxval=0.5)
    )
    # random orthogonal basis per cluster via QR
    zs = jax.random.normal(kr, (num_clusters, dim, dim))
    qs, _ = jnp.linalg.qr(zs)
    assign = jax.random.randint(kc, (num,), 0, num_clusters)
    z = jax.random.normal(ka, (num, dim))
    z = z * scales[assign]
    # rotate in row chunks: the gathered per-row bases are (rows, dim, dim),
    # 19.7 GB for a 1.2M-item catalog at dim 32 if taken at once
    z = jnp.concatenate([
        jnp.einsum("nd,nde->ne", z[i:i + _ROTATE_ROWS],
                   qs[assign[i:i + _ROTATE_ROWS]])
        for i in range(0, num, _ROTATE_ROWS)])
    return z + means[assign]


#: rows ``sift_like`` rotates per step
_ROTATE_ROWS = 65536


# ---------------------------------------------------------------------------
# LM tokens
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def lm_batch(key: jax.Array, batch: int, seq: int, vocab: int):
    """Zipf(1.1)-distributed token ids; labels = next-token shift.

    Inverse-CDF sampling: one uniform per token looked up in the ranks'
    cumulative mass, so the batch costs O(batch·seq) memory (a Gumbel
    sample would draw (batch, seq, vocab) noise: 0.8 GB at 2 × 8k tokens
    over 12,800 ids, beside a running train step)."""
    mass = jnp.cumsum(jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -1.1)
    u = jax.random.uniform(key, (batch, seq + 1)) * mass[-1]
    tokens = jnp.minimum(jnp.searchsorted(mass, u, side="right"), vocab - 1)
    return tokens[:, :-1].astype(jnp.int32), tokens[:, 1:].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Retrieval click-log (two-tower / MIND) with known ground truth
# ---------------------------------------------------------------------------

class ClickLog:
    """Latent-factor click generator.

    Items/users live in a latent space with anisotropic structure; a user's
    history is sampled from items near their latent vector, the next click
    (the label) likewise. Item popularity is zipf — matching the paper's
    industrial setting where a learned index must handle skewed exposure.
    """

    def __init__(self, seed: int, num_items: int, dim: int = 32,
                 num_clusters: int = 64):
        key = jax.random.PRNGKey(seed)
        ki, kp = jax.random.split(key)
        self.num_items = num_items
        self.dim = dim
        self.item_vecs = np.array(  # np.array: writable copy (asarray of a
            # jax array is read-only)
            sift_like(ki, num_items, dim, num_clusters=num_clusters, anisotropy=4.0)
        )
        self.item_vecs /= np.linalg.norm(self.item_vecs, axis=1, keepdims=True) + 1e-9
        pop = 1.0 / np.arange(1, num_items + 1) ** 1.05
        self._pop = pop / pop.sum()

    def batch(self, seed: int, batch: int, hist_len: int, cand: int = 64):
        """Returns (hist_ids (B, L) int32 with −1 pad, pos_ids (B,))."""
        rng = np.random.RandomState(seed)
        # sample a "session anchor" item by popularity, history = its knn-ish
        anchors = rng.choice(self.num_items, size=batch, p=self._pop)
        av = self.item_vecs[anchors]  # (B, d)
        # propose candidates and keep the most similar as history + label
        props = rng.randint(0, self.num_items, size=(batch, cand))
        sims = np.einsum("bd,bcd->bc", av, self.item_vecs[props])
        order = np.argsort(-sims, axis=1)
        top = np.take_along_axis(props, order, axis=1)
        hist = top[:, 1 : hist_len + 1].astype(np.int32)
        if hist.shape[1] < hist_len:
            pad = -np.ones((batch, hist_len - hist.shape[1]), np.int32)
            hist = np.concatenate([hist, pad], axis=1)
        # random-length histories (pad tail with −1)
        lens = rng.randint(max(1, hist_len // 4), hist_len + 1, size=batch)
        mask = np.arange(hist_len)[None, :] < lens[:, None]
        hist = np.where(mask, hist, -1).astype(np.int32)
        pos = top[:, 0].astype(np.int32)
        return jnp.asarray(hist), jnp.asarray(pos)

    def eval_queries(self, seed: int, num: int, hist_len: int, k_truth: int = 100):
        """Queries + ground-truth top-k item sets (by latent similarity) for
        p@k / r@k evaluation (paper Table 1 protocol)."""
        rng = np.random.RandomState(seed)
        hist, _ = [np.asarray(a) for a in self.batch(seed, num, hist_len)]
        hv = np.zeros((num, self.dim))
        for b in range(num):
            ids = hist[b][hist[b] >= 0]
            hv[b] = self.item_vecs[ids].mean(0) if len(ids) else 0.0
        sims = hv @ self.item_vecs.T  # (num, N)
        truth = np.argsort(-sims, axis=1)[:, :k_truth]
        return jnp.asarray(hist), truth


# ---------------------------------------------------------------------------
# CTR (wide&deep / DIN)
# ---------------------------------------------------------------------------

def ctr_batch(key: jax.Array, batch: int, n_fields: int, vocab: int):
    """Sparse ids + labels from a hidden logistic model over field crosses."""
    kf, kl, kw = jax.random.split(key, 3)
    ids = jax.random.randint(kf, (batch, n_fields), 0, vocab)
    # hidden weights: hash each (field, id) to a score
    w = jax.random.normal(kw, (n_fields, 64))
    feat = jax.vmap(lambda row: jnp.take(w, jnp.arange(n_fields), axis=0)
                    * jnp.cos(row[:, None] * 0.37))(ids)
    logit = jnp.sum(feat, axis=(1, 2)) * 0.05
    labels = jax.random.bernoulli(kl, jax.nn.sigmoid(logit)).astype(jnp.float32)
    return ids.astype(jnp.int32), labels


def din_batch(key: jax.Array, batch: int, hist_len: int, vocab: int):
    kh, kt, kl = jax.random.split(key, 3)
    hist = jax.random.randint(kh, (batch, hist_len), 0, vocab).astype(jnp.int32)
    target = jax.random.randint(kt, (batch,), 0, vocab).astype(jnp.int32)
    # label: does the target "match" the history's dominant bucket
    match = (jnp.median(hist % 97, axis=1) - (target % 97)).astype(jnp.float32)
    p = jax.nn.sigmoid(1.0 - 0.1 * jnp.abs(match))
    labels = jax.random.bernoulli(kl, p).astype(jnp.float32)
    return hist, target, labels
