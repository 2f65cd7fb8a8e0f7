"""The paper's trainable embedding-index layer:  T(X) = φ(X·R)·Rᵀ  (§2.1).

Sits at the top of the item tower of a two-tower retrieval model (Fig 1).
Forward rotates the batch into the PQ-friendly basis, product-quantizes with
a straight-through estimator, and rotates back, so downstream retrieval loss
sees (a differentiable surrogate of) exactly what the serving index returns.

φ is a ``repro.quant`` Quantizer (a ``quant.PQ`` view over the param
codebooks): the forward uses ``encode_st``, the loss term uses
``distortion``, and serving uses ``encode``/``adc_tables`` — the same
protocol every other quantizer consumer in the repo speaks.

Parameters:
  * ``R``: the rotation — updated by the configured ``repro.rotations``
    learner (``OptimizerConfig.rotation``), never by the inner optimizer.
  * ``codebooks``: (D, K, sub) — trained by the distortion loss (plain SGD
    path) or by streaming EMA. Kept as a raw array leaf so the optimizer's
    name-based manifold routing and launch/cells ParamSpecs see a flat tree;
    ``quantizer()`` wraps it in the protocol object on demand.

The total loss (Eq. 1) is  L_ret(T(X)) + (1/m)·‖XR − φ(XR)‖².
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import quant


class IndexLayerConfig(NamedTuple):
    dim: int
    num_subspaces: int = 8
    num_codewords: int = 256
    distortion_weight: float = 1.0

    @property
    def pq_cfg(self) -> quant.PQConfig:
        return quant.PQConfig(self.num_subspaces, self.num_codewords)


class IndexLayerParams(NamedTuple):
    """R is a plain array so the whole tree is jax.grad-able; the rotation
    learner's state (step counter, preconditioners) lives in the optimizer
    (training.optimizer treats any leaf named 'R'/'rot_*' as a manifold
    parameter and routes it through ``OptimizerConfig.rotation``'s learner
    instead of Adam)."""

    R: jax.Array
    codebooks: jax.Array


def quantizer(params: IndexLayerParams) -> quant.PQ:
    """The layer's φ as a protocol object (view over the codebook leaf)."""
    return quant.PQ(params.codebooks)


def init(key: jax.Array, cfg: IndexLayerConfig, dtype=jnp.float32) -> IndexLayerParams:
    n, sub = cfg.dim, cfg.dim // cfg.num_subspaces
    cb = 0.01 * jax.random.normal(
        key, (cfg.num_subspaces, cfg.num_codewords, sub), dtype=dtype
    )
    return IndexLayerParams(R=jnp.eye(n, dtype=dtype), codebooks=cb)


def warm_start(
    key: jax.Array,
    X: jax.Array,
    cfg: IndexLayerConfig,
    opq_iters: int = 200,
    kmeans_iters: int = 1,
) -> IndexLayerParams:
    """Paper §3.2 setup: run OPQ on a warm-up sample to initialize R and the
    codebooks before joint training starts."""
    R, pq_obj, _ = quant.opq.fit(key, X, cfg.pq_cfg, iters=opq_iters,
                                 kmeans_iters=kmeans_iters)
    return IndexLayerParams(R=R, codebooks=pq_obj.codebooks)


def apply(params: IndexLayerParams, X: jax.Array) -> tuple[jax.Array, jax.Array]:
    """T(X) = φ(XR)Rᵀ with STE; returns (T(X), distortion scalar).

    Gradients: ∂/∂X flows straight-through φ and through both rotations;
    ∂/∂codebooks comes from the distortion term; ∂/∂R is consumed by the GCD
    update outside (the caller differentiates wrt ``params.R``).
    """
    phi = quantizer(params)
    XR = X @ params.R
    out = phi.encode_st(XR) @ params.R.T
    dist = phi.distortion(XR)
    return out, dist


def apply_no_ste(params: IndexLayerParams, X: jax.Array) -> jax.Array:
    """Serving-path forward: hard quantization, no gradient bridging."""
    phi = quantizer(params)
    return phi.decode(phi.encode(X @ params.R)) @ params.R.T


def encode(params: IndexLayerParams, X: jax.Array) -> jax.Array:
    """Index-build path: item codes (m, D) for the serving index."""
    return quantizer(params).encode(X @ params.R)


def adc_scores(params: IndexLayerParams, queries: jax.Array,
               codes: jax.Array) -> jax.Array:
    """Serving-path ADC scoring: (b, n) queries × (N, D) codes -> (b, N).

    Inner-product scores in the rotated space equal scores in the original
    space because R is orthogonal: ⟨q, φ(xR)Rᵀ⟩ = ⟨qR, φ(xR)⟩. Scores go
    through the shared ADC kernel family (jnp oracle path off-TPU).
    """
    tables = quantizer(params).adc_tables(queries @ params.R)
    return quant.adc_score_tables(tables, codes)
