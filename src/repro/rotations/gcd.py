"""Givens-coordinate-descent learners (paper Algorithm 2) as RotationLearners.

``GCD`` owns the projection-free manifold update:

    G  = ∇_R L                      (ordinary backprop gradient)
    A  = GᵀR − RᵀG                  (directional derivatives, Prop. 1)
    (pi, pj) ← select n/2 disjoint pairs   (GCD-R / GCD-G / GCD-S)
    θℓ = −λ · Â[iℓ, jℓ] / √2
    R  ← R · ∏ℓ R_{iℓ jℓ}(θℓ)       (commuting block update, O(n²))

R stays exactly orthogonal at every step (up to fp rounding) — no SVD, no
matrix exponential, no Cayley solve. The optional diagonal preconditioners
(adagrad / adam over the (n, n) directional-derivative field) implement the
paper's remark that GCD "can be easily integrated with standard neural
network training algorithms, such as Adagrad and Adam".

``SubspaceGCD`` restricts the matching to pairs inside one PQ subspace
(serving-aware GCD, extracted from the former
``index.maintain.subspace_gcd_step``): masked entries carry zero weight, so
greedy completes the matching with them only after all useful
within-subspace pairs — and their step angle θ = −λ·0/√2 is exactly 0, an
identity rotation. The resulting Δ is block-diagonal over the PQ subspaces,
so ``maintain.refresh_delta`` absorbs it EXACTLY (codes provably unchanged).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import givens, matching
from repro.rotations import base

METHODS = ("random", "greedy", "steepest", "overlap_greedy", "overlap_random")


class GCDState(NamedTuple):
    """State of a GCD-trained rotation (formerly core.rotation.RotationState)."""

    R: jax.Array              # (n, n) current rotation, in SO(n)
    step: jax.Array           # int32 step counter
    accum: jax.Array          # (n, n) preconditioner 1st accumulator (adagrad/adam-m)
    accum2: jax.Array         # (n, n) adam-v accumulator (unused for adagrad)


def _precondition(state: GCDState, A: jax.Array, preconditioner: str,
                  beta1: float = 0.9, beta2: float = 0.999, eps: float = 1e-8):
    """Optionally rescale the directional-derivative field elementwise."""
    if preconditioner == "none":
        return A, state.accum, state.accum2
    t = state.step.astype(jnp.float32) + 1.0
    if preconditioner == "adagrad":
        acc = state.accum + jnp.square(A)
        Ahat = A / (jnp.sqrt(acc) + eps)
        return Ahat, acc, state.accum2
    if preconditioner == "adam":
        m = beta1 * state.accum + (1.0 - beta1) * A
        v = beta2 * state.accum2 + (1.0 - beta2) * jnp.square(A)
        mhat = m / (1.0 - beta1**t)
        vhat = v / (1.0 - beta2**t)
        Ahat = mhat / (jnp.sqrt(vhat) + eps)
        return Ahat, m, v
    raise ValueError(f"unknown preconditioner {preconditioner!r}")


@dataclasses.dataclass(frozen=True)
class GCD:
    """The paper's GCD family; ``method`` picks the pair-selection strategy."""

    method: str = "greedy"           # one of METHODS
    preconditioner: str = "none"     # none | adagrad | adam
    sweeps: int = 16                 # 2-opt sweeps for method="steepest"
    reorthonormalize_every: int = 0  # 0 = never (exact in f32)
    score_kernel_min_n: int = 256    # fused Pallas A=GᵀR−RᵀG at n ≥ this; 0 off

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f"unknown GCD method {self.method!r}")

    def init(self, n: int, dtype=jnp.float32) -> GCDState:
        return self.init_from(jnp.eye(n, dtype=dtype))

    def init_from(self, R: jax.Array) -> GCDState:
        n = R.shape[-1]
        return GCDState(
            R=R,
            step=jnp.int32(0),
            accum=jnp.zeros((n, n), jnp.float32),
            accum2=jnp.zeros((n, n), jnp.float32),
        )

    def with_rotation(self, state: GCDState, R: jax.Array) -> GCDState:
        return state._replace(R=R)

    def materialize(self, state: GCDState) -> jax.Array:
        return state.R

    def select_pairs(self, Ahat: jax.Array, key: jax.Array):
        """The matching step — (pi, pj) from the preconditioned score field."""
        n = Ahat.shape[-1]
        if self.method == "random":
            return matching.random_matching(key, n)
        if self.method == "greedy":
            # exact-equivalent vectorized-rounds variant: ~12× faster at
            # n=512 than the one-edge-at-a-time scan
            return matching.greedy_matching_fast(Ahat)
        if self.method == "steepest":
            return matching.steepest_matching(Ahat, sweeps=self.sweeps)
        if self.method == "overlap_greedy":
            return matching.overlapping_topk(Ahat)
        return matching.overlapping_random(key, Ahat.shape[-1])

    def update(self, state: GCDState, grad: jax.Array, lr: float | jax.Array,
               key: jax.Array) -> tuple[GCDState, base.GivensDelta]:
        # the named scope marks every op of the update (scoring,
        # preconditioning, pair selection, the apply) in the compiled
        # program's metadata, so a profile can be read by stage
        with jax.named_scope("gcd"):
            A = self._score(grad.astype(jnp.float32),
                            state.R.astype(jnp.float32))
            Ahat, acc, acc2 = _precondition(state, self._mask(A),
                                            self.preconditioner)
            pi, pj = self.select_pairs(Ahat, key)
            theta = (-jnp.asarray(lr, jnp.float32) * Ahat[pi, pj]
                     / givens.SQRT2)
            delta = base.GivensDelta(
                pi=pi, pj=pj, theta=theta,
                overlapping=self.method.startswith("overlap"))
            step = state.step + 1
            R_new = base.maybe_reorthonormalize(
                delta.apply(state.R), step, self.reorthonormalize_every)
        return GCDState(R=R_new, step=step, accum=acc, accum2=acc2), delta

    def _score(self, G: jax.Array, R: jax.Array) -> jax.Array:
        """A = GᵀR − RᵀG. Large rotations route through the fused Pallas
        kernel (one pass over G/R instead of matmul + transpose + subtract);
        bit-identical to the reference — pinned in tests/test_rotations.py.
        Below ``score_kernel_min_n`` the kernel's block padding costs more
        than it saves, so small/odd sizes keep the jnp reference."""
        n = G.shape[-1]
        if self.score_kernel_min_n and n >= self.score_kernel_min_n:
            from repro.kernels import ops as kernel_ops
            return kernel_ops.gcd_score(G, R)
        return givens.directional_derivs(G, R)

    def _mask(self, A: jax.Array) -> jax.Array:
        """Hook for SubspaceGCD; the full-matching family is unmasked."""
        return A


@dataclasses.dataclass(frozen=True)
class SubspaceGCD(GCD):
    """GCD with the matching restricted to within-subspace planes.

    ``sub`` is the PQ subspace width (n // num_subspaces). Cross-subspace
    entries of A are zeroed before the greedy matching, so every pair with
    nonzero angle stays inside one subspace slice and the delta can be
    absorbed exactly into product codebooks (``maintain.refresh_delta``).
    This restricts coordinate descent to the subgroup SO(sub)^D — strictly
    less expressive per step than a full matching, so trainers typically
    interleave: cheap exact-refresh subspace steps between queries, an
    occasional full step + ~1% approximate refresh when the descent stalls.
    """

    sub: int = 0
    method: str = "greedy"

    def __post_init__(self):
        super().__post_init__()
        if self.sub <= 0:
            raise ValueError("SubspaceGCD needs sub > 0 (the subspace width)")
        if self.method.startswith("overlap"):
            raise ValueError("SubspaceGCD requires a disjoint matching")

    def _mask(self, A: jax.Array) -> jax.Array:
        d_idx = jnp.arange(A.shape[-1]) // self.sub
        return jnp.where(d_idx[:, None] == d_idx[None, :], A, 0.0)


class FrozenState(NamedTuple):
    R: jax.Array
    step: jax.Array


@dataclasses.dataclass(frozen=True)
class Frozen:
    """The frozen-R control: update is a no-op with an O(1) identity delta."""

    reorthonormalize_every: int = 0  # accepted for config uniformity; unused

    def init(self, n: int, dtype=jnp.float32) -> FrozenState:
        return self.init_from(jnp.eye(n, dtype=dtype))

    def init_from(self, R: jax.Array) -> FrozenState:
        return FrozenState(R=R, step=jnp.int32(0))

    def with_rotation(self, state: FrozenState, R: jax.Array) -> FrozenState:
        return state._replace(R=R)

    def materialize(self, state: FrozenState) -> jax.Array:
        return state.R

    def update(self, state: FrozenState, grad: jax.Array,
               lr: float | jax.Array, key: jax.Array):
        del grad, lr, key
        return (state._replace(step=state.step + 1),
                base.identity_delta(state.R.dtype))
