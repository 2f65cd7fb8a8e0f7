"""Optimizer with rotation-learner manifold routing.

Ordinary parameters get AdamW (configurable moment dtype — bf16 moments for
the ≥100B archs, see DESIGN.md §6). Any leaf whose name is in
``MANIFOLD_LEAVES`` ({'R', 'rot_k', 'rot_v', 'rot_c'}) is an SO(n) rotation and is
routed through the ``repro.rotations`` learner configured by
``OptimizerConfig.rotation`` instead — GCD (the paper's Algorithm 2,
projection-free and exactly orthogonal at every step), Cayley-SGD,
SVD/Procrustes, or the frozen-R control, all swappable by registry spec.
Stacked rotations (leading layer axis, e.g. per-layer KV rotations
(L, hd, hd) or latent rotations (L, r, r)) are vmapped over the learner's
update.

This is the paper's headline integration claim: GCD "can be easily
integrated with standard neural network training algorithms" — and with the
learner protocol, so can every baseline it is compared against.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import rotations as rot_lib

MANIFOLD_LEAVES = ("R", "rot_k", "rot_v", "rot_c")


class OptimizerConfig(NamedTuple):
    name: str = "adamw"              # adamw | adafactor
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    moment_dtype: Any = jnp.float32  # bf16 halves optimizer memory at ≥100B
    compute_dtype: Any = jnp.float32  # bf16 halves the per-leaf update temps
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"         # cosine | constant
    # --- microbatch gradient accumulation (big-arch memory fit) ---
    accum_steps: int = 1
    accum_dtype: Any = jnp.float32
    # --- manifold (SO(n)) leaf settings: which rotation learner + its lr ---
    rotation: rot_lib.RotationConfig = rot_lib.RotationConfig()


class OptState(NamedTuple):
    mu: Any        # first moments (zeros for manifold leaves)
    nu: Any        # second moments
    rot: Any       # dict[path-key, learner state] for the manifold leaves
    step: jax.Array


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", getattr(last, "name", str(last)))


def is_manifold_path(path) -> bool:
    return _leaf_name(path) in MANIFOLD_LEAVES


def path_key(path) -> str:
    """Stable string key for a param-tree path (OptState.rot dict key)."""
    return "/".join(_leaf_name((p,)) for p in path)


def _init_rot_leaf(learner, p):
    """Learner state for one manifold leaf (vmapped for stacked (L, n, n))."""
    if p.ndim == 3:
        return jax.vmap(learner.init_from)(p)
    return learner.init_from(p)


def init_rot_states(params, cfg: OptimizerConfig):
    """The ``OptState.rot`` dict: one learner state per manifold leaf."""
    learner = rot_lib.from_config(cfg.rotation)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {
        path_key(path): _init_rot_leaf(learner, p)
        for path, p in flat if is_manifold_path(path)
    }


def factored_shapes(shape: tuple[int, ...]):
    """Adafactor factored second-moment shapes: (row-stat, col-stat).

    ≥2D: vr drops the last dim, vc drops the second-to-last.
    1D/0D: full-size v in the row slot, scalar placeholder in the col slot.
    """
    if len(shape) >= 2:
        return shape[:-1], shape[:-2] + shape[-1:]
    return shape, ()


def init(params, cfg: OptimizerConfig) -> OptState:
    adafactor = cfg.name == "adafactor"

    def mu_like(path, p):
        if adafactor:  # mu slot holds the factored ROW stat (f32, tiny)
            return jnp.zeros(factored_shapes(p.shape)[0], jnp.float32)
        return jnp.zeros(p.shape, cfg.moment_dtype)

    def nu_like(path, p):
        if adafactor:  # nu slot holds the factored COL stat
            return jnp.zeros(factored_shapes(p.shape)[1], jnp.float32)
        return jnp.zeros(p.shape, cfg.moment_dtype)

    mu = jax.tree_util.tree_map_with_path(mu_like, params)
    nu = jax.tree_util.tree_map_with_path(nu_like, params)
    return OptState(mu=mu, nu=nu, rot=init_rot_states(params, cfg),
                    step=jnp.int32(0))


def schedule_lr(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (s + 1.0) / max(cfg.warmup_steps, 1))
    if cfg.schedule == "cosine":
        frac = jnp.clip(s / max(cfg.total_steps, 1), 0.0, 1.0)
        decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def global_norm(tree) -> jax.Array:
    """Global L2 norm via per-leaf self-dot with f32 ACCUMULATION.

    ``jnp.sum(jnp.square(x))`` on a bf16 leaf upcasts the whole array to f32
    before reducing (jnp's half-precision sum semantics); under SPMD that
    materializes an f32 copy of every gradient leaf — measured ~8 GiB/device
    on the 340B arch. ``dot_general(x, x, preferred_element_type=f32)``
    accumulates in f32 without ever materializing an f32 operand."""

    def sq(x):
        # contract over ALL axes in place — a reshape(-1) first would break
        # the sharding and all-gather every leaf (measured: 2.9 TiB/device).
        dims = tuple(range(x.ndim))
        return jax.lax.dot_general(
            x, x, ((dims, dims), ((), ())),
            preferred_element_type=jnp.float32)

    return jnp.sqrt(sum(sq(x) for x in jax.tree.leaves(tree)))


def _f32_mean_sq_over(g: jax.Array, axis: int) -> jax.Array:
    """mean(g², axis) with f32 accumulation and NO f32 copy of g (bf16
    operands contracted against a ones vector — see layers._f32_sumsq)."""
    g2 = g * g  # bf16 elementwise
    ones = jnp.ones((g.shape[axis],), g.dtype)
    nd = g.ndim
    ax = axis % nd
    dims = (((ax,), (0,)), ((), ()))
    s = jax.lax.dot_general(g2, ones, dims, preferred_element_type=jnp.float32)
    return s / g.shape[ax]


def _adafactor_leaf(cfg: OptimizerConfig, lr, t, g, p, vr, vc):
    """Adafactor (Shazeer & Stern 2018), β1=0, factored second moment.

    The memory story at ≥300B: Adam keeps 2 params-sized moments plus their
    update-pipeline copies; Adafactor keeps O(rows+cols) f32 stats, so the
    optimizer adds ~nothing to the params+grads footprint. Update-RMS
    clipping (d=1) replaces global grad-norm clipping.
    """
    b2 = 1.0 - t ** -0.8  # Adafactor's schedule for the decay
    eps = 1e-30
    if p.ndim >= 2:
        r = _f32_mean_sq_over(g, -1)          # (rows...)
        c = _f32_mean_sq_over(g, -2)          # (..., cols)
        vr_n = b2 * vr + (1.0 - b2) * r
        vc_n = b2 * vc + (1.0 - b2) * c
        # v̂ = vr ⊗ vc / mean(vr): factored reconstruction
        denom = jnp.mean(vr_n, axis=-1, keepdims=True)
        rfac = jax.lax.rsqrt(vr_n / jnp.maximum(denom, eps) + eps)
        cfac = jax.lax.rsqrt(vc_n + eps)
        u = g.astype(cfg.compute_dtype) * (
            rfac[..., None] * cfac[..., None, :]).astype(cfg.compute_dtype)
    else:
        vr_n = b2 * vr + (1.0 - b2) * jnp.square(g.astype(jnp.float32))
        vc_n = vc
        u = g.astype(cfg.compute_dtype) * jax.lax.rsqrt(
            vr_n + eps).astype(cfg.compute_dtype)
    # update-RMS clip at d=1.0
    rms = jnp.sqrt(jnp.maximum(
        jax.lax.dot_general(u, u, ((tuple(range(u.ndim)),) * 2, ((), ())),
                            preferred_element_type=jnp.float32)
        * (1.0 / float(u.size)),  # float: u.size overflows int32 at ≥300B
        1e-30))
    scale = (lr / jnp.maximum(1.0, rms)).astype(cfg.compute_dtype)
    if cfg.weight_decay > 0:
        u = u + jnp.asarray(cfg.weight_decay, cfg.compute_dtype) * p.astype(cfg.compute_dtype)
    p_n = (p.astype(cfg.compute_dtype) - scale * u).astype(p.dtype)
    return p_n, vr_n, vc_n


def _update_impl(grads, state: OptState, params, cfg: OptimizerConfig,
                 key: jax.Array):
    """Shared update body → (new_params, new_state, rotation_deltas).

    ``rotation_deltas`` maps each manifold leaf's ``path_key`` to the
    ``RotationDelta`` the learner applied this step — the exact pytree a
    live index consumes through ``Engine.refresh``. ``update`` discards it
    (XLA dead-code-eliminates the unused outputs); ``update_with_deltas``
    returns it for the overlapped train-and-refresh loop."""
    step = state.step
    lr = schedule_lr(cfg, step)
    t = (step + 1).astype(jnp.float32)

    gn = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gn, 1e-9)) if cfg.grad_clip > 0 else 1.0
    b1c = 1.0 - cfg.beta1**t
    b2c = 1.0 - cfg.beta2**t

    flat_g = jax.tree_util.tree_flatten_with_path(grads)[0]
    keys = jax.random.split(key, max(len(flat_g), 1))
    key_for = {path: k for (path, _), k in zip(flat_g, keys)}

    learner = rot_lib.from_config(cfg.rotation)
    rot_n: dict[str, Any] = {}
    deltas: dict[str, Any] = {}
    cdt = cfg.compute_dtype

    def upd(path, g, p, mu, nu):
        g = g.astype(cdt) * clip.astype(cdt) if cfg.grad_clip > 0 else g.astype(cdt)
        if is_manifold_path(path):
            kk = key_for[path]
            # re-sync the learner state's R from the param leaf (source of
            # truth, e.g. after a partial checkpoint restore)
            st = state.rot[path_key(path)]

            def one_rot(s, G, k):
                return learner.update(s, G, cfg.rotation.lr, k)

            if p.ndim == 3:  # stacked per-layer rotations
                st = jax.vmap(learner.with_rotation)(st, p)
                ks = jax.random.split(kk, p.shape[0])
                st2, delta = jax.vmap(one_rot)(st, g, ks)
                p_n = jax.vmap(learner.materialize)(st2)
            else:
                st = learner.with_rotation(st, p)
                st2, delta = one_rot(st, g, kk)
                p_n = learner.materialize(st2)
            rot_n[path_key(path)] = st2
            deltas[path_key(path)] = delta
            return p_n.astype(p.dtype), mu, nu
        if cfg.name == "adafactor":
            return _adafactor_leaf(cfg, lr, t, g, p, mu, nu)
        one = jnp.asarray(1.0, cdt)
        mu_n = jnp.asarray(cfg.beta1, cdt) * mu.astype(cdt) + (one - cfg.beta1) * g
        nu_n = jnp.asarray(cfg.beta2, cdt) * nu.astype(cdt) + (one - cfg.beta2) * jnp.square(g)
        upd_v = (mu_n / b1c.astype(cdt)) / (jnp.sqrt(nu_n / b2c.astype(cdt))
                                            + jnp.asarray(cfg.eps, cdt))
        if cfg.weight_decay > 0:
            upd_v = upd_v + jnp.asarray(cfg.weight_decay, cdt) * p.astype(cdt)
        p_n = p.astype(cdt) - lr.astype(cdt) * upd_v
        return (p_n.astype(p.dtype), mu_n.astype(cfg.moment_dtype),
                nu_n.astype(cfg.moment_dtype))

    results = jax.tree_util.tree_map_with_path(
        upd, grads, params, state.mu, state.nu
    )
    # unzip the 3-tuples back into trees
    treedef = jax.tree.structure(params)
    flat = treedef.flatten_up_to(results)
    p_n = treedef.unflatten([r[0] for r in flat])
    mu_n = treedef.unflatten([r[1] for r in flat])
    nu_n = treedef.unflatten([r[2] for r in flat])
    return p_n, OptState(mu=mu_n, nu=nu_n, rot=rot_n, step=step + 1), deltas


@functools.partial(jax.jit, static_argnames=("cfg",))
def update(grads, state: OptState, params, cfg: OptimizerConfig, key: jax.Array):
    """Returns (new_params, new_state). Clips the global grad norm, then
    AdamW everywhere except the SO(n) leaves, which go through the
    configured ``repro.rotations`` learner (``cfg.rotation``)."""
    p_n, state_n, _deltas = _update_impl(grads, state, params, cfg, key)
    return p_n, state_n


@functools.partial(jax.jit, static_argnames=("cfg",))
def update_with_deltas(grads, state: OptState, params, cfg: OptimizerConfig,
                       key: jax.Array):
    """``update`` that also returns ``{path_key: RotationDelta}`` for the
    manifold leaves — feed these to a live index (``Engine.refresh``) to
    keep it aligned with the trainer's rotations at zero rebuild cost."""
    return _update_impl(grads, state, params, cfg, key)
