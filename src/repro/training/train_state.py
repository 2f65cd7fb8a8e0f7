"""Train state + generic train-step builder.

``make_train_step(loss_fn, opt_cfg)`` turns any ``loss_fn(params, batch)``
into a jit-able ``(state, batch) → (state, metrics)`` step that:
  * differentiates the loss (rotations included — their grads feed the
    configured ``repro.rotations`` learner),
  * routes updates through training.optimizer (AdamW + manifold learner),
  * advances the RNG deterministically from the step counter.

End-to-end losses that train *through* a quantized index compose with
``eq1_loss`` below: the paper's Eq.(1) built from any ``repro.quant``
Quantizer via its straight-through ``encode_st`` (this is the route
core.index_layer.apply takes inside recsys.twotower_loss too).

The same step function is what launch/dryrun.py lowers for the training
cells, so the compiled artifact includes the full optimizer and the GCD
update — the roofline sees the real system, not just the forward pass.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.training import optimizer as opt_lib


def eq1_loss(quantizer, R: jax.Array, X: jax.Array,
             task_loss: Callable[[jax.Array], jax.Array],
             distortion_weight: float = 1.0) -> jax.Array:
    """Paper Eq.(1):  L_task(T(X)) + w·(1/m)‖XR − φ(XR)‖²  with
    T(X) = φ(XR)Rᵀ and φ any ``repro.quant`` Quantizer.

    The non-differentiable φ is bridged by ``Quantizer.encode_st`` (forward
    = quantized value, backward = identity wrt X), so ∂/∂X reaches the
    towers, ∂/∂codebooks comes from the distortion term, and ∂/∂R feeds the
    rotation learner's manifold update in training.optimizer.
    """
    XR = X @ R
    tx = quantizer.encode_st(XR) @ R.T
    return task_loss(tx) + distortion_weight * quantizer.distortion(XR)


class TrainState(NamedTuple):
    params: Any
    opt_state: opt_lib.OptState
    step: jax.Array
    rng: jax.Array


def init_state(key: jax.Array, params, opt_cfg: opt_lib.OptimizerConfig) -> TrainState:
    return TrainState(
        params=params,
        opt_state=opt_lib.init(params, opt_cfg),
        step=jnp.int32(0),
        rng=key,
    )


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    opt_cfg: opt_lib.OptimizerConfig,
    grad_shardings=None,
    emit_deltas: bool = False,
    has_aux: bool = False,
) -> Callable:
    """loss_fn(params, *batch_arrays) -> scalar. Returns a pure step fn.

    ``has_aux=True``: loss_fn returns (scalar, {name: scalar}) and the named
    scalars join the step's metrics (e.g. an expert layer's counters);
    microbatch accumulation then takes no aux.

    ``emit_deltas=True`` adds ``metrics["rotation_deltas"]`` — the
    ``{path_key: RotationDelta}`` dict each manifold update applied, ready
    to replay onto a live index via ``Engine.refresh`` (the overlapped
    train-and-refresh loop in ``repro.pipeline``).

    ``opt_cfg.accum_steps > 1`` splits the global batch into microbatches
    scanned sequentially with f32 gradient accumulation — activation memory
    shrinks by the accumulation factor (the grads scan is NOT differentiated,
    so only one microbatch's activations are ever live).

    ``grad_shardings`` (a params-shaped tree of NamedShardings) pins each
    gradient leaf to its parameter's sharding — without it the SPMD
    partitioner is free to stage cotangent stacks through exotic tilings."""

    A = opt_cfg.accum_steps

    def _pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings)

    if has_aux and A > 1:
        raise ValueError("has_aux needs accum_steps == 1")

    def _grads(params, *batch):
        if has_aux:
            (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, *batch)
            return loss, _pin(g), aux
        if A == 1:
            loss, g = jax.value_and_grad(loss_fn)(params, *batch)
            return loss, _pin(g), {}
        micro = jax.tree.map(
            lambda x: x.reshape(A, x.shape[0] // A, *x.shape[1:]), batch)
        gz = _pin(jax.tree.map(
            lambda p: jnp.zeros(p.shape, opt_cfg.accum_dtype), params))

        inv = 1.0 / A

        def scaled_loss(p, *mbatch):
            # fold the 1/A into the loss so no post-hoc params-sized
            # `g * inv` tree-map copy is needed
            return loss_fn(p, *mbatch) * inv

        def mb(carry, mbatch):
            lsum, gsum = carry
            l, g = jax.value_and_grad(scaled_loss)(params, *mbatch)
            g = _pin(g)
            gsum = _pin(jax.tree.map(
                lambda a, b: a + b.astype(opt_cfg.accum_dtype), gsum, g))
            return (lsum + l, gsum), None

        (loss, grads), _ = jax.lax.scan(mb, (jnp.float32(0.0), gz), micro)
        return loss, grads, {}

    def train_step(state: TrainState, *batch) -> tuple[TrainState, dict]:
        loss, grads, aux = _grads(state.params, *batch)
        key, sub = jax.random.split(state.rng)
        if emit_deltas:
            params, opt_state, deltas = opt_lib.update_with_deltas(
                grads, state.opt_state, state.params, opt_cfg, sub
            )
        else:
            params, opt_state = opt_lib.update(
                grads, state.opt_state, state.params, opt_cfg, sub
            )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": opt_lib.global_norm(grads),
            "lr": opt_lib.schedule_lr(opt_cfg, state.step),
            **aux,
        }
        if emit_deltas:
            metrics["rotation_deltas"] = deltas
        return (
            TrainState(params=params, opt_state=opt_state,
                       step=state.step + 1, rng=key),
            metrics,
        )

    return train_step
