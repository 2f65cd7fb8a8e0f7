"""Error-feedback int8 gradient compression for the cross-pod axis.

The inter-pod (DCN) links are the slowest hop in a multi-pod mesh; the
standard trick (1-bit Adam / EF-SGD lineage) is to quantize the cross-pod
all-reduce payload and carry the quantization error into the next step.

``ef_psum_int8`` quantizes to int8 with a *shared* scale (one scalar psum)
and pre-divides by the axis size so the integer sum cannot overflow int8 —
the payload of the big all-reduce is 1 byte/element instead of 4 (f32) or
2 (bf16). The local quantization residual is returned for error feedback:

    x      = g + err                      (apply feedback)
    q      = round(x / (s·n)) ∈ [−127,127/n]
    g_out  = psum(q) · s · n / n = psum(q)·s
    err'   = x − q·s·n                    (carry what was lost)

With error feedback the scheme is unbiased over time and converges at the
full-precision rate on smooth objectives (Karimireddy et al. 2019).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ef_quantize(x: jax.Array, err: jax.Array, axis_size: int):
    """Returns (q int8, scale f32 scalar, new_err). Shared-scale int8 with
    1/axis_size headroom so the integer psum stays in int8 range."""
    xf = x.astype(jnp.float32) + err
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / (scale * axis_size)), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale * axis_size
    return q, scale, xf - deq


def ef_psum_int8(g: jax.Array, err: jax.Array, axis: str, axis_size: int):
    """Inside shard_map: all-reduce ``g`` over ``axis`` with an int8 payload.

    The scale must be identical on every participant, so it is psum-maxed
    first (a scalar — negligible traffic). Returns (g_summed, new_err).
    """
    xf = g.astype(jnp.float32) + err
    amax = jax.lax.pmax(jnp.max(jnp.abs(xf)), axis)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / (scale * axis_size)), -127, 127).astype(jnp.int8)
    summed_q = jax.lax.psum(q, axis)           # 1-byte payload on the wire
    g_out = summed_q.astype(jnp.float32) * scale * axis_size
    new_err = xf - q.astype(jnp.float32) * scale * axis_size
    return g_out, new_err


def make_compressed_crosspod_psum(mesh, axis: str = "pod"):
    """Build a shard_map'd reducer f(g_stacked, err_stacked) -> (g_sum, err').

    ``g_stacked`` carries a leading pod axis of size n (one differing gradient
    per pod, sharded over ``axis``); the error-feedback buffer has the same
    layout and stays pod-local. The summed gradient comes back replicated.

    Used by the launcher when ``--grad-compress`` is on: the data/model-axis
    reductions stay full precision (fast ICI), only the pod-axis hop is
    compressed.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]

    def f(g, err):
        g_sum, err_new = ef_psum_int8(g[0], err[0], axis, n)
        return g_sum, err_new[None]

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(axis), P(axis)), out_specs=(P(), P(axis)),
        check_vma=False,
    )
