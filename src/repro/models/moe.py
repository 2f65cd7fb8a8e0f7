"""Mixture-of-Experts: a dropless expert layer that holds a share of the
experts (TPU-native grouped matmuls).

The layer is told which experts it holds (``experts_held`` of
``num_experts``, starting at ``first_held``): the router keeps all its
outputs and its top-k, and the layer computes the part of the result that
its own experts give, for every token routed to them, with none dropped.
Under expert parallelism each chip holds one share; the parts of all the
shares, with the shared experts counted once, sum to the whole layer. On
one device with ``experts_held == num_experts`` it is the whole layer.

  1. softmax router over all E experts → top-k (gates, ids); gates
     renormalised over the k only where ``norm_topk_prob`` says so
  2. the (token, choice) assignments to held experts are sorted by expert,
     the rest after them: one stable argsort of T·k keys
  3. grouped matmuls over the held experts (``jax.lax.ragged_dot``; on a
     TPU the grouped instruction visits only the rows of each group and
     leaves the rows past them unwritten, so those rows are masked)
  4. gather back by the inverse permutation, weight by the gates (zero for
     assignments to experts held elsewhere), sum over the k choices.

Memory is O(T·k·d); no (T, E, C) tensor and no capacity exist. Shared
experts (one GLU of width ``shared_ff``) run on every token. Under a mesh
whose rules shard the experts (or their width), the same steps run under
``shard_map`` on each device's share and a ``psum`` adds the shares.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.sharding import rules as sh


class MoEConfig(NamedTuple):
    num_experts: int                 # router outputs (all experts)
    top_k: int
    experts_held: int | None = None  # experts this device holds (None: all)
    first_held: int = 0              # id of the first held expert
    d_ff: int | None = None          # routed expert width (None: cfg.d_ff)
    shared_ff: int = 0               # shared-expert GLU width (0: none)
    norm_topk_prob: bool = True      # renormalise the top-k gates
    seq_aux: bool = False            # balance loss per sequence, not batch
    aux_alpha: float = 0.0           # loss weight of Σ over layers of aux

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held


def route(x: jax.Array, router_w: jax.Array, cfg: MoEConfig, seq_len: int):
    """(gates (T, k), ids (T, k), aux) of the router on tokens x (T, d).

    aux is the expert-level balance loss (E/k)·Σ_i f_i·P_i, with f_i the
    share of tokens choosing expert i among their top k and P_i its mean
    router probability, taken per sequence of ``seq_len`` tokens and
    averaged when ``seq_aux`` (DeepSeek-V2 §2.2.3), else over the batch.
    """
    E, k = cfg.num_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    group = seq_len if cfg.seq_aux else x.shape[0]
    chosen = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1)
    f = jnp.mean(chosen.reshape(-1, group, E), axis=1)           # (G, E)
    P = jnp.mean(probs.reshape(-1, group, E), axis=1)
    aux = jnp.mean(jnp.sum(f * P, axis=-1)) * (E / k)
    return gates, ids, aux


def moe_block(
    x: jax.Array,          # (T, d) tokens
    router_w: jax.Array,   # (d, E)
    wi: jax.Array,         # (held, d, f)
    wg: jax.Array | None,  # (held, d, f) for GLU variants
    wo: jax.Array,         # (held, f, d)
    cfg: MoEConfig,
    *,
    activation: str,
    rule_table: dict[str, Any],
    seq_len: int,
    shared: tuple | None = None,   # (wi, wg, wo) of the shared experts
) -> tuple[jax.Array, jax.Array, dict]:
    """Returns (output (T, d), aux balance loss, stats).

    stats: ``local_tokens`` (assignments to held experts), ``max_expert_load``
    (the most any held expert received) and ``dropped`` (assignments to held
    experts whose row lies outside the rows the grouped matmuls cover, so
    was masked to zero: 0 unless the sort or the group sizes lose rows).
    Rows inside the groups that the grouped matmuls compute wrongly do not
    show here; only a comparison of the outputs can see them.

    Under a mesh whose rules shard the experts (or, when they do not divide
    the axis, the expert width) over a mesh axis, each device computes its
    own share of the held experts for its tokens and the shares are summed
    by a ``psum`` over that axis (``_expert_parallel``)."""
    from repro.models import layers

    mesh = compat.current_mesh()
    ep = _ep_axes(mesh, rule_table, x.shape, wi.shape)
    if ep is None:
        out, aux, counts = _held_part(
            x, router_w, wi, wg, wo, cfg, activation, seq_len,
            lambda a: sh.constrain(a, ("act_tokens", None), rule_table))
        stats = _stats(*counts)
    else:
        out, aux, stats = _expert_parallel(
            x, router_w, wi, wg, wo, cfg, activation, seq_len, mesh, *ep)
    out = sh.constrain(out, ("act_tokens", None), rule_table)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            swi, swg, swo = shared
            hs = layers.activate(x @ swi.astype(x.dtype), activation)
            if swg is not None:
                hs = hs * (x @ swg.astype(x.dtype))
            out = out + hs @ swo.astype(x.dtype)
    return out.astype(x.dtype), aux, stats


def _held_part(x, router_w, wi, wg, wo, cfg: MoEConfig, activation: str,
               seq_len: int, pin=lambda a: a):
    """The routed experts' part of the layer on one device: (out, aux,
    (assignments to held experts, rows per held expert, held assignments
    left outside the covered rows)); ``pin`` constrains the (T·k, d) row
    buffers' sharding."""
    from repro.models import layers

    T, d = x.shape
    k, H = cfg.top_k, cfg.held
    with jax.named_scope("moe.route"):
        gates, ids, aux = route(x, router_w, cfg, seq_len)
        local = ids.reshape(-1) - cfg.first_held            # (T·k,)
        held = (local >= 0) & (local < H)
        key = jnp.where(held, local, H)                     # others last
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)                            # unsort
        s_tok = order // k                                  # token of row
        group_sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
        # rows past the held groups: the TPU's grouped matmul leaves its
        # output there unwritten, so those rows are zeroed on the way in
        # (which also zeroes their cotangent on the way back to x) and out
        covered = jnp.sum(group_sizes)
        in_groups = (jnp.arange(T * k) < covered)[:, None]
        xs = pin(jnp.where(in_groups, jnp.take(x, s_tok, axis=0), 0))
        dropped = jnp.sum((held & (inv >= covered)).astype(jnp.int32))
    with jax.named_scope("moe.experts"):
        h = jax.lax.ragged_dot(xs, wi.astype(xs.dtype), group_sizes)
        if wg is not None:
            g = jax.lax.ragged_dot(xs, wg.astype(xs.dtype), group_sizes)
            h = layers.activate(h, activation) * g
        else:
            h = layers.activate(h, activation)
        ye = jax.lax.ragged_dot(h, wo.astype(h.dtype), group_sizes)
        ye = pin(jnp.where(in_groups, ye, 0))
    with jax.named_scope("moe.route"):
        # combine: unsort (a gather by the inverse permutation), weight by
        # the gates (zero where the expert is held elsewhere), sum over k
        w = jnp.where(held, gates.reshape(-1), 0.0).astype(ye.dtype)
        y_tk = pin(jnp.take(ye, inv, axis=0) * w[:, None])  # (t, j) order
        out = jnp.sum(y_tk.reshape(T, k, d), axis=1)
    return out, aux, (jnp.sum(held.astype(jnp.int32)), group_sizes, dropped)


def _stats(n_local, sizes, dropped) -> dict:
    """``moe_block``'s counters from the assignments to held experts, the
    rows each held expert's grouped matmul computed, and the held
    assignments no grouped matmul row covered."""
    return {"local_tokens": n_local.astype(jnp.float32),
            "max_expert_load": jnp.max(sizes).astype(jnp.float32),
            "dropped": dropped.astype(jnp.float32)}


def _ep_axes(mesh, rule_table, x_shape, w_shape):
    """(token axes, expert axis, width axis) of the expert-parallel layout,
    or None when the mesh shards neither the experts nor their width.
    Tokens keep the batch's sharding; the expert axis, when the experts do
    not divide it, shards the expert width instead (the rules' fallback)."""
    if mesh is None:
        return None
    wspec = sh.logical_to_spec(
        ("w_experts", None, "w_expert_mlp"), rule_table, mesh, w_shape,
        "moe experts")
    e_ax, f_ax = wspec[0], wspec[2]
    if e_ax is None and f_ax is None:
        return None
    tok = sh.logical_to_spec(("act_batch", None), rule_table, mesh, x_shape,
                             "moe tokens")[0]
    return tok, e_ax, f_ax


def _expert_parallel(x, router_w, wi, wg, wo, cfg: MoEConfig,
                     activation: str, seq_len: int, mesh, tok, e_ax, f_ax):
    """``_held_part`` under ``shard_map``: each device routes its tokens
    over all experts and computes the part its own experts (or its slice of
    every expert's width) give; a ``psum`` over the expert axis adds the
    parts, as the shares of the chips that hold a layer add up."""
    from jax.sharding import PartitionSpec as P

    model = tuple(a for a in (e_ax, f_ax) if a is not None)
    toks = () if tok is None else (tok if isinstance(tok, tuple) else (tok,))
    w_in, w_out = P(e_ax, None, f_ax), P(e_ax, f_ax, None)

    def body(x, router_w, wi, wg, wo):
        first = cfg.first_held
        if e_ax is not None:
            first = first + jax.lax.axis_index(e_ax) * wi.shape[0]
        part = cfg._replace(experts_held=wi.shape[0], first_held=first)
        out, aux, counts = _held_part(x, router_w, wi, wg, wo, part,
                                      activation, seq_len)
        if toks:
            aux = jax.lax.pmean(aux, toks)
            counts = jax.lax.psum(counts, toks)
        st = _stats(*counts)
        if e_ax is not None:   # a width slice's devices count the same rows
            st = {"local_tokens": jax.lax.psum(st["local_tokens"], e_ax),
                  "max_expert_load": jax.lax.pmax(st["max_expert_load"],
                                                  e_ax),
                  "dropped": jax.lax.psum(st["dropped"], e_ax)}
        return jax.lax.psum(out, model), aux, st

    stat_spec = {"local_tokens": P(), "max_expert_load": P(), "dropped": P()}
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tok, None), P(), w_in, w_in, w_out),
        out_specs=(P(tok, None), P(), stat_spec), check_vma=False,
    )(x, router_w, wi, wg, wo)
