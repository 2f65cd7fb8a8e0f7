"""Faults planted in the LM training path, for the ``train_lm`` cell's
tests and for setting its limits on the chip: each takes a pytest
``MonkeyPatch`` and breaks one thing the cell's checks must catch.

``control`` is not planted in the program: it is the reference computed a
precision below the configuration's (``first_steps(dtype=...)``).
"""
from __future__ import annotations


def renormalised_gates(mp):
    """The top-6 gates renormalised to sum to 1 (``norm_topk_prob``
    ignored)."""
    from bench import lm

    orig = lm.lm_config

    def broken(cfg):
        model = orig(cfg)
        return model._replace(moe=model.moe._replace(norm_topk_prob=True))
    mp.setattr(lm, "lm_config", broken)


def capacity_dispatch(mp, factor: float = 1.0):
    """Capacity dispatch: each held expert computes at most
    ceil(T·k·factor / E) of its rows, the rest dropped (and counted)."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers
    from repro.models import moe as moe_lib

    def broken(x, router_w, wi, wg, wo, cfg, *, activation, rule_table,
               seq_len, shared=None):
        T, d = x.shape
        k, H = cfg.top_k, cfg.held
        C = -(-int(T * k * factor) // cfg.num_experts)
        gates, ids, aux = moe_lib.route(x, router_w, cfg, seq_len)
        local = ids.reshape(-1) - cfg.first_held
        held = (local >= 0) & (local < H)
        key = jnp.where(held, local, H)
        order = jnp.argsort(key, stable=True)
        counts = jnp.bincount(key, length=H + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T * k) - starts[key[order]]
        kept = held & (pos[jnp.argsort(order)] < C)
        key = jnp.where(kept, local, H)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
        # rows past the groups masked as the program masks them
        in_groups = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]
        xs = jnp.where(in_groups, jnp.take(x, order // k, axis=0), 0)
        h = layers.activate(jax.lax.ragged_dot(xs, wi.astype(x.dtype), sizes),
                            activation)
        h = h * jax.lax.ragged_dot(xs, wg.astype(x.dtype), sizes)
        ye = jnp.where(in_groups,
                       jax.lax.ragged_dot(h, wo.astype(x.dtype), sizes), 0)
        w = jnp.where(kept, gates.reshape(-1), 0.0).astype(ye.dtype)
        out = jnp.sum((jnp.take(ye, inv, axis=0) * w[:, None])
                      .reshape(T, k, d), axis=1)
        if shared is not None:
            swi, swg, swo = shared
            hs = layers.activate(x @ swi.astype(x.dtype), activation)
            out = out + (hs * (x @ swg.astype(x.dtype))) @ swo.astype(x.dtype)
        # counted as the program counts it: held assignments whose row
        # lies past the rows the grouped matmuls cover
        dropped = jnp.sum(held & (inv >= jnp.sum(sizes)))
        stats = {"local_tokens": jnp.sum(held).astype(jnp.float32),
                 "max_expert_load": jnp.max(sizes).astype(jnp.float32),
                 "dropped": dropped.astype(jnp.float32)}
        return out.astype(x.dtype), aux, stats
    mp.setattr(moe_lib, "moe_block", broken)


def unrotated_latent(mp):
    """The latent term computed on c, not on c·R (the rotation left out)."""
    from repro.core import kv_quant

    orig = kv_quant.latent_distortion

    def broken(rot_c, cb_c, c):
        import jax.numpy as jnp

        eye = jnp.eye(rot_c.shape[-1], dtype=rot_c.dtype)
        return orig(eye, cb_c, c)
    mp.setattr(kv_quant, "latent_distortion", broken)


def half_batch(mp):
    """The step's loss and gradient taken on the first half of the
    batch's sequences only."""
    from repro.models import transformer as tfm

    orig = tfm.forward_train_stats

    def broken(params, tokens, labels, cfg):
        h = tokens.shape[0] // 2
        return orig(params, tokens[:h], labels[:h], cfg)
    mp.setattr(tfm, "forward_train_stats", broken)


PLANTED = {"renormalised_gates": renormalised_gates,
           "capacity_dispatch": capacity_dispatch,
           "unrotated_latent": unrotated_latent,
           "half_batch": half_batch}
