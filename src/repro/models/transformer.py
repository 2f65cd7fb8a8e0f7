"""Decoder-only transformer LM substrate: dense / GQA / MoE variants.

One config covers the five assigned LM architectures (qwen1.5-4b, olmo-1b,
nemotron-4-340b, grok-1, llama4-maverick) and DeepSeek-V2-Lite. Layers are
scanned (params carry a leading layer axis) and rematerialized, so the HLO
stays compact at 96 layers and activation memory is O(1) in depth.

``moe_every=2`` (llama4-style interleaving) scans over two-layer super-blocks
— sublayer "a" dense, sublayer "b" MoE — because lax.scan needs homogeneous
per-step params. ``first_dense_layers`` leading dense layers (DeepSeek's
``first_k_dense_replace``) run before the scan, unrolled, with their own
params (``dense_layers``).

``mla`` switches attention to DeepSeek-V2's multi-head latent attention
(arXiv 2405.04434 §2.1): keys and values come from one 512-wide latent
``c_kv`` per token (down-projection, RMSNorm, per-head up-projections) plus
one decoupled RoPE key shared by all heads, with YaRN frequencies. With
``kv_quant`` set, the PQ index layer sits on that shared latent (one
rotation ``rot_c`` and one codebook per layer) instead of per-head K/V.

Paths:
  forward_train   tokens → mean xent loss (+ MoE aux, + optional KV-PQ
                  distortion term — the paper's Eq. 1 second term applied to
                  the KV stream); ``forward_train_stats`` also returns the
                  expert layers' routing counters
  serve_prefill   tokens → last-token logits + KV cache (dense or PQ codes)
  serve_decode    one token in, one token out, cache updated in place
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kv_quant
from repro.models import layers, moe as moe_lib, param
from repro.models.param import ParamSpec
from repro.sharding import rules as sh


class MLAConfig(NamedTuple):
    """Multi-head latent attention without a query LoRA, with YaRN RoPE
    (DeepSeek-V2 §2.1 and its config's ``rope_scaling``)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_factor: float = 1.0         # YaRN scaling factor (1: plain RoPE)
    original_max_pos: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = layers.yarn_mscale(self.rope_factor, self.mscale_all_dim)
        return self.q_head_dim ** -0.5 * m * m

    @property
    def rope_mscale(self) -> float:
        return (layers.yarn_mscale(self.rope_factor, self.mscale)
                / layers.yarn_mscale(self.rope_factor, self.mscale_all_dim))


class TransformerConfig(NamedTuple):
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    activation: str = "silu"
    use_glu: bool = True
    qkv_bias: bool = False
    norm: str = "rmsnorm"
    rope_theta: float = 10000.0
    moe: moe_lib.MoEConfig | None = None
    moe_every: int = 1               # 2 → dense/MoE interleave (llama4)
    first_dense_layers: int = 0      # leading dense layers (DeepSeek)
    mla: MLAConfig | None = None     # latent attention (DeepSeek-V2)
    train_seq_len: int = 128         # the trainer's LM sequence length
    kv_quant: kv_quant.KVQuantConfig | None = None
    train_kv_quant: bool = False     # add KV distortion term to the train loss
    dtype: Any = jnp.bfloat16        # activation dtype
    param_dtype: Any = jnp.bfloat16
    q_chunk: int = 256
    xent_chunk: int = 8192
    moe_chunk: int = 0               # >0: serve-path MoE processed in token
    #                                  chunks (bounds the (T·k, d) routed-row
    #                                  buffers at 1M-token prefill)
    remat: bool = True
    scan_groups: int = 1             # two-level layer scan: only every
    #                                  (scan_len/scan_groups)-th boundary is
    #                                  saved in bwd (sqrt-remat, ~+1/G fwd)
    train_accum_steps: int = 1       # microbatch accumulation (memory fit)
    rules: str = "lm_base"           # key into sharding rule registry

    @property
    def rule_table(self) -> dict[str, Any]:
        return sh.RULE_REGISTRY[self.rules]

    @property
    def interleaved(self) -> bool:
        return self.moe is not None and self.moe_every == 2

    @property
    def scan_len(self) -> int:
        return ((self.num_layers - self.first_dense_layers)
                // (2 if self.interleaved else 1))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _attn_specs(cfg: TransformerConfig, L: int):
    d = cfg.d_model
    if cfg.mla is not None:
        m, H = cfg.mla, cfg.num_heads
        r = m.kv_lora_rank
        return {
            "wq": ParamSpec((L, d, H * m.q_head_dim), ("layers", "w_embed", "w_heads")),
            "wkv_a": ParamSpec((L, d, r + m.qk_rope_head_dim), ("layers", "w_embed", None)),
            "kv_norm": ParamSpec((L, r), ("layers", None), init="ones"),
            "wkv_b": ParamSpec((L, r, H * (m.qk_nope_head_dim + m.v_head_dim)),
                               ("layers", None, "w_heads")),
            "wo": ParamSpec((L, H * m.v_head_dim, d), ("layers", "w_heads", "w_embed")),
        }
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = {
        "wq": ParamSpec((L, d, Hq * hd), ("layers", "w_embed", "w_heads")),
        "wk": ParamSpec((L, d, Hkv * hd), ("layers", "w_embed", "w_kv_heads")),
        "wv": ParamSpec((L, d, Hkv * hd), ("layers", "w_embed", "w_kv_heads")),
        "wo": ParamSpec((L, Hq * hd, d), ("layers", "w_heads", "w_embed")),
    }
    if cfg.qkv_bias:
        attn["bq"] = ParamSpec((L, Hq * hd), ("layers", "w_heads"), init="zeros")
        attn["bk"] = ParamSpec((L, Hkv * hd), ("layers", "w_kv_heads"), init="zeros")
        attn["bv"] = ParamSpec((L, Hkv * hd), ("layers", "w_kv_heads"), init="zeros")
    return attn


def _sublayer_specs(cfg: TransformerConfig, L: int, moe_on: bool):
    d = cfg.d_model
    f = cfg.d_ff
    if moe_on:
        m = cfg.moe
        E, Eh, fe = m.num_experts, m.held, m.d_ff or f
        ffn = {
            "router": ParamSpec((L, d, E), ("layers", "w_embed", None)),
            "wi": ParamSpec((L, Eh, d, fe), ("layers", "w_experts", "w_embed", "w_expert_mlp")),
            "wo": ParamSpec((L, Eh, fe, d), ("layers", "w_experts", "w_expert_mlp", "w_embed")),
        }
        if cfg.use_glu:
            ffn["wg"] = ParamSpec((L, Eh, d, fe), ("layers", "w_experts", "w_embed", "w_expert_mlp"))
        if m.shared_ff:
            fs = m.shared_ff
            ffn["shared_wi"] = ParamSpec((L, d, fs), ("layers", "w_embed", "w_mlp"))
            ffn["shared_wg"] = ParamSpec((L, d, fs), ("layers", "w_embed", "w_mlp"))
            ffn["shared_wo"] = ParamSpec((L, fs, d), ("layers", "w_mlp", "w_embed"))
    else:
        ffn = {
            "wi": ParamSpec((L, d, f), ("layers", "w_embed", "w_mlp")),
            "wo": ParamSpec((L, f, d), ("layers", "w_mlp", "w_embed")),
        }
        if cfg.use_glu:
            ffn["wg"] = ParamSpec((L, d, f), ("layers", "w_embed", "w_mlp"))

    out = {"attn": _attn_specs(cfg, L), "ffn": ffn}
    if cfg.norm == "rmsnorm":
        out["ln1"] = ParamSpec((L, d), ("layers", None), init="ones")
        out["ln2"] = ParamSpec((L, d), ("layers", None), init="ones")
    return out


def param_specs(cfg: TransformerConfig):
    d, V, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    hd = cfg.head_dim
    if cfg.interleaved:
        Lb = L // 2
        layers_spec = {
            "a": _sublayer_specs(cfg, Lb, moe_on=False),
            "b": _sublayer_specs(cfg, Lb, moe_on=True),
        }
    else:
        layers_spec = _sublayer_specs(cfg, L - cfg.first_dense_layers,
                                      moe_on=cfg.moe is not None)

    specs = {
        "embed": ParamSpec((V, d), ("w_vocab", "w_embed"), scale=1.0),
        "head": ParamSpec((V, d), ("w_vocab", "w_embed")),
        "layers": layers_spec,
    }
    if cfg.first_dense_layers:
        specs["dense_layers"] = _sublayer_specs(cfg, cfg.first_dense_layers,
                                                moe_on=False)
    if cfg.norm == "rmsnorm":
        specs["ln_f"] = ParamSpec((d,), (None,), init="ones")

    if cfg.kv_quant is not None:
        kq = cfg.kv_quant
        D, K, sub = kq.num_subspaces, kq.num_codewords, kq.sub
        if cfg.mla is not None:   # one latent stream per layer
            n = kq.head_dim
            specs["kvq"] = {
                "rot_c": ParamSpec((L, n, n), ("layers", "rot_in", "rot_out"), init="eye"),
                "cb_c": ParamSpec((L, D, K, sub), ("layers", "pq_dim", "pq_code", "pq_sub"), scale=0.02),
            }
            return specs
        specs["kvq"] = {
            "rot_k": ParamSpec((L, hd, hd), ("layers", "rot_in", "rot_out"), init="eye"),
            "rot_v": ParamSpec((L, hd, hd), ("layers", "rot_in", "rot_out"), init="eye"),
            "cb_k": ParamSpec((L, D, K, sub), ("layers", "pq_dim", "pq_code", "pq_sub"), scale=0.02),
            "cb_v": ParamSpec((L, D, K, sub), ("layers", "pq_dim", "pq_code", "pq_sub"), scale=0.02),
        }
    return specs


def init_params(key: jax.Array, cfg: TransformerConfig):
    return param.init_params(key, param_specs(cfg), cfg.param_dtype)


def _kvq_scan_tree(params, cfg: TransformerConfig):
    """KV-quant params reshaped for the layer scan: leading scan_len (and a
    sublayer pair axis when interleaved), the leading dense layers' rows
    left out. None when disabled."""
    if "kvq" not in params:
        return None
    kvq = params["kvq"]
    if cfg.first_dense_layers:
        kvq = jax.tree.map(lambda a: a[cfg.first_dense_layers:], kvq)
    if cfg.interleaved:
        Lb = cfg.scan_len
        return jax.tree.map(lambda a: a.reshape(Lb, 2, *a.shape[1:]), kvq)
    return kvq


def _kvq_params(kvq_leaf_tree) -> kv_quant.KVQuantParams | None:
    if kvq_leaf_tree is None:
        return None
    return kv_quant.KVQuantParams(
        rot_k=kvq_leaf_tree["rot_k"], rot_v=kvq_leaf_tree["rot_v"],
        cb_k=kvq_leaf_tree["cb_k"], cb_v=kvq_leaf_tree["cb_v"],
    )


def _kvq_sub(kvq_tree, i):
    if kvq_tree is None:
        return None
    return jax.tree.map(lambda a: a[i], kvq_tree)


# ---------------------------------------------------------------------------
# Layer body
# ---------------------------------------------------------------------------

def _qkv(lp, h, cfg: TransformerConfig, positions):
    B, S, d = h.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = h @ lp["attn"]["wq"].astype(h.dtype)
    k = h @ lp["attn"]["wk"].astype(h.dtype)
    v = h @ lp["attn"]["wv"].astype(h.dtype)
    if cfg.qkv_bias:
        q = q + lp["attn"]["bq"].astype(h.dtype)
        k = k + lp["attn"]["bk"].astype(h.dtype)
        v = v + lp["attn"]["bv"].astype(h.dtype)
    q = q.reshape(B, S, Hq, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(lp, h, cfg: TransformerConfig, positions):
    """MLA projections (DeepSeek-V2 §2.1.2–2.1.3, no query LoRA): q from h;
    the latent c = RMSNorm(h·W_DKV) and the shared RoPE key from one
    down-projection; per-head k_nope and v from c·W_UKV. Returns
    q, k (B, S, H, nope + rope), v (B, S, H, v_head_dim) and c (B, S, r)."""
    m, H = cfg.mla, cfg.num_heads
    B, S, _ = h.shape
    dn, dr, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank
    a = lp["attn"]
    q = (h @ a["wq"].astype(h.dtype)).reshape(B, S, H, dn + dr)
    kv_a = h @ a["wkv_a"].astype(h.dtype)
    c = layers.rms_norm(kv_a[..., :r], a["kv_norm"])
    kv = (c @ a["wkv_b"].astype(h.dtype)).reshape(B, S, H, dn + m.v_head_dim)
    freqs = layers.yarn_frequencies(dr, cfg.rope_theta, m.rope_factor,
                                    m.original_max_pos, m.beta_fast,
                                    m.beta_slow)
    q_pe = layers.apply_rope(q[..., dn:], positions, freqs=freqs,
                             mscale=m.rope_mscale)
    k_pe = layers.apply_rope(kv_a[:, :, None, r:], positions, freqs=freqs,
                             mscale=m.rope_mscale)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
    return q, k, kv[..., dn:], c


def _ffn(lp, h, cfg: TransformerConfig, moe_on: bool):
    """Dense MLP or MoE on (B, S, d). Returns (out, aux_loss, stats)."""
    rt = cfg.rule_table
    if moe_on:
        B, S, d = h.shape
        T = B * S
        f = lp["ffn"]
        shared = ((f["shared_wi"], f["shared_wg"], f["shared_wo"])
                  if "shared_wi" in f else None)

        def run_moe(tokens, seq_len):
            return moe_lib.moe_block(
                tokens, f["router"].astype(jnp.float32), f["wi"],
                f.get("wg"), f["wo"], cfg.moe, activation=cfg.activation,
                rule_table=rt, seq_len=seq_len, shared=shared)

        flat = h.reshape(T, d)
        if cfg.moe_chunk and T > cfg.moe_chunk:
            assert T % cfg.moe_chunk == 0
            nc = T // cfg.moe_chunk
            out, aux, st = jax.lax.map(
                lambda t: run_moe(t, min(S, cfg.moe_chunk)),
                flat.reshape(nc, cfg.moe_chunk, d))
            out = out.reshape(T, d)
            aux = jnp.mean(aux)
            st = {"local_tokens": jnp.sum(st["local_tokens"]),
                  "max_expert_load": jnp.max(st["max_expert_load"]),
                  "dropped": jnp.sum(st["dropped"])}
        else:
            out, aux, st = run_moe(flat, S)
        return out.reshape(B, S, d), aux, st
    hh = h @ lp["ffn"]["wi"].astype(h.dtype)
    hh = sh.constrain(hh, ("act_batch", "act_seq", "act_mlp"), rt)
    if cfg.use_glu:
        g = h @ lp["ffn"]["wg"].astype(h.dtype)
        hh = layers.activate(hh, cfg.activation) * g
    else:
        hh = layers.activate(hh, cfg.activation)
    out = hh @ lp["ffn"]["wo"].astype(h.dtype)
    return out, jnp.float32(0.0), _NO_STATS


#: the expert-layer counters a dense layer contributes (see moe.moe_block)
_NO_STATS = {"local_tokens": 0.0, "max_expert_load": 0.0, "dropped": 0.0}


def _norm(lp, name, x, cfg: TransformerConfig):
    scale = lp[name] if cfg.norm == "rmsnorm" else None
    return layers.apply_norm(x, scale, cfg.norm)


def _layer_train(x, lp, cfg: TransformerConfig, positions, kvq_l, moe_on):
    """Full-sequence layer forward. Returns (x, (aux, kv_dist, stats))."""
    rt = cfg.rule_table
    B, S = x.shape[:2]
    h = _norm(lp, "ln1", x, cfg)
    mla = cfg.mla is not None
    with jax.named_scope("mla") if mla else contextlib.nullcontext():
        if mla:
            q, k, v, c = _mla_qkv(lp, h, cfg, positions)
        else:
            q, k, v = _qkv(lp, h, cfg, positions)
        q = sh.constrain(q, ("act_batch", "act_seq", "act_heads", None), rt)
        att = layers.blockwise_attention(
            q, k, v, q_chunk=cfg.q_chunk,
            scale=cfg.mla.softmax_scale if mla else None)
        x = x + att.reshape(B, S, -1) @ lp["attn"]["wo"].astype(x.dtype)
    h2 = _norm(lp, "ln2", x, cfg)
    y, aux, stats = _ffn(lp, h2, cfg, moe_on)
    x = x + y
    # act_boundary_seq: None normally; "model" under *_bigtrain rules so the
    # residual saved for backward is stored seq-sharded (ZeRO-activations).
    x = sh.constrain(x, ("act_batch", "act_boundary_seq", "act_embed"), rt)

    kv_dist = jnp.float32(0.0)
    if cfg.train_kv_quant and kvq_l is not None:
        if mla:
            # the paper's Eq. (1) second term on every token's latent
            with jax.named_scope("mla.kvq"):
                kv_dist = kv_quant.latent_distortion(
                    kvq_l["rot_c"], kvq_l["cb_c"], c)
        else:
            # Distortion term on a subsample of this layer's K/V vectors —
            # the paper's Eq. (1) second term for the KV index.
            kvp = _kvq_params(kvq_l)
            ks = k[:, : min(64, S)].reshape(-1, cfg.head_dim)
            vs = v[:, : min(64, S)].reshape(-1, cfg.head_dim)
            kv_dist = kv_quant.kv_distortion(kvp, ks, vs)
    return x, (aux, kv_dist, stats)


def _constrain_grouped(grouped, params, cfg: TransformerConfig):
    """Apply logical shardings (with the extra leading group axis) to the
    (G, per, ...) reshaped scan inputs."""
    rt = cfg.rule_table
    spec_tree = param_specs(cfg)
    logical_layers = param.logical_tree(spec_tree["layers"])
    kvq_logical = (param.logical_tree(spec_tree["kvq"])
                   if "kvq" in spec_tree else None)
    if cfg.interleaved and kvq_logical is not None:
        # kvq leaves gained a sublayer-pair axis in _kvq_scan_tree
        kvq_logical = jax.tree.map(
            lambda lg: (lg[0], None) + tuple(lg[1:]), kvq_logical,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))
    logical = (logical_layers, kvq_logical)

    def is_logical(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)

    arrs, treedef = jax.tree.flatten(grouped)
    lgs = jax.tree.leaves(logical, is_leaf=is_logical)
    assert len(arrs) == len(lgs), (len(arrs), len(lgs))
    pinned = [sh.constrain(a, ("layers",) + tuple(lg), rt)
              for a, lg in zip(arrs, lgs)]
    return jax.tree.unflatten(treedef, pinned)


def _maybe_remat(fn, cfg: TransformerConfig):
    """Full remat (save layer inputs only). The tempting
    dots_with_no_batch_dims_saveable policy saves every projection output —
    per-token matmuls have no dot batch dims — which stacked f32 copies of
    (L, B, S, d) across the layer scan (measured +200 GiB/dev at the 4k
    train shape). Recomputing the layer costs ~1 extra fwd pass and keeps
    only the bf16 boundary per layer."""
    if not cfg.remat:
        return fn
    return jax.checkpoint(fn, static_argnums=(2, 5))


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------

def _add(acc, aux, dist, stats):
    """Fold one layer's (aux, distortion, counters) into the running totals:
    sums, and the largest expert load."""
    return {"aux": acc["aux"] + aux, "dist": acc["dist"] + dist,
            "local_tokens": acc["local_tokens"] + stats["local_tokens"],
            "max_expert_load": jnp.maximum(acc["max_expert_load"],
                                           stats["max_expert_load"]),
            "dropped": acc["dropped"] + stats["dropped"]}


def forward_train(params, tokens: jax.Array, labels: jax.Array,
                  cfg: TransformerConfig) -> jax.Array:
    """tokens/labels (B, S) int32 → scalar loss."""
    return forward_train_stats(params, tokens, labels, cfg)[0]


def forward_train_stats(params, tokens: jax.Array, labels: jax.Array,
                        cfg: TransformerConfig):
    """tokens/labels (B, S) int32 → (scalar loss, stats). stats (MoE
    configs only) holds the expert layers' counters: ``moe.local_tokens``
    (assignments to held experts, mean over expert layers),
    ``moe.max_expert_load`` (largest group) and ``moe.dropped`` (0)."""
    rt = cfg.rule_table
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x = sh.constrain(x, ("act_batch", "act_seq", "act_embed"), rt)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    fn = _maybe_remat(_layer_train, cfg)
    f32 = jnp.float32(0.0)
    acc = {"aux": f32, "dist": f32, "local_tokens": f32,
           "max_expert_load": f32, "dropped": f32}

    kvq_all = params.get("kvq")
    for i in range(cfg.first_dense_layers):
        lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
        kvq_l = (None if kvq_all is None
                 else jax.tree.map(lambda a: a[i], kvq_all))
        x, (aux, dist, st) = fn(x, lp, cfg, positions, kvq_l, False)
        acc = _add(acc, aux, dist, st)

    def body(carry, scanned):
        x, acc = carry
        lp, kvq_l = scanned
        if cfg.interleaved:
            x, (a1, d1, s1) = fn(x, lp["a"], cfg, positions, _kvq_sub(kvq_l, 0), False)
            x, (a2, d2, s2) = fn(x, lp["b"], cfg, positions, _kvq_sub(kvq_l, 1), True)
            acc = _add(_add(acc, a1, d1, s1), a2, d2, s2)
        else:
            x, (aux, dist, st) = fn(x, lp, cfg, positions, kvq_l, cfg.moe is not None)
            acc = _add(acc, aux, dist, st)
        return (x, acc), None

    scanned = (params["layers"], _kvq_scan_tree(params, cfg))
    carry0 = (x, acc)
    G = cfg.scan_groups
    if G > 1:
        assert cfg.scan_len % G == 0
        per = cfg.scan_len // G
        grouped = jax.tree.map(
            lambda a: a.reshape(G, per, *a.shape[1:]), scanned)
        # Re-pin shardings after the grouping reshape: without this the SPMD
        # partitioner invents (32, 8)-style tilings for the grouped weights
        # and buys them back with f32 full-rematerialization temporaries
        # (measured ~7 GiB/dev on nemotron).
        grouped = _constrain_grouped(grouped, params, cfg)

        @jax.checkpoint
        def run_group(carry, group_xs):
            carry, _ = jax.lax.scan(body, carry, group_xs)
            return carry, None

        (x, acc), _ = jax.lax.scan(run_group, carry0, grouped)
    else:
        (x, acc), _ = jax.lax.scan(body, carry0, scanned)
    x = _final_norm(params, x, cfg)
    loss = layers.softmax_xent_chunked(
        x.reshape(B * S, cfg.d_model), params["head"], labels.reshape(-1),
        chunk=cfg.xent_chunk,
    )
    total = loss
    stats = {}
    if cfg.moe is not None:
        total = total + cfg.moe.aux_alpha * acc["aux"]
        stats = {"moe.local_tokens": acc["local_tokens"] / cfg.scan_len,
                 "moe.max_expert_load": acc["max_expert_load"],
                 "moe.dropped": acc["dropped"]}
    if cfg.train_kv_quant and "kvq" in params:
        total = total + 0.1 * acc["dist"] / cfg.num_layers
    return total, stats


def _final_norm(params, x, cfg: TransformerConfig):
    scale = params["ln_f"] if cfg.norm == "rmsnorm" else None
    return layers.apply_norm(x, scale, cfg.norm)


# ---------------------------------------------------------------------------
# Serving: prefill + decode (dense cache or PQ-compressed cache)
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    k: jax.Array       # (L, B, Hkv, S, hd)
    v: jax.Array
    length: jax.Array  # (B,) int32 — number of valid positions


class PQDecodeCache(NamedTuple):
    k_codes: jax.Array  # (L, B, Hkv, S, D) uint8
    v_codes: jax.Array
    length: jax.Array


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               quantized: bool | None = None):
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    quantized = (cfg.kv_quant is not None) if quantized is None else quantized
    if quantized:
        D = cfg.kv_quant.num_subspaces
        z = jnp.zeros((L, batch, Hkv, max_len, D), jnp.uint8)
        return PQDecodeCache(k_codes=z, v_codes=z, length=jnp.zeros((batch,), jnp.int32))
    z = jnp.zeros((L, batch, Hkv, max_len, hd), cfg.dtype)
    return DecodeCache(k=z, v=z, length=jnp.zeros((batch,), jnp.int32))


def _write_cache(cache_layer: jax.Array, new: jax.Array, length: jax.Array):
    """cache (B, Hkv, S, e) ← new (B, Hkv, e) at per-batch position length."""

    def upd(c, n, pos):  # c (Hkv, S, e), n (Hkv, e)
        return jax.lax.dynamic_update_slice_in_dim(c, n[:, None], pos, axis=1)

    return jax.vmap(upd)(cache_layer, new, length)


def _decode_sublayer(x, lp, cfg: TransformerConfig, pos, kvq_l, moe_on,
                     kc, vc, quantized: bool, rt):
    B = x.shape[0]
    Hq, hd = cfg.num_heads, cfg.head_dim
    h = _norm(lp, "ln1", x[:, None], cfg)  # (B, 1, d)
    q, k, v = _qkv(lp, h, cfg, pos[:, None])
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    if quantized:
        kvp = _kvq_params(kvq_l)
        ck, cv = kv_quant.encode_kv(kvp, k, v)
        kc = _write_cache(kc, ck, pos)
        vc = _write_cache(vc, cv, pos)
        kc = sh.constrain(kc, ("act_batch", None, "act_kv_seq", None), rt)
        vc = sh.constrain(vc, ("act_batch", None, "act_kv_seq", None), rt)
        mask = jnp.arange(kc.shape[2])[None] <= pos[:, None]
        att = kv_quant.adc_decode_attention(kvp, q, kc, vc, length_mask=mask)
    else:
        kc = _write_cache(kc, k, pos)
        vc = _write_cache(vc, v, pos)
        kc = sh.constrain(kc, ("act_batch", None, "act_kv_seq", None), rt)
        vc = sh.constrain(vc, ("act_batch", None, "act_kv_seq", None), rt)
        att = layers.decode_attention(q, kc, vc, pos + 1)
    x = x + att.reshape(B, Hq * hd) @ lp["attn"]["wo"].astype(x.dtype)
    h2 = _norm(lp, "ln2", x[:, None], cfg)
    y, _aux, _ = _ffn(lp, h2, cfg, moe_on)
    return x + y[:, 0], kc, vc


def _serve_supported(cfg: TransformerConfig) -> None:
    if cfg.mla is not None or cfg.first_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: the serve paths have no latent cache and no "
            "leading dense layers; only the training forward runs MLA")


def serve_decode(params, token: jax.Array, cache, cfg: TransformerConfig):
    """One decode step. token (B,) int32 → (logits (B, V), new cache).

    The cache rides in the scan CARRY and is updated in place with
    dynamic_update_slice — emitting per-layer caches as scan ys doubles the
    cache footprint (input stack + output stack both live; measured 62 vs
    ~10 GiB/device on nemotron decode_32k)."""
    _serve_supported(cfg)
    rt = cfg.rule_table
    x = jnp.take(params["embed"], token, axis=0).astype(cfg.dtype)  # (B, d)
    pos = cache.length  # (B,)
    quantized = isinstance(cache, PQDecodeCache)
    k_all, v_all = (cache.k_codes, cache.v_codes) if quantized else (cache.k, cache.v)
    n_sub = 2 if cfg.interleaved else 1

    def body(carry, scanned):
        x, k_all, v_all = carry
        lp, kvq_l, li = scanned  # li = layer index of sublayer "a"

        def run(x, k_all, v_all, lp_s, kvq_s, moe_on, idx):
            kc, vc = k_all[idx], v_all[idx]
            x, kc, vc = _decode_sublayer(
                x, lp_s, cfg, pos, kvq_s, moe_on, kc, vc, quantized, rt)
            k_all = jax.lax.dynamic_update_slice_in_dim(
                k_all, kc[None], idx, axis=0)
            v_all = jax.lax.dynamic_update_slice_in_dim(
                v_all, vc[None], idx, axis=0)
            return x, k_all, v_all

        if cfg.interleaved:
            x, k_all, v_all = run(x, k_all, v_all, lp["a"],
                                  _kvq_sub(kvq_l, 0), False, li)
            x, k_all, v_all = run(x, k_all, v_all, lp["b"],
                                  _kvq_sub(kvq_l, 1), True, li + 1)
        else:
            x, k_all, v_all = run(x, k_all, v_all, lp, kvq_l,
                                  cfg.moe is not None, li)
        return (x, k_all, v_all), None

    layer_ids = jnp.arange(cfg.scan_len, dtype=jnp.int32) * n_sub
    scanned = (params["layers"], _kvq_scan_tree(params, cfg), layer_ids)
    (x, new_k, new_v), _ = jax.lax.scan(body, (x, k_all, v_all), scanned)
    x = _final_norm(params, x[:, None], cfg)[:, 0]
    logits = (x.astype(jnp.float32) @ params["head"].astype(jnp.float32).T)
    logits = sh.constrain(logits, ("act_batch", "act_vocab"), rt)
    if quantized:
        new_cache = PQDecodeCache(new_k, new_v, cache.length + 1)
    else:
        new_cache = DecodeCache(new_k, new_v, cache.length + 1)
    return logits, new_cache


def _prefill_sublayer(x, lp, cfg, positions, kvq_l, moe_on, quantized, rt):
    B, S = x.shape[:2]
    h = _norm(lp, "ln1", x, cfg)
    q, k, v = _qkv(lp, h, cfg, positions)
    q = sh.constrain(q, ("act_batch", "act_seq", "act_heads", None), rt)
    att = layers.blockwise_attention(q, k, v, q_chunk=cfg.q_chunk)
    att = att.reshape(B, S, cfg.num_heads * cfg.head_dim)
    x = x + att @ lp["attn"]["wo"].astype(x.dtype)
    h2 = _norm(lp, "ln2", x, cfg)
    y, _aux, _ = _ffn(lp, h2, cfg, moe_on)
    x = x + y
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, hd)
    vt = v.transpose(0, 2, 1, 3)
    if quantized:
        kvp = _kvq_params(kvq_l)
        ck, cv = kv_quant.encode_kv(kvp, kt, vt)
        return x, ck, cv
    return x, kt, vt


def serve_prefill(params, tokens: jax.Array, cfg: TransformerConfig,
                  max_len: int | None = None):
    """tokens (B, S) → (last-token logits, populated cache of size max_len)."""
    _serve_supported(cfg)
    rt = cfg.rule_table
    B, S = tokens.shape
    max_len = max_len or S
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    quantized = cfg.kv_quant is not None

    def body(x, scanned):
        lp, kvq_l = scanned
        if cfg.interleaved:
            x, k0, v0 = _prefill_sublayer(
                x, lp["a"], cfg, positions, _kvq_sub(kvq_l, 0), False, quantized, rt)
            x, k1, v1 = _prefill_sublayer(
                x, lp["b"], cfg, positions, _kvq_sub(kvq_l, 1), True, quantized, rt)
            return x, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))
        x, kt, vt = _prefill_sublayer(
            x, lp, cfg, positions, kvq_l, cfg.moe is not None, quantized, rt)
        return x, (kt, vt)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], _kvq_scan_tree(params, cfg)))
    if cfg.interleaved:
        ks = ks.reshape(cfg.num_layers, *ks.shape[2:])
        vs = vs.reshape(cfg.num_layers, *vs.shape[2:])
    x = _final_norm(params, x, cfg)
    logits = x[:, -1].astype(jnp.float32) @ params["head"].astype(jnp.float32).T

    pad = max_len - S
    pad_width = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
    if quantized:
        cache = PQDecodeCache(
            k_codes=jnp.pad(ks, pad_width), v_codes=jnp.pad(vs, pad_width),
            length=jnp.full((B,), S, jnp.int32),
        )
    else:
        cache = DecodeCache(
            k=jnp.pad(ks, pad_width), v=jnp.pad(vs, pad_width),
            length=jnp.full((B,), S, jnp.int32),
        )
    return logits, cache


def model_flops_per_token(cfg: TransformerConfig) -> float:
    """6·N_active — the §Roofline MODEL_FLOPS numerator per token."""
    d, f, L = cfg.d_model, cfg.d_ff, cfg.num_layers
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        attn = (d * Hq * m.q_head_dim + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank * Hq * (m.qk_nope_head_dim + m.v_head_dim)
                + Hq * m.v_head_dim * d)
    else:
        attn = d * (Hq + 2 * Hkv) * hd + Hq * hd * d
    n_mats = 3 if cfg.use_glu else 2
    dense_ffn = n_mats * d * f
    moe_ffn = 0.0
    if cfg.moe is not None:
        moe_ffn = (cfg.moe.top_k * n_mats * d * (cfg.moe.d_ff or f)
                   + 3 * d * cfg.moe.shared_ff)
    if cfg.interleaved:
        ffn_total = (L // 2) * dense_ffn + (L // 2) * moe_ffn
    elif cfg.moe is not None:
        ffn_total = (cfg.first_dense_layers * dense_ffn
                     + cfg.scan_len * moe_ffn)
    else:
        ffn_total = L * dense_ffn
    head = cfg.vocab_size * d
    return 6.0 * (L * attn + ffn_total + head)


def num_params(cfg: TransformerConfig) -> int:
    return param.count_params(param_specs(cfg))
