"""Logical-axis sharding rules (MaxText-style).

Every parameter and major activation in the model zoo is annotated with a
tuple of *logical* axis names. A per-architecture rule table maps logical
names to physical mesh axes; ``logical_to_spec`` resolves the tuple into a
``PartitionSpec``. This keeps the mesh layout (16×16 single-pod, 2×16×16
multi-pod) decoupled from model code, and lets the perf hillclimb swap
sharding strategies by editing one dict.

Conventions:
  * rule value None  → axis replicated
  * rule value str   → single mesh axis
  * rule value tuple → multiple mesh axes (e.g. batch over ("pod", "data"))
  * a logical axis absent from the table → replicated (safe default)

Rules are validated against tensor shapes at resolve time: a mesh axis is
dropped (replication) when it does not divide the dimension — with a warning
collected for the dry-run report, so "qwen has 20 heads, model axis is 16"
shows up as an explicit decision, not a crash.
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

# ---------------------------------------------------------------------------
# Base rule tables
# ---------------------------------------------------------------------------

# Dense/GQA transformer LM. Weights ZeRO-shard their biggest dim over "data"
# and tensor-shard over "model"; activations shard batch over (pod, data) and
# the model-parallel dim over "model".
LM_BASE_RULES: dict[str, Any] = {
    # --- activations ---
    "act_batch": ("pod", "data"),
    "act_seq": None,
    "act_embed": None,
    "act_heads": "model",
    "act_kv_seq": None,          # decode cache seq; decode cells flip to "model"
    "act_boundary_seq": None,    # saved layer boundaries; big-train rules
    #                              shard these over "model" (ZeRO-activations)
    "act_mlp": "model",
    "act_vocab": "model",
    "act_tokens": "data",        # flattened token dim in MoE dispatch
    # --- weights ---
    # ZeRO/FSDP axis; "pod" is filtered out automatically on the single-pod
    # mesh, so multi-pod runs ZeRO-shard across pods too.
    "w_embed": ("pod", "data"),
    "w_heads": "model",
    "w_kv_heads": None,          # GQA: kv heads usually < 16 → replicate
    "w_head_dim": None,
    "w_mlp": "model",
    "w_vocab": "model",
    "w_experts": "model",        # expert parallelism (EP)
    # When E doesn't divide the model axis (grok: 8 experts < 16), the
    # divisibility check drops the EP sharding and this rule tensor-shards
    # the expert hidden dim instead (dedup keeps whichever lands first).
    "w_expert_mlp": "model",
    "layers": None,              # scan axis: never sharded
}

GNN_BASE_RULES: dict[str, Any] = {
    "act_nodes": ("pod", "data"),
    "act_edges": ("pod", "data"),
    "act_feat": None,
    "act_hidden": None,
    "w_in": None,
    "w_out": "model",
    "layers": None,
}

RECSYS_BASE_RULES: dict[str, Any] = {
    "act_batch": ("pod", "data"),
    "act_feat": None,
    "act_hidden": "model",
    "act_cand": ("pod", "data"),   # candidate axis for bulk/retrieval scoring
    "vocab_rows": "model",         # embedding tables row-sharded
    "w_embed_dim": None,
    "w_in": None,
    "w_hidden": "model",
    "w_out": None,
    "fields": None,
    "layers": None,
}

# Paper's own two-tower (dim 512): tiny — replicate weights, shard batch.
PAPER_RULES: dict[str, Any] = dict(RECSYS_BASE_RULES)

# IVF-PQ serving (repro.index): queries are data-parallel; the flattened
# candidate axis (nprobe·blocks·block_size per query) is the big one and
# shards over "model", which splits the selected-list scan across devices.
# Index storage (centroids, codebooks, CSR codes/ids) is replicated by
# default — at 2 B/row/subspace a 100M-item index is ~3 GiB, well under
# chip HBM; the row-sharded variant below flips the corpus rows to
# ("pod", "data").
IVF_RULES: dict[str, Any] = {
    "act_batch": ("pod", "data"),
    "ivf_cand": "model",
    "ivf_cap": None,
    "ivf_lists": None,
    "ivf_rows": None,          # shard axis of a stacked per-shard index
}

# Row-sharded IVF (repro.search sharded backends): the corpus lives
# partitioned over the mesh's data axes end to end — each device owns one
# CSR shard (its own block-aligned lists over its local rows) and serves
# the fused scan locally; results merge with an all_gather + re-top-k.
# Capacity scales with the mesh: rows/device ≈ HBM / (2 B/row/subspace),
# so a ("pod", "data") = 32-way shard lifts the 100M-item ceiling to ~3B.
# Centroids, codebooks, and R stay replicated (they are O(n²), not O(N)).
IVF_SHARDED_RULES: dict[str, Any] = dict(IVF_RULES)
IVF_SHARDED_RULES.update({
    "ivf_cap": ("pod", "data"),
    "ivf_rows": ("pod", "data"),
})

# Rotation/PQ parameters are small and replicated everywhere.
for _t in (LM_BASE_RULES, GNN_BASE_RULES, RECSYS_BASE_RULES, PAPER_RULES):
    _t.update({"rot_in": None, "rot_out": None, "pq_sub": None,
               "pq_code": None, "pq_dim": None})


def merge(base, **overrides):
    out = dict(base)
    out.update(overrides)
    return out


# Named rule tables — configs reference these by key so the whole sharding
# strategy of an arch is one string (and the perf hillclimb is a dict edit).
RULE_REGISTRY: dict[str, dict[str, Any]] = {
    # Head-sharded tensor parallelism (heads % 16 == 0: nemotron, grok).
    "lm_base": LM_BASE_RULES,
    # Attention data-parallel, FFN/vocab/experts tensor-parallel — for archs
    # whose head count does not divide the model axis (qwen 20H, llama4 40H,
    # olmo 16H-kv16 small enough that TP overhead loses anyway).
    "lm_attn_dp": merge(LM_BASE_RULES, **{
        "w_heads": "data", "act_heads": None, "w_kv_heads": "data",
    }),
    # ≥300B training: the per-layer boundary stack saved for backward
    # dominates → shard the saved boundary's seq dim over "model"
    # (all-gathered on use; trades one fast-ICI collective per layer for
    # 16× boundary memory).
    "lm_base_bigtrain": merge(LM_BASE_RULES, **{
        "act_boundary_seq": "model",
    }),
    "lm_attn_dp_bigtrain": merge(LM_BASE_RULES, **{
        "w_heads": "data", "act_heads": None, "w_kv_heads": "data",
        "act_boundary_seq": "model",
    }),
    # Decode/prefill serving: the KV cache dominates memory → shard its seq
    # dim over "model" (context parallelism; XLA all-reduces the softmax
    # stats). Batch stays on (pod, data).
    # NB: weight STORAGE keeps tensor sharding ("model") even when the
    # attention math runs with full heads (act_heads None) — storing
    # attention weights on the (already-used) data axis left them 16×
    # under-sharded (measured +7 GiB/dev on nemotron decode).
    "lm_decode": merge(LM_BASE_RULES, **{
        "act_kv_seq": "model", "act_heads": None,
        "w_heads": "model", "w_kv_heads": "model",
    }),
    "lm_decode_attn_dp": merge(LM_BASE_RULES, **{
        "act_kv_seq": "model", "act_heads": None,
        "w_heads": "model", "w_kv_heads": "model",
    }),
    # Long-context decode (batch=1): the batch axis is given back, the KV
    # seq dim shards over BOTH data and model (524288 / 256 = 2048/device).
    "lm_long_ctx": merge(LM_BASE_RULES, **{
        "act_batch": None, "act_kv_seq": ("data", "model"),
        "act_heads": None, "w_heads": "model", "w_kv_heads": "model",
    }),
    "lm_long_ctx_attn_dp": merge(LM_BASE_RULES, **{
        "act_batch": None, "act_kv_seq": ("data", "model"),
        "act_heads": None, "w_heads": "model", "w_kv_heads": "model",
    }),
    "gnn": GNN_BASE_RULES,
    "recsys": RECSYS_BASE_RULES,
    "paper": PAPER_RULES,
    "ivf": IVF_RULES,
    "ivf_sharded": IVF_SHARDED_RULES,
}


def merge_rules(base: Mapping[str, Any], **overrides: Any) -> dict[str, Any]:
    out = dict(base)
    out.update(overrides)
    return out


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

_WARNINGS: list[str] = []


def pop_warnings() -> list[str]:
    out = list(_WARNINGS)
    _WARNINGS.clear()
    return out


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        size = 1
        for a in axis:
            size *= _mesh_axis_size(mesh, a)
        return size
    return mesh.shape[axis] if axis in mesh.shape else 1


def _present(mesh: Mesh, axis):
    """Filter out mesh axes that don't exist on this mesh (e.g. 'pod' on the
    single-pod mesh)."""
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.shape)
        return kept if kept else None
    return axis if axis in mesh.shape else None


def logical_to_spec(
    logical_axes: Sequence[str | None],
    rules: Mapping[str, Any],
    mesh: Mesh,
    shape: Sequence[int] | None = None,
    tensor_name: str = "?",
) -> PartitionSpec:
    """Resolve logical axis names to a PartitionSpec, dropping (with a
    recorded warning) any mesh axis that does not divide the dimension."""
    spec = []
    for d, name in enumerate(logical_axes):
        axis = _present(mesh, rules.get(name)) if name is not None else None
        if axis is not None and shape is not None:
            size = _mesh_axis_size(mesh, axis)
            if shape[d] % size != 0:
                _WARNINGS.append(
                    f"{tensor_name}: logical axis {name!r} dim {shape[d]} not"
                    f" divisible by mesh axes {axis} (size {size}) — replicated"
                )
                axis = None
        spec.append(axis)
    # PartitionSpec disallows duplicate mesh axes; keep first occurrence.
    seen: set[str] = set()
    clean = []
    for axis in spec:
        if axis is None:
            clean.append(None)
            continue
        ax_tuple = axis if isinstance(axis, tuple) else (axis,)
        kept = tuple(a for a in ax_tuple if a not in seen)
        seen.update(kept)
        clean.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return PartitionSpec(*clean)


def tree_specs(logical_tree, rules, mesh, shape_tree=None):
    """Map a pytree of logical-axis tuples (+ optional matching shapes tree)
    to a pytree of PartitionSpecs."""
    if shape_tree is None:
        return jax.tree.map(
            lambda lg: logical_to_spec(lg, rules, mesh),
            logical_tree,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x),
        )
    return jax.tree.map(
        lambda lg, shp: logical_to_spec(lg, rules, mesh, shp),
        logical_tree,
        shape_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(logical_tree, rules, mesh: Mesh, shape_tree=None):
    specs = tree_specs(logical_tree, rules, mesh, shape_tree)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def constrain(x, logical_axes, rules, mesh=None):
    """with_sharding_constraint by logical names (no-op when no mesh ctx)."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = logical_to_spec(logical_axes, rules, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh():
    """Ambient mesh context via the ``compat.current_mesh`` probe."""
    from repro import compat

    return compat.current_mesh()
