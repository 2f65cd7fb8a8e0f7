"""The work of an LM train step, counted from shapes: FLOPs of the step,
FLOPs and HBM bytes of the held experts (``bench/work.py`` counts the
two-tower's).

Model work, not the implementation's: matmul FLOPs of the forward (2·m·k·n
each; causal attention over the (S + 1)/2 keys a query sees on average),
the backward at twice the forward, and no recomputation; the held experts
count only the rows routed to them (``local_tokens``, read from the step's
own counters). So no share of a peak computed from these counts passes
100% unless the time leaves out part of the work.
"""
from __future__ import annotations

BF16 = 2


def _dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        E=cfg["deployment"]["router_experts"], Eh=cfg["n_routed_experts"],
        L=cfg["num_hidden_layers"], Ld=cfg["first_k_dense_replace"],
        V=cfg["vocab_size"], K=cfg["assumed"]["latent_pq"]["num_codewords"])


def params(cfg: dict) -> int:
    """Parameters held on the chip (experts held, vocabulary slice)."""
    m = _dims(cfg)
    d, H, r = m["d"], m["H"], m["r"]
    attn = (d * H * (m["dn"] + m["dr"]) + d * (r + m["dr"]) + r
            + r * H * (m["dn"] + m["dv"]) + H * m["dv"] * d + 2 * d)
    dense = 3 * d * m["f"]
    moe = d * m["E"] + 3 * d * m["fe"] * m["Eh"] + 3 * d * m["fs"]
    latent = r * r + m["K"] * r
    return (m["L"] * (attn + latent) + m["Ld"] * dense
            + (m["L"] - m["Ld"]) * moe + 2 * m["V"] * d + d)


def expert_rows(cfg: dict, local_tokens: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts' grouped matmuls in one step,
    forward and backward, for ``local_tokens`` routed rows per expert
    layer: three matmuls of d × fe per row; bytes the bfloat16 rows in and
    out and each held expert's weights, read in the forward and the
    backward, with their gradients written."""
    m = _dims(cfg)
    layers = m["L"] - m["Ld"]
    d, fe = m["d"], m["fe"]
    flops = 3 * layers * local_tokens * 2.0 * 3 * d * fe
    bytes_ = layers * (3 * local_tokens * (2 * d + 3 * fe) * BF16
                       + 3 * m["Eh"] * 3 * d * fe * BF16)
    return flops, float(bytes_)


def train_step(cfg: dict, batch: int, seq: int, local_tokens: float) -> float:
    """FLOPs of one train step: MLA, the dense and expert layers, the
    router, shared experts, the latent PQ term and the head, forward and
    backward; Adam over every leaf but the rotations; one GCD step on each
    layer's rotation."""
    m = _dims(cfg)
    d, H, r, L, Ld = m["d"], m["H"], m["r"], m["L"], m["Ld"]
    T = batch * seq
    keys = (seq + 1) / 2.0
    proj = 2.0 * (d * H * (m["dn"] + m["dr"]) + d * (r + m["dr"])
                  + r * H * (m["dn"] + m["dv"]) + H * m["dv"] * d)
    core = 2.0 * H * (m["dn"] + m["dr"] + m["dv"]) * keys
    latent = 2.0 * (r * r + r * m["K"])
    per_token = (L * (proj + core + latent) + Ld * 2.0 * 3 * d * m["f"]
                 + (L - Ld) * 2.0 * (d * m["E"] + 3 * d * m["fs"])
                 + 2.0 * d * m["V"])
    experts, _ = expert_rows(cfg, local_tokens)
    gcd = L * (2.0 * r ** 3 + 4.0 * r * r)
    return 3 * T * per_token + experts + 12.0 * params(cfg) + gcd
