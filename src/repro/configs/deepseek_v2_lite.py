"""deepseek-v2-lite [moe, mla]: 27L d_model=2048 16H, MLA (kv_lora_rank 512,
qk_nope 128, qk_rope 64, v 128, YaRN ×40), dense layer 0 (d_ff 10944) then
26 MoE layers of 64 routed experts (width 1408, top-6 softmax, no top-k
renormalisation) + 2 shared, vocab 102400
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].

``make_config`` is one chip's share of the 8-way expert-parallel
deployment (bench/configs/deepseek-v2-lite-ep8.json): every width as
published; eight chips share each layer, so this chip holds 8 of the 64
experts of each MoE layer (the router keeps its 64 outputs and top-6) and
an eighth of the vocabulary; 6 layers (the dense one and 5 MoE) stand for
the pipeline stage it would run. ``make_published`` is the whole model.
Activations bfloat16, parameters and optimizer state float32.
"""
import jax.numpy as jnp

from repro.configs import base
from repro.core.kv_quant import KVQuantConfig
from repro.models.moe import MoEConfig
from repro.models.transformer import MLAConfig, TransformerConfig

#: the published model's rope_scaling (YaRN)
_YARN = dict(rope_factor=40.0, original_max_pos=4096, beta_fast=32.0,
             beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def make_published() -> TransformerConfig:
    return TransformerConfig(
        name="deepseek-v2-lite", num_layers=27, d_model=2048, num_heads=16,
        num_kv_heads=16, head_dim=128, d_ff=10944, vocab_size=102400,
        activation="silu", use_glu=True, norm="rmsnorm", rope_theta=10000.0,
        moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, shared_ff=2 * 1408,
                      norm_topk_prob=False, seq_aux=True,
                      aux_alpha=0.001),
        first_dense_layers=1,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128, **_YARN),
        # the paper's index layer on the latent: width 512, 64 × 256 PQ
        kv_quant=KVQuantConfig(head_dim=512, num_subspaces=64,
                               num_codewords=256),
        train_kv_quant=True, train_seq_len=8192, q_chunk=512,
        xent_chunk=8192, dtype=jnp.bfloat16, param_dtype=jnp.float32,
    )


def make_config() -> TransformerConfig:
    full = make_published()
    return full._replace(
        name="deepseek-v2-lite-ep8", num_layers=6, vocab_size=12800,
        moe=full.moe._replace(experts_held=8))


def make_smoke() -> TransformerConfig:
    return TransformerConfig(
        name="deepseek-v2-lite-smoke", num_layers=3, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=256,
        activation="silu", use_glu=True, norm="rmsnorm",
        moe=MoEConfig(num_experts=8, top_k=3, experts_held=4, d_ff=32,
                      shared_ff=64, norm_topk_prob=False, seq_aux=True,
                      aux_alpha=0.001),
        first_dense_layers=1,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16, **_YARN),
        kv_quant=KVQuantConfig(head_dim=32, num_subspaces=4,
                               num_codewords=16),
        train_kv_quant=True, train_seq_len=32, q_chunk=16, xent_chunk=32,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )


ARCH = base.ArchSpec(
    arch_id="deepseek-v2-lite", family="lm", make_config=make_config,
    make_smoke=make_smoke,
    shapes={"train_8k": base.Shape("train", {"seq_len": 8192,
                                             "global_batch": 2})},
    notes="MLA with a GCD-rotated 64×256 PQ latent; dropless top-6 over 64 "
          "experts, 8 held per chip (8-way expert parallelism).",
)
