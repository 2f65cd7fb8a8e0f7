"""Glue between an LM configuration file and the program: the file as the
program's ``TransformerConfig`` (with its expert share), its registration
with the trainer's arch registry, and the trainer's step program compiled
again from abstract shapes (what the scope readers parse).

A configuration file keeps the keys of the model's public config.json
(``n_routed_experts`` holds the experts held here, the published count
under ``published``); ``deployment`` says how the layer is shared and
``assumed`` what the published config does not give.
"""
from __future__ import annotations


def lm_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    import jax.numpy as jnp

    from repro.core.kv_quant import KVQuantConfig
    from repro.models.moe import MoEConfig
    from repro.models.transformer import MLAConfig, TransformerConfig

    a, dep, rs = cfg["assumed"], cfg["deployment"], cfg["rope_scaling"]
    if cfg["routed_scaling_factor"] != 1:
        raise ValueError("the expert layer scales no gates: "
                         "routed_scaling_factor must be 1")
    pq = a["latent_pq"]
    fe = cfg["moe_intermediate_size"]
    return TransformerConfig(
        name=cfg["name"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], activation=cfg["hidden_act"],
        use_glu=True, norm="rmsnorm", rope_theta=float(cfg["rope_theta"]),
        moe=MoEConfig(
            num_experts=dep["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            experts_held=cfg["n_routed_experts"],
            first_held=dep["first_held_expert"], d_ff=fe,
            shared_ff=cfg["n_shared_experts"] * fe,
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            seq_aux=bool(cfg["seq_aux"]), aux_alpha=a["aux_loss_alpha"]),
        first_dense_layers=cfg["first_k_dense_replace"],
        mla=MLAConfig(
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], rope_factor=float(rs["factor"]),
            original_max_pos=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        kv_quant=KVQuantConfig(head_dim=cfg["kv_lora_rank"],
                               num_subspaces=pq["num_subspaces"],
                               num_codewords=pq["num_codewords"]),
        train_kv_quant=True, train_seq_len=a["seq_len"],
        q_chunk=a["q_chunk"], xent_chunk=a["xent_chunk"],
        dtype=jnp.dtype(a["dtype"]), param_dtype=jnp.dtype(a["param_dtype"]))


def register_arch(model) -> str:
    """Register an LM configuration with the trainer's arch registry and
    return its id."""
    from repro import configs
    from repro.configs import base

    arch_id = "bench-" + model.name
    configs.REGISTRY[arch_id] = base.ArchSpec(
        arch_id=arch_id, family="lm", make_config=lambda: model,
        make_smoke=lambda: model, shapes={})
    return arch_id


#: the trainer's step program as the trace names it (``train_step``, jitted)
MODULE = "jit_train_step"


def step_program(run):
    """The parsed HLO of the trainer's step for this run's configuration,
    built as ``launch/train.py`` builds it and compiled from abstract
    shapes (the persistent compile cache serves it); kept in
    ``run.values``. None when the program has no ``build_step``."""
    if "step_program" in run.values:
        return run.values["step_program"]
    import jax

    from bench import scopes
    from repro.launch import train as train_lib
    from repro.training import train_state as ts

    program = None
    build = getattr(train_lib, "build_step", None)
    if build is not None:
        wl = run.workload
        model = lm_config(run.config)
        step_fn, ocfg = build(model, "lm", wl["schedule_steps"],
                              "gcd_greedy", emit_deltas=True)

        def init(key):
            params = train_lib.init_model(key, model, "lm")
            return ts.init_state(jax.random.fold_in(key, 1), params, ocfg)

        state = jax.eval_shape(init, jax.random.PRNGKey(0))
        tok = jax.ShapeDtypeStruct((wl["batch"], model.train_seq_len),
                                   "int32")
        program = scopes.parse(
            step_fn.lower(state, tok, tok).compile().as_text())
    run.values["step_program"] = program
    return program


def scope_ms(run, reduced, scope: str):
    """Device ms per traced step of the outermost ops under ``scope`` in
    the trainer's step program (``bench/scopes.py``); None when the trace
    or the program has nothing to read."""
    from bench import scopes

    steps = run.values.get("traced_units", run.values.get("steps"))
    if reduced is None or not steps or not any(
            n.startswith(MODULE + "/") for n in reduced.op_seconds):
        return None
    program = step_program(run)
    if (program is None or program.module != MODULE
            or not scopes.ran_here(program, reduced.op_seconds)):
        return None
    s = scopes.device_seconds(program, scopes.outermost(program, scope),
                              reduced.op_seconds)
    return None if s is None else 1e3 * s / steps
