"""DeepSeek-V2-Lite on the CPU at the smoke size, against the plain float32
reference (``bench/reference/deepseek_v2.py``): MLA, the whole model's loss
and gradients, the expert-share identity, dropless routing under a forced
imbalance, and the stacked latent rotations under GCD-G."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import lm
from bench.reference import deepseek_v2 as ref
from repro import configs
from repro.models import layers, moe as moe_lib
from repro.models import transformer as tfm

#: the smoke size, in the configuration file's keys
SMOKE = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "n_routed_experts": 4, "num_experts_per_tok": 3,
         "num_hidden_layers": 3, "vocab_size": 256}


def _bench_cfg() -> dict:
    with open("bench/configs/deepseek-v2-lite-ep8.json") as f:
        return json.load(f)


def smoke_cfg(**over) -> dict:
    cfg = _bench_cfg()
    cfg.update(SMOKE, name="deepseek-v2-lite-smoke")
    cfg["deployment"] = dict(cfg["deployment"], router_experts=8)
    cfg["assumed"] = dict(
        cfg["assumed"], seq_len=32, dtype="float32", q_chunk=16,
        xent_chunk=32, reference_q_chunk=16,
        latent_pq={"num_subspaces": 4, "num_codewords": 16,
                   "distortion_weight": 0.1})
    cfg.update(over)
    return cfg


def _named(tree) -> dict:
    return {"/".join(str(getattr(p, "key", p)) for p in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_configs_are_the_bench_files():
    """The registry's configurations are the ones the benchmark file and
    the smoke size describe; the chip's share holds ~638M parameters."""
    arch = configs.get("deepseek-v2-lite")
    assert lm.lm_config(_bench_cfg()) == arch.make_config()
    assert lm.lm_config(smoke_cfg()) == arch.make_smoke()
    assert 630e6 < tfm.num_params(arch.make_config()) < 645e6
    assert "deepseek-v2-lite" not in configs.ASSIGNED


def test_yarn_matches_reference():
    s = ref.sizes(_bench_cfg())
    inv, m, scale = ref.yarn(s)
    mla = configs.get("deepseek-v2-lite").make_config().mla
    got = layers.yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    assert mla.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert scale == pytest.approx(mla.softmax_scale)
    assert m == pytest.approx(mla.rope_mscale) == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [0, 5])
def test_mla_forward_matches_reference(seed):
    cfg = smoke_cfg()
    model, s = lm.lm_config(cfg), ref.sizes(cfg)
    key = jax.random.PRNGKey(seed)
    rp = ref.init(key, s)
    params = tfm.init_params(key, model)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, 32, 64))
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    with jax.default_matmul_precision("highest"):
        h = layers.rms_norm(x, lp["ln1"])
        q, k, v, c = tfm._mla_qkv(lp, h, model, pos)
        att = layers.blockwise_attention(q, k, v, q_chunk=16,
                                         scale=model.mla.softmax_scale)
        out = att.reshape(2, 32, -1) @ lp["attn"]["wo"]
    a = {n: rp["layers/" + ("" if n.startswith("ln") else "attn/") + n][0]
         for n in ref.PROJ}
    rq, rk, rv, rc = ref.proj(a, x, s)
    ro = ref.attend(rq, rk, rv, 0, s).reshape(2, 32, -1) @ \
        rp["layers/attn/wo"][0]
    for got, want in ((q, rq), (k, rk), (v, rv), (c, rc), (out, ro)):
        assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("seed", [0, 11])
def test_smoke_loss_and_grads_match_reference(seed):
    cfg = smoke_cfg()
    model, s = lm.lm_config(cfg), ref.sizes(cfg)
    key = jax.random.PRNGKey(seed)
    params = tfm.init_params(key, model)
    rp = ref.init(key, s)
    tok = jax.random.randint(jax.random.fold_in(key, 1), (2, 32), 0, 256)
    lab = jax.random.randint(jax.random.fold_in(key, 2), (2, 32), 0, 256)
    with jax.default_matmul_precision("highest"):
        (loss, st), g = jax.value_and_grad(
            lambda p: tfm.forward_train_stats(p, tok, lab, model),
            has_aux=True)(params)
    rloss, rg = ref.loss_and_grads(rp, tok, lab, s)
    assert float(loss) == pytest.approx(rloss, rel=1e-5)
    assert float(st["moe.dropped"]) == 0.0
    g = _named(g)
    assert sorted(g) == sorted(rg)
    for name, want in rg.items():
        assert _rel(g[name], want) < 1e-4, name


def _moe_inputs(E, Eh, k, d=32, fe=16, T=48, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (T, d))
    router = jax.random.normal(ks[1], (d, E)) / math.sqrt(d)
    w = {n: jax.random.normal(kk, sh) / math.sqrt(sh[-2]) for n, kk, sh in (
        ("wi", ks[2], (E, d, fe)), ("wg", ks[3], (E, d, fe)),
        ("wo", ks[4], (E, fe, d)), ("shared_wi", ks[5], (d, 2 * fe)),
        ("shared_wg", ks[6], (d, 2 * fe)), ("shared_wo", ks[7], (2 * fe, d)))}
    s = dict(d=d, E=E, Eh=E, first=0, k=k, norm_topk=False, scale=1.0,
             eps=1e-6)
    return x, router, w, s


def _ref_layer(x, router, w, s, held=None, first=0, shared=True):
    """The reference's feed-forward half on tokens x (T, d) with the
    attention branch zero, minus its residual: the expert layer alone on
    RMSNorm(x) (unit norm weights)."""
    T, d = x.shape
    sl = slice(None) if held is None else slice(first, first + held)
    a = {"wo": jnp.zeros((d, d)), "ln2": jnp.ones((d,)),
         "ffn/router": router, "ffn/wi": w["wi"][sl], "ffn/wg": w["wg"][sl],
         "ffn/wo": w["wo"][sl],
         "ffn/shared_wi": w["shared_wi"] * (1.0 if shared else 0.0),
         "ffn/shared_wg": w["shared_wg"], "ffn/shared_wo": w["shared_wo"]}
    s = dict(s, Eh=held or s["E"], first=first)
    y, _ = ref.ffn_half(a, x[None], jnp.zeros((1, T, d)), s, True)
    return y[0] - x


def _program_layer(x, router, w, cfg, held, first, shared=True):
    h = layers.rms_norm(x, jnp.ones((x.shape[1],)))
    sl = slice(first, first + held)
    out, _, st = moe_lib.moe_block(
        h, router, w["wi"][sl], w["wg"][sl], w["wo"][sl],
        cfg._replace(experts_held=held, first_held=first), activation="silu",
        rule_table={}, seq_len=x.shape[0],
        shared=(w["shared_wi"], w["shared_wg"], w["shared_wo"])
        if shared else None)
    return out, st


def test_expert_share_identity():
    """8 shares of 8 experts each, the shared experts counted once, sum to
    the uncut reference layer of 64 experts (and to the program's)."""
    E, k = 64, 6
    x, router, w, s = _moe_inputs(E, E, k, T=64)
    cfg = moe_lib.MoEConfig(num_experts=E, top_k=k, norm_topk_prob=False)
    with jax.default_matmul_precision("highest"):
        whole = _ref_layer(x, router, w, s)
        parts = [_program_layer(x, router, w, cfg, 8, 8 * i, shared=i == 0)
                 for i in range(8)]
        uncut, _ = _program_layer(x, router, w, cfg, E, 0)
        ref_parts = [_ref_layer(x, router, w, s, held=8, first=8 * i,
                                shared=i == 0) for i in range(8)]
    total = sum(p for p, _ in parts)
    assert _rel(total, whole) < 1e-5
    assert _rel(uncut, whole) < 1e-5
    assert _rel(sum(ref_parts), whole) < 1e-5
    # every (token, choice) is computed by exactly one share
    assert sum(float(st["local_tokens"]) for _, st in parts) == 64 * k
    for i in range(8):
        assert _rel(parts[i][0], ref_parts[i]) < 1e-5


@pytest.mark.parametrize("norm_topk", [False, True])
def test_dropless_under_forced_imbalance(norm_topk):
    """Every token routed to expert 0 first: its group takes all T rows,
    none is dropped, and the output matches the reference (a capacity of
    T·k/E per expert would have dropped all but T·k/E of them)."""
    E, k, T = 8, 2, 40
    x, router, w, s = _moe_inputs(E, E, k, T=T, seed=3)
    x = x + 3.0                                 # a shared direction
    router = router.at[:, 0].set(5.0)           # aligned with it
    s = dict(s, norm_topk=norm_topk)
    cfg = moe_lib.MoEConfig(num_experts=E, top_k=k,
                            norm_topk_prob=norm_topk)
    with jax.default_matmul_precision("highest"):
        out, st = _program_layer(x, router, w, cfg, E, 0)
        want = _ref_layer(x, router, w, s)
    h = layers.rms_norm(x, jnp.ones((x.shape[1],)))
    ids = moe_lib.route(h, router, cfg, T)[1]
    assert bool(jnp.all(ids[:, 0] == 0))
    assert float(st["max_expert_load"]) == T
    assert float(st["dropped"]) == 0.0
    assert float(st["local_tokens"]) == T * k
    assert _rel(out, want) < 1e-5


def test_stacked_rot_c_stay_orthogonal_under_gcd():
    """The trainer's normal path on the smoke configuration: GCD-G moves
    each layer's latent rotation and keeps it in SO(r); the step reports
    the expert-layer counters and the trainer records them."""
    from repro import obs
    from repro.launch import train as train_lib

    with obs.override(True):
        state, losses = train_lib.train("deepseek-v2-lite", 3, 2, None,
                                        seed=4, rotation="gcd_greedy")
        gauges = obs.default_registry().snapshot()["gauges"]
    obs.default_registry().reset()
    assert gauges["moe.dropped"] == 0.0
    assert 0 < gauges["moe.local_tokens"] <= 2 * 32 * 3
    assert gauges["moe.max_expert_load"] > 0
    rot = np.asarray(state.params["kvq"]["rot_c"], np.float64)
    assert rot.shape == (3, 32, 32)
    eye = np.eye(32)
    for R in rot:
        assert np.max(np.abs(R.T @ R - eye)) < 1e-5
        assert np.max(np.abs(R - eye)) > 0
    assert all(np.isfinite(losses))
    step_fn, ocfg = train_lib.build_step(
        configs.get("deepseek-v2-lite").make_smoke(), "lm", 3, "gcd_greedy",
        emit_deltas=False)
    tok = jnp.zeros((2, 32), jnp.int32)
    _, metrics = step_fn(state, tok, tok)
    assert float(metrics["moe.dropped"]) == 0.0
    assert float(metrics["moe.local_tokens"]) > 0


def test_lm_batch_length_comes_from_the_config():
    from repro.launch import train as train_lib

    for arch_id, seq in (("deepseek-v2-lite", 32), ("olmo-1b", 128)):
        cfg = configs.get(arch_id).make_smoke()
        tok, lab = train_lib.make_batch_fn(cfg, "lm", 2)(
            jax.random.PRNGKey(0))
        assert tok.shape == lab.shape == (2, seq)
