"""The ``train_lm`` driver end to end at a CPU size (the DeepSeek-V2-Lite
cell with toy widths): a sound run comes out correct, each fault planted
in the timed path and the reference a precision below the configuration's
come out not correct; and the cell's per-layer readers return numbers on
a synthetic trace of the trainer's own step program."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import jax.numpy as jnp
import pytest

from bench import harness, lm, peaks, scopes
from bench import lm_faults

CELL = "dsv2lite-train-8k"

#: toy widths, in the configuration file's keys
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "num_experts_per_tok": 3,
        "num_hidden_layers": 3, "vocab_size": 256}


def cell() -> tuple[dict, dict]:
    """(workload, configuration) of the cell, cut to toy widths."""
    entry = {w["name"]: w for w in harness.benchmark()["workloads"]}[CELL]
    wl = harness.workload(CELL)
    cfg = harness.config(entry["config"])
    cfg.update(TINY, name="tiny-" + cfg["name"])
    cfg["deployment"] = dict(cfg["deployment"], router_experts=8)
    cfg["assumed"] = dict(
        cfg["assumed"], seq_len=32, dtype="float32", q_chunk=16,
        xent_chunk=32, reference_q_chunk=16,
        latent_pq={"num_subspaces": 4, "num_codewords": 16,
                   "distortion_weight": 0.1})
    wl.update(warm_steps=3, schedule_steps=1000)
    return wl, cfg


def run(*, seed: int = 2**33 + 7, seconds: float = 0.3, trace=False):
    import jax

    wl, cfg = cell()
    r = harness.Run(name=CELL, workload=wl, config=cfg, seed=seed,
                    seconds=seconds, trace=trace,
                    t_start=time.perf_counter(), devices=jax.devices(),
                    peaks=peaks.TABLE["TPU v5 lite"])
    r.watch_compiles()
    return r, harness.driver("train_lm").run(r)


def correct(result) -> bool:
    return bool(result.correct and harness.checks_pass(result.checks))


def test_sound_run_is_correct():
    r, res = run()
    assert correct(res), res.checks
    assert res.checks["dropped"]["value"] == 0.0
    assert res.info["compiles_in_window"] == 0
    assert res.attempted > 0 and res.end_to_end["train_step_ms"] > 0
    assert res.info["losses"] == pytest.approx(res.info["ref_losses"],
                                               rel=1e-5)
    assert "loss_gap" not in res.checks and res.info["loss_gap"] < 1e-3
    for name in ("grad_gap", "change_gap"):
        assert res.checks[name]["value"] < 1e-3, name
    assert 0 < res.info["local_tokens"] <= 2 * 32 * 3
    assert r.values["step_flops"] > r.values["expert_flops"] > 0


@pytest.mark.parametrize("fault", sorted(lm_faults.PLANTED))
def test_planted_fault_is_caught(fault):
    import jax

    # traced layers are cached by function (jax.checkpoint): the patched
    # module functions are seen only by a fresh trace, and later tests
    # must not see them
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        lm_faults.PLANTED[fault](mp)
        _, res = run()
    jax.clear_caches()
    assert not correct(res), (fault, res.checks)


def test_control_precision_is_caught():
    """The reference a precision below the configuration's (float8
    operands), judged by the run's own comparison against float32."""
    from bench.reference import deepseek_v2
    from bench.traffic import train_lm
    from repro.data import synthetic

    wl, cfg = cell()
    import jax

    key = jax.random.PRNGKey(3)
    batches = [tuple(jnp.asarray(a) for a in synthetic.lm_batch(
        jax.random.fold_in(key, i), 2, 32, 256)) for i in range(2)]
    want = deepseek_v2.first_steps(3, cfg, wl["optimizer"], batches)
    got = deepseek_v2.first_steps(3, cfg, wl["optimizer"], batches,
                                  dtype=jnp.float8_e4m3fn)
    g = train_lm.gaps(got, want)
    assert any(g[n] > wl["limits"][n] for n in ("grad_gap", "change_gap")), g


@pytest.fixture(scope="module")
def traced():
    """A tiny run with its step program, and a synthetic trace: every
    instruction of the program timed 1 ms."""
    r, res = run()
    r.values["traced_units"] = 4
    r.values["traced_s"] = 0.5
    program = lm.step_program(r)
    op_s = {f"{program.module}/{n}": 0.001 for n in program.op_names}
    return r, program, NS(op_seconds=op_s, chips=1, busy_s=0.4,
                          window_s=0.5)


@pytest.mark.parametrize("metric,scope", [
    ("mla.attn_ms", "mla"), ("latent_gcd.update_ms", "gcd"),
    ("moe.experts_roofline", "moe.experts")])
def test_scope_readers_read_their_scope(traced, metric, scope):
    r, program, red = traced
    top = scopes.outermost(program, scope)
    assert top
    got = harness.layer_reader(metric).read(r, red)
    ms = 1e3 * 0.001 * len(top) / 4
    if metric == "moe.experts_roofline":
        v, pk = r.values, r.peaks
        least = max(v["expert_flops"] / pk.bf16_flops,
                    v["expert_bytes"] / pk.hbm_bytes_per_s)
        assert got == pytest.approx(100 * least / (ms / 1e3))
    else:
        assert got == pytest.approx(ms)


def test_whole_step_readers(traced):
    r, _, red = traced
    mfu = harness.layer_reader("lm.mfu").read(r, red)
    assert mfu == pytest.approx(100 * r.values["step_flops"] * 4 / 0.5
                                / r.peaks.bf16_flops)
    idle = harness.layer_reader("device.idle.train").read(r, red)
    assert idle == pytest.approx(20.0)


def test_readers_read_nothing_without_the_program(traced):
    r, _, red = traced
    other = NS(op_seconds={"jit_other/fusion.1": 1.0}, chips=1,
               busy_s=0.1, window_s=1.0)
    for metric in ("mla.attn_ms", "latent_gcd.update_ms",
                   "moe.experts_roofline"):
        reader = harness.layer_reader(metric)
        assert reader.read(r, None) is None
        assert reader.read(r, other) is None


def test_control_readings_at_a_cpu_size():
    """``bench/control_lm.py`` on the toy cell: the sound run passes, the
    planted fault and the control do not."""
    import jax

    from bench import control_lm
    from bench import lm_faults

    wl, cfg = cell()
    out = {r["reading"]: r for r in control_lm.readings(
        CELL, wl, cfg, 2**33 + 9, 0.3, jax.devices(),
        peaks.TABLE["TPU v5 lite"],
        faults={"unrotated_latent": lm_faults.unrotated_latent})}
    assert list(out) == ["program", "unrotated_latent", "control"]
    assert out["program"]["correct"] and out["program"]["dropped"] == 0.0
    assert not out["unrotated_latent"]["correct"]
    assert any(out["control"][n] > wl["limits"][n]
               for n in ("grad_gap", "change_gap"))
