"""Every Pallas kernel compiles for a TPU v5e at the paper's widths, and
the expert layer's grouped matmul at DeepSeek-V2-Lite's.

Interpret mode cannot see what the TPU compiler refuses: blocks that break
the (8, 128) tiling rule, tiles that overflow the scoped VMEM limit, shape
casts Mosaic cannot lower. These tests hand each kernel's shapes to the TPU
compiler for a described (not attached) v5e chip and check that the
compiled program holds the kernel as a ``tpu_custom_call``.

Widths follow ``configs/paper_twotower.py``: embedding n = 512, a 64×256
PQ index (64 code columns, 256 codewords), scan tile 128, a corpus of 2^20
rows. The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time, and every test worker
imports this file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (adc_batch, adc_lookup, embedding_bag, gcd_score,
                           givens_rotate, ivf_adc, lut_build, pq_assign)

N_DIM = 512            # embedding width
DP, K = 64, 256        # code columns × codewords
ROWS = 1 << 20         # corpus rows
B = 64                 # query batch
# selected (query, block) pairs of one IVF scan: 256 queries × nprobe 32 ×
# 16 blocks per list, a schedule longer than SMEM holds in one call
STEPS = 131072
F32, U8, I8, I32 = jnp.float32, jnp.uint8, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


# name -> (kernel call with interpret=False, its argument shapes)
KERNELS = {
    "ivf_adc": (
        lambda lut, c, bi, bq: ivf_adc.ivf_adc(lut, c, bi, bq,
                                               interpret=False),
        [((B, DP, K), F32), ((ROWS, DP), U8), ((STEPS,), I32),
         ((STEPS,), I32)]),
    "ivf_adc_int8": (
        lambda lut, c, bi, bq, sc: ivf_adc.ivf_adc(lut, c, bi, bq, sc,
                                                   interpret=False),
        [((B, DP, K), I8), ((ROWS, DP), U8), ((STEPS,), I32),
         ((STEPS,), I32), ((B, DP, 2), F32)]),
    "ivf_adc_masked": (
        lambda lut, c, bi, bq, ids: ivf_adc.ivf_adc(lut, c, bi, bq, None, ids,
                                                    interpret=False),
        [((B, DP, K), F32), ((ROWS, DP), U8), ((STEPS,), I32),
         ((STEPS,), I32), ((ROWS,), I32)]),
    # the serving call: ids mask and the sentinel block's steps skipped, a
    # scalar compare on the prefetched schedule steering ``pl.when``
    "ivf_adc_masked_holes": (
        lambda lut, c, bi, bq, ids: ivf_adc.ivf_adc(
            lut, c, bi, bq, None, ids, hole_block=ROWS // 128 - 1,
            interpret=False),
        [((B, DP, K), F32), ((ROWS, DP), U8), ((STEPS,), I32),
         ((STEPS,), I32), ((ROWS,), I32)]),
    "adc_lookup": (
        lambda lut, c: adc_lookup.adc_lookup(lut, c, interpret=False),
        [((B, DP, K), F32), ((ROWS, DP), U8)]),
    "adc_lookup_masked": (
        lambda lut, c, ids: adc_lookup.adc_lookup(lut, c, None, ids,
                                                  interpret=False),
        [((B, DP, K), F32), ((ROWS, DP), U8), ((ROWS,), I32)]),
    # a depth-2 residual quantizer doubles the code columns: the scan step
    # must shrink its row block to keep the one-hot tile inside VMEM
    "adc_lookup_rq2": (
        lambda lut, c: adc_lookup.adc_lookup(lut, c, interpret=False),
        [((B, 2 * DP, K), F32), ((ROWS, 2 * DP), U8)]),
    "fused_lut": (
        lambda q, qd, cb, cm: lut_build.fused_lut(q, qd, cb, cm,
                                                  interpret=False),
        [((B, N_DIM), F32), ((N_DIM, N_DIM), F32),
         ((DP, K, N_DIM // DP), F32), ((DP, DP), F32)]),
    "gcd_score": (
        lambda g, r: gcd_score.gcd_score(g, r, interpret=False),
        [((N_DIM, N_DIM), F32), ((N_DIM, N_DIM), F32)]),
    "givens_rotate": (
        lambda xe, xo, c, s: givens_rotate.givens_rotate(xe, xo, c, s,
                                                         interpret=False),
        [((N_DIM, N_DIM // 2), F32), ((N_DIM, N_DIM // 2), F32),
         ((N_DIM // 2,), F32), ((N_DIM // 2,), F32)]),
    # off the serving path: PQ encode, recsys bag lookup, KV-cache decode
    "pq_assign": (
        lambda x, cb: pq_assign.pq_assign(x, cb, interpret=False),
        [((65536, N_DIM), F32), ((DP, K, N_DIM // DP), F32)]),
    "embedding_bag": (
        lambda t, i, bg: embedding_bag.embedding_bag(t, i, bg, 4096,
                                                     interpret=False),
        [((1_541_673, N_DIM), F32), ((65536,), I32), ((65536,), I32)]),
    "adc_batch": (
        lambda lut, c: adc_batch.adc_batch(lut, c, interpret=False),
        [((64, 4, 16, 256), F32), ((64, 4096, 16), U8)]),
    # the dropless expert layer's grouped matmul (XLA's own TPU kernel) at
    # DeepSeek-V2-Lite's widths: 2 × 8,192 tokens × top-6 routed rows, the
    # 8 experts one chip holds, 2,048 → 1,408
    "moe_grouped_matmul": (
        jax.lax.ragged_dot,
        [((16384 * 6, 2048), jnp.bfloat16), ((8, 2048, 1408), jnp.bfloat16),
         ((8,), I32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
