"""PQ-compressed KV cache with a learned (GCD) rotation — the paper's
embedding-index layer transplanted onto LM attention (beyond-paper feature,
see DESIGN.md §4).

Keys/values are quantized **per head vector** (head_dim-dim) with a per-layer
rotation R ∈ SO(head_dim) and per-layer codebooks — each of keys and values
is a ``quant.PQ`` instance (viewed over the ``cb_k``/``cb_v`` param leaves),
exactly the T(X)=φ(XR)Rᵀ structure of the paper. Decode-time attention never
dequantizes the cache into dense form:

  * scores:  q·k̂ᵀ = Σ_d LUT[d, code_d] — ADC through the shared kernel
             family's grouped member (kernels/adc_batch.py; one (batch,
             kv-head) pair per group, GQA rep queries per group)
  * output:  Σ_s w_s·v̂_s = Σ_{d,k} H[d,k]·C_v[d,k]  with the weight histogram
             H[d,k] = Σ_{s: code_s,d = k} w_s   (scatter-add + tiny matmul)

Memory: head_dim·2 bytes → D bytes per vector (e.g. 128·2B → 16B at D=16,
a 16× cut) — this is what makes the 500k-context decode cells feasible.

Latent attention (MLA) caches one shared latent per token instead of
per-head K/V; ``latent_distortion`` is the training term for a PQ over that
latent (one rotation ``rot_c`` and one codebook per layer).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.kernels import ops as kops
from repro.kernels.common import use_kernels


class KVQuantConfig(NamedTuple):
    head_dim: int
    num_subspaces: int = 16
    num_codewords: int = 256

    @property
    def sub(self) -> int:
        return self.head_dim // self.num_subspaces

    @property
    def pq_cfg(self) -> quant.PQConfig:
        return quant.PQConfig(self.num_subspaces, self.num_codewords)


class KVQuantParams(NamedTuple):
    """Per-layer parameters (no leading layer axis; stack outside).

    Raw array leaves (models/transformer ParamSpecs and the optimizer's
    name-based manifold routing need a flat tree); ``quant_k``/``quant_v``
    wrap the codebooks in the Quantizer protocol on demand.
    """

    rot_k: jax.Array  # (hd, hd)
    rot_v: jax.Array  # (hd, hd)
    cb_k: jax.Array   # (D, K, sub)
    cb_v: jax.Array   # (D, K, sub)

    @property
    def quant_k(self) -> quant.PQ:
        return quant.PQ(self.cb_k)

    @property
    def quant_v(self) -> quant.PQ:
        return quant.PQ(self.cb_v)


def init(key: jax.Array, cfg: KVQuantConfig, dtype=jnp.float32) -> KVQuantParams:
    k1, k2 = jax.random.split(key)
    hd, D, K, sub = cfg.head_dim, cfg.num_subspaces, cfg.num_codewords, cfg.sub
    return KVQuantParams(
        rot_k=jnp.eye(hd, dtype=dtype),
        rot_v=jnp.eye(hd, dtype=dtype),
        cb_k=0.02 * jax.random.normal(k1, (D, K, sub), dtype=dtype),
        cb_v=0.02 * jax.random.normal(k2, (D, K, sub), dtype=dtype),
    )


def _flatten_heads(x: jax.Array) -> tuple[jax.Array, tuple]:
    """(..., hd) -> (prod(...), hd) plus the lead shape for unflattening."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def encode_kv(params: KVQuantParams, k: jax.Array, v: jax.Array):
    """Quantize key/value tensors (..., hd) -> codes (..., D) uint8/int32."""
    qk, qv = params.quant_k, params.quant_v
    kf, lead = _flatten_heads(k)
    vf, _ = _flatten_heads(v)
    ck = qk.encode(kf @ params.rot_k).astype(qk.code_dtype)
    cv = qv.encode(vf @ params.rot_v).astype(qv.code_dtype)
    return ck.reshape(*lead, qk.code_width), cv.reshape(*lead, qv.code_width)


def decode_k(params: KVQuantParams, codes: jax.Array) -> jax.Array:
    """Codes (..., D) -> dense keys (..., hd): k̂ = decode(c)·Rᵀ."""
    lead = codes.shape[:-1]
    flat = params.quant_k.decode(codes.reshape(-1, codes.shape[-1]))
    return (flat @ params.rot_k.T).reshape(*lead, params.rot_k.shape[0])


def decode_v(params: KVQuantParams, codes: jax.Array) -> jax.Array:
    lead = codes.shape[:-1]
    flat = params.quant_v.decode(codes.reshape(-1, codes.shape[-1]))
    return (flat @ params.rot_v.T).reshape(*lead, params.rot_v.shape[0])


def adc_scores_grouped(params: KVQuantParams, q: jax.Array, k_codes: jax.Array,
                       *, use_kernel: bool | None = None) -> jax.Array:
    """Grouped ADC scoring — the decode hot path.

    q (g, r, hd) queries vs k_codes (g, S, D): group g is one (batch,
    kv-head) pair, r its GQA query repetition. Builds one (r, D, K) LUT per
    group (LUT = adc_tables(qR)) and dispatches to the shared grouped kernel
    (kernels/adc_batch.py) or its scan-accumulated jnp oracle — codes are
    never broadcast over r, so the peak buffer stays O(g·r·S).
    Returns (g, r, S).
    """
    g, r, hd = q.shape
    lut = params.quant_k.adc_tables((q @ params.rot_k).reshape(g * r, hd))
    lut = lut.reshape(g, r, *lut.shape[1:])  # (g, r, D, K)
    return kops.adc_batch(lut, k_codes,
                          use_kernel=use_kernels(use_kernel))


def adc_scores(params: KVQuantParams, q: jax.Array, k_codes: jax.Array,
               *, use_kernel: bool | None = None) -> jax.Array:
    """q (..., hd) vs key codes (..., S, D) -> scores (..., S).

    ⟨q, k̂⟩ = ⟨qR, decode(c)⟩ = Σ_d LUT[d, c_d] with LUT = adc_tables(qR).
    Leading axes of q and k_codes must broadcast-match (e.g. (B, H) each);
    each joint lead element becomes one single-query group of the grouped
    scorer. Size-1 broadcast axes materialize a code copy here — the GQA
    decode path calls ``adc_scores_grouped`` directly to share one code set
    across the rep queries instead.
    """
    hd = q.shape[-1]
    S, D = k_codes.shape[-2:]
    lead = jnp.broadcast_shapes(q.shape[:-1], k_codes.shape[:-2])
    qb = jnp.broadcast_to(q, (*lead, hd)).reshape(-1, 1, hd)
    cb = jnp.broadcast_to(k_codes, (*lead, S, D)).reshape(-1, S, D)
    out = adc_scores_grouped(params, qb, cb, use_kernel=use_kernel)
    return out.reshape(*lead, S)


def weighted_value_sum(params: KVQuantParams, w: jax.Array,
                       v_codes: jax.Array) -> jax.Array:
    """Σ_s w[..., s] · v̂[..., s, :] without dequantizing the cache.

    H[..., d, k] = Σ_{s: code=k} w_s  (histogram), out = Σ_{d,k} H·C_v[d,k]
    concatenated over d.  w: (..., S), v_codes: (..., S, D) -> (..., hd).
    """
    D, K, sub = params.cb_v.shape
    S = w.shape[-1]
    lead = w.shape[:-1]
    # scatter-add the weights into (D, K) histograms. GQA repetition: the
    # rep axis of w shares one set of codes — vmap with codes held constant
    # instead of broadcasting them (a materialized int32 broadcast costs
    # rep × S × D × 4 bytes: ~5 GiB at the 500k-context decode shape).
    code_lead = v_codes.shape[:-2]
    rep_shape = lead[len(code_lead):]       # extra axes w has beyond codes
    wf = w.reshape(-1, *rep_shape, S).reshape(
        -1, int(np.prod(rep_shape, dtype=int)) if rep_shape else 1, S)
    cf = v_codes.astype(jnp.int32).reshape(-1, S, D)

    def one_hist(wb, cb):  # wb (R, S), cb (S, D) -> (R, D, K)
        def per_rep(wr):
            return jax.vmap(
                lambda col: jax.ops.segment_sum(wr, col, num_segments=K),
                in_axes=1,
            )(cb)
        return jax.vmap(per_rep)(wb)

    hist = jax.vmap(one_hist)(wf, cf).reshape(*lead, D, K)
    parts = jnp.einsum("...dk,dks->...ds", hist, params.cb_v)  # (..., D, sub)
    out = parts.reshape(*parts.shape[:-2], D * sub)
    return out @ params.rot_v.T  # rotate back out of the PQ basis


def adc_decode_attention(
    params: KVQuantParams,
    q: jax.Array,          # (B, H, hd) single-step query
    k_codes: jax.Array,    # (B, H_kv, S, D)
    v_codes: jax.Array,    # (B, H_kv, S, D)
    length_mask: jax.Array | None = None,  # (B, S) bool, True = valid
    scale: float | None = None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """One decode step of attention entirely in the compressed domain.

    Supports GQA: H query heads read from H_kv cache heads (H % H_kv == 0).
    Returns (B, H, hd).
    """
    B, H, hd = q.shape
    H_kv, S, D = k_codes.shape[1:]
    rep = H // H_kv
    scale = (hd ** -0.5) if scale is None else scale
    # grouped scorer: one (batch, kv-head) pair per group, rep queries each —
    # codes are NOT broadcast over the rep axis.
    qg = q.reshape(B * H_kv, rep, hd)
    scores = adc_scores_grouped(
        params, qg, k_codes.reshape(B * H_kv, S, D), use_kernel=use_kernel
    ).reshape(B, H_kv, rep, S) * scale
    if length_mask is not None:
        scores = jnp.where(length_mask[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    # v_codes passed WITHOUT the rep axis: the histogram vmap shares one set
    # of codes across the rep heads (no broadcast materialization).
    out = weighted_value_sum(params, w, v_codes)  # (B, H_kv, rep, hd)
    return out.reshape(B, H, hd)


def kv_distortion(params: KVQuantParams, k: jax.Array, v: jax.Array) -> jax.Array:
    """Distortion loss on sampled K/V vectors — the Eq.(1) second term for the
    KV index; drives codebook SGD training and supplies ∇_R for GCD."""
    kf, _ = _flatten_heads(k)
    vf, _ = _flatten_heads(v)
    dk = params.quant_k.distortion(kf @ params.rot_k)
    dv = params.quant_v.distortion(vf @ params.rot_v)
    return dk + dv


def latent_distortion(rot_c: jax.Array, cb_c: jax.Array,
                      c: jax.Array) -> jax.Array:
    """The Eq. (1) second term on a latent-attention stream (MLA): the PQ
    index layer over the shared latent ``c`` (..., r) that every head's K/V
    is up-projected from — one rotation ``rot_c`` ∈ SO(r) and one codebook
    ``cb_c`` (D, K, r/D) per layer, (1/m)‖cR − φ(cR)‖² over the m vectors.
    Drives codebook SGD and supplies ∇_rot_c for GCD."""
    z = c.reshape(-1, c.shape[-1]).astype(jnp.float32) @ rot_c
    pq = quant.PQ(cb_c)
    # nearest codewords in row chunks: the (rows, D, K) distance table of
    # all 16k latents of a train step would take 1 GiB at D=64, K=256
    m, chunk = z.shape[0], LATENT_ASSIGN_ROWS
    zs = jax.lax.stop_gradient(z)
    if m > chunk and m % chunk == 0:
        codes = jax.lax.map(pq.encode, zs.reshape(m // chunk, chunk, -1))
        codes = codes.reshape(m, -1)
    else:
        codes = pq.encode(zs)
    return pq.distortion(z, codes)


#: rows per chunk of the latent term's nearest-codeword search
LATENT_ASSIGN_ROWS = 2048
