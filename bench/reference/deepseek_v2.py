"""Plain reference of the DeepSeek-V2-Lite trainer's first steps, at one
chip's expert share: the model of arXiv 2405.04434 §2.1–2.2 as its public
config.json sets it, the PQ index layer's distortion term (the paper's
Eq. 1 second term, arXiv 2203.05082) on each layer's latent, AdamW on every
leaf but the latent rotations, and one greedy Givens-coordinate-descent
step (GCD-G, Algorithm 2 of 2203.05082) on each layer's rotation.

Written from the papers and the configuration alone, in straightforward
``jax.numpy`` at float32 under ``precision="highest"``; it imports nothing
of the program and takes nothing the program made. No kernels, no scan, no
checkpointing in the model: the forward is layer by layer, attention a
plain causal softmax over each query chunk, each held expert a dense
matmul over every token weighted by its gate (zero where the token did not
choose it). To fit one chip the gradients are taken in blocks — per layer
(projections, each query chunk of attention, the feed-forward half, the
latent term) with the layer's input kept from the forward — and Adam's
moments live on the host.

The model, per layer l (RMSNorm eps from the config):

* MLA without a query LoRA: q = h·W_Q split per head into q_nope (128)
  and q_rope (64); [c, k_R] = h·W_DKV_KR; c = RMSNorm(c) (the 512-wide
  latent); [k_nope, v] = c·W_UKV per head; RoPE with YaRN frequencies
  (factor 40, original 4,096, β_fast 32, β_slow 1) on q_rope and on the
  one k_R shared by all heads; scores (q·k)·192^-0.5·m², m = 0.1·0.707·
  ln 40 + 1; out = softmax·v, then ·W_O.
* Layer 0 a dense SiLU-GLU of width 10,944; the others MoE: router
  softmax over all 64 experts, top-6, gates not renormalised
  (``norm_topk_prob`` false) and scaled by ``routed_scaling_factor``; the
  held experts (``n_routed_experts`` of them here, from
  ``deployment.first_held_expert``) each a SiLU-GLU of width 1,408; the
  experts held elsewhere add nothing; 2 shared experts as one GLU of width
  2,816 on every token.
* Loss: mean next-token cross-entropy over the vocabulary slice
  + α·Σ_l (64/6)·mean over sequences of Σ_i f_i·P_i (``seq_aux``)
  + (w / L)·Σ_l (1/m)‖c R_l − φ_l(c R_l)‖² over the m tokens' latents.

Departures, noted:

* only the latent c is quantized; the RoPE key k_R stays dense;
* document boundaries are not masked (one causal sequence per row);
* the balance loss's coefficient α and the distortion weight w are not in
  the published config: they are the configuration's ``assumed``
  ``aux_loss_alpha`` and ``latent_pq.distortion_weight``;
* RoPE rotates the two halves of q_rope/k_R (x1, x2) as pairs; DeepSeek's
  code first de-interleaves them, a fixed permutation of W_Q's and
  W_DKV_KR's rope columns that random weights do not see.

Parameters are drawn from the seed with the same key schedule the trainer
uses (leaves in nested sorted-name order, one key each, normal × 1/√fan_in
unless noted), so both start from the same point. ``dtype`` other than
float32 rounds every matmul operand to that type (float8_e4m3fn: the
precision below the configuration's bfloat16): the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    """The numbers the reference reads from the configuration file."""
    pq = cfg["assumed"]["latent_pq"]
    dep = cfg["deployment"]
    return dict(
        d=cfg["hidden_size"], H=cfg["num_attention_heads"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        E=dep["router_experts"], Eh=cfg["n_routed_experts"],
        first=dep["first_held_expert"], k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]),
        L=cfg["num_hidden_layers"], Ld=cfg["first_k_dense_replace"],
        V=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]),
        rope=tuple(sorted(cfg["rope_scaling"].items())),
        D=pq["num_subspaces"], K=pq["num_codewords"],
        w=float(pq["distortion_weight"]),
        alpha=float(cfg["assumed"]["aux_loss_alpha"]),
        q_chunk=cfg["assumed"].get("reference_q_chunk", 512))


def leaf_shapes(s: dict) -> dict:
    """path tuple -> (shape, init, scale); init "normal" (scale None:
    1/√shape[-2]), "ones" or "eye"."""
    d, H, r = s["d"], s["H"], s["r"]

    def attn(L):
        return {("attn", "kv_norm"): ((L, r), "ones", None),
                ("attn", "wkv_a"): ((L, d, r + s["dr"]), "normal", None),
                ("attn", "wkv_b"): ((L, r, H * (s["dn"] + s["dv"])),
                                    "normal", None),
                ("attn", "wo"): ((L, H * s["dv"], d), "normal", None),
                ("attn", "wq"): ((L, d, H * (s["dn"] + s["dr"])),
                                 "normal", None),
                ("ln1",): ((L, d), "ones", None),
                ("ln2",): ((L, d), "ones", None)}

    Ld, Lm = s["Ld"], s["L"] - s["Ld"]
    out = {("embed",): ((s["V"], d), "normal", 1.0),
           ("head",): ((s["V"], d), "normal", None),
           ("ln_f",): ((d,), "ones", None),
           ("kvq", "rot_c"): ((s["L"], r, r), "eye", None),
           ("kvq", "cb_c"): ((s["L"], s["D"], s["K"], r // s["D"]),
                             "normal", 0.02)}
    if Ld:
        for k, v in attn(Ld).items():
            out[("dense_layers",) + k] = v
        f = s["f"]
        out[("dense_layers", "ffn", "wi")] = ((Ld, d, f), "normal", None)
        out[("dense_layers", "ffn", "wg")] = ((Ld, d, f), "normal", None)
        out[("dense_layers", "ffn", "wo")] = ((Ld, f, d), "normal", None)
    for k, v in attn(Lm).items():
        out[("layers",) + k] = v
    Eh, fe, fs = s["Eh"], s["fe"], s["fs"]
    ffn = {"router": (Lm, d, s["E"]), "wi": (Lm, Eh, d, fe),
           "wg": (Lm, Eh, d, fe), "wo": (Lm, Eh, fe, d),
           "shared_wi": (Lm, d, fs), "shared_wg": (Lm, d, fs),
           "shared_wo": (Lm, fs, d)}
    for k, shape in ffn.items():
        out[("layers", "ffn", k)] = (shape, "normal", None)
    return out


def name(path: tuple) -> str:
    return "/".join(path)


def init(key, s: dict) -> dict:
    """Flat {"a/b/c": array} of float32 parameters, drawn as the trainer
    draws them."""
    shapes = leaf_shapes(s)
    paths = sorted(shapes)
    keys = jax.random.split(key, len(paths))
    out = {}
    for k, path in zip(keys, paths):
        shape, kind, scale = shapes[path]
        if kind == "ones":
            out[name(path)] = jnp.ones(shape, jnp.float32)
        elif kind == "eye":
            out[name(path)] = jnp.broadcast_to(
                jnp.eye(shape[-1], dtype=jnp.float32), shape)
        else:
            sc = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
            out[name(path)] = sc * jax.random.normal(k, shape, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# The model, in blocks (each a pure function of its arrays)
# ---------------------------------------------------------------------------

def _lo(x, dt):
    return x if dt is None else x.astype(dt).astype(jnp.float32)


def _mm(a, b, dt=None):
    return jnp.matmul(_lo(a, dt), _lo(b, dt), precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn(s: dict):
    """(inverse frequencies (dr/2,), cos/sin scale, softmax scale)."""
    rs = dict(s["rope"])
    dim, base, fac = s["dr"], s["theta"], float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def mscale(m):
        return 1.0 if fac <= 1 else 0.1 * m * math.log(fac) + 1.0

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    lo_ = max(math.floor(corr(rs["beta_fast"])), 0)
    hi_ = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if lo_ == hi_:
        hi_ += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo_) / (hi_ - lo_), 0, 1)
    inv = extra / fac * ramp + extra * (1 - ramp)
    m_all = mscale(rs["mscale_all_dim"])
    return (inv.astype(np.float32), mscale(rs["mscale"]) / m_all,
            (s["dn"] + s["dr"]) ** -0.5 * m_all * m_all)


def _rope(x, pos, inv, m):
    ang = pos[:, None].astype(jnp.float32) * inv[None]        # (S, dr/2)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    cos, sin = cos[None, :, None], sin[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def proj(a: dict, x, s: dict, dt=None):
    """MLA projections of one layer: x (B, S, d) -> q, k (B, S, H, dn+dr),
    v (B, S, H, dv), c (B, S, r)."""
    B, S, _ = x.shape
    H, r, dn, dr = s["H"], s["r"], s["dn"], s["dr"]
    inv, m, _ = yarn(s)
    h = _rms(x, a["ln1"], s["eps"])
    q = _mm(h, a["wq"], dt).reshape(B, S, H, dn + dr)
    kva = _mm(h, a["wkv_a"], dt)
    c = _rms(kva[..., :r], a["kv_norm"], s["eps"])
    kv = _mm(c, a["wkv_b"], dt).reshape(B, S, H, dn + s["dv"])
    pos = jnp.arange(S)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, inv, m)], -1)
    kr = _rope(kva[:, :, None, r:], pos, inv, m)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kr, (B, S, H, dr))], -1)
    return q, k, kv[..., dn:], c


def attend(qc, k, v, start, s: dict, dt=None):
    """Causal softmax attention of the query rows [start, start + n)."""
    n, S = qc.shape[1], k.shape[1]
    sc = jnp.einsum("bqhd,bshd->bhqs", _lo(qc, dt), _lo(k, dt),
                    precision=HI) * yarn(s)[2]
    mask = (start + jnp.arange(n))[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", _lo(p, dt), _lo(v, dt),
                      precision=HI)


def _glu(x, wi, wg, wo, dt):
    return _mm(jax.nn.silu(_mm(x, wi, dt)) * _mm(x, wg, dt), wo, dt)


def ffn_half(a: dict, x, o, s: dict, moe: bool, dt=None):
    """Attention output projection, residual, and the feed-forward half of
    one layer: returns (x_out (B, S, d), balance loss)."""
    B, S, d = x.shape
    x = x + _mm(o.reshape(B, S, -1), a["wo"], dt)
    h = _rms(x, a["ln2"], s["eps"])
    if not moe:
        return x + _glu(h, a["ffn/wi"], a["ffn/wg"], a["ffn/wo"], dt), 0.0
    t = h.reshape(B * S, d)
    probs = jax.nn.softmax(_mm(t, a["ffn/router"], dt), axis=-1)
    gates, ids = jax.lax.top_k(probs, s["k"])
    if s["norm_topk"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * s["scale"]
    y = _glu(t, a["ffn/shared_wi"], a["ffn/shared_wg"], a["ffn/shared_wo"],
             dt)
    for e in range(s["Eh"]):
        w = jnp.sum(jnp.where(ids == s["first"] + e, gates, 0.0), axis=-1)
        y = y + w[:, None] * _glu(t, a["ffn/wi"][e], a["ffn/wg"][e],
                                  a["ffn/wo"][e], dt)
    chosen = jnp.sum(jax.nn.one_hot(ids, s["E"]), axis=1).reshape(B, S, -1)
    f = jnp.mean(chosen, axis=1)
    P = jnp.mean(probs.reshape(B, S, -1), axis=1)
    aux = jnp.mean(jnp.sum(f * P, axis=-1)) * s["E"] / s["k"]
    return x + y.reshape(B, S, d), aux


def distortion(rot, cb, c, s: dict, dt=None):
    """(1/m)‖cR − φ(cR)‖² over the m latents, φ the nearest codeword in
    each of the D subspaces (the assignment carries no gradient)."""
    z = _mm(c.reshape(-1, s["r"]), rot, dt)
    zs = z.reshape(z.shape[0], s["D"], -1)
    d2 = (jnp.sum(zs * zs, -1)[..., None]
          - 2 * jnp.einsum("mds,dks->mdk", zs, cb, precision=HI)
          + jnp.sum(cb * cb, -1)[None])
    code = jax.lax.stop_gradient(jnp.argmin(d2, axis=-1))
    q = cb[jnp.arange(s["D"])[None], code].reshape(z.shape)
    return jnp.mean(jnp.sum((z - q) ** 2, axis=-1))


def head_loss(ln_f, head, x, labels, s: dict, dt=None):
    h = _rms(x, ln_f, s["eps"]).reshape(-1, s["d"])
    logits = _mm(h, head.T, dt)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels.reshape(-1, 1), -1)[:, 0]
    return jnp.mean(lse - gold)


# jitted blocks: forward, and forward-then-pullback of a cotangent

def _hash(s: dict):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _proj(a, x, sk, dt):
    return proj(a, x, dict(sk), dt)


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _proj_bwd(a, x, ct, sk, dt):
    return jax.vjp(lambda a, x: proj(a, x, dict(sk), dt), a, x)[1](ct)


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _attend(qc, k, v, start, sk, dt):
    return attend(qc, k, v, start, dict(sk), dt)


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _attend_bwd(qc, k, v, ct, start, sk, dt):
    return jax.vjp(lambda q, k, v: attend(q, k, v, start, dict(sk), dt),
                   qc, k, v)[1](ct)


@functools.partial(jax.jit, static_argnames=("sk", "moe", "dt"))
def _ffn(a, x, o, sk, moe, dt):
    return ffn_half(a, x, o, dict(sk), moe, dt)


@functools.partial(jax.jit, static_argnames=("sk", "moe", "dt"))
def _ffn_bwd(a, x, o, ct, sk, moe, dt):
    return jax.vjp(lambda a, x, o: ffn_half(a, x, o, dict(sk), moe, dt),
                   a, x, o)[1](ct)


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _dist_vg(rot, cb, c, scale, sk, dt):
    v, pull = jax.vjp(lambda r, b, c: distortion(r, b, c, dict(sk), dt),
                      rot, cb, c)
    return v, pull(scale)


@functools.partial(jax.jit, static_argnames=("sk", "dt"))
def _head_vg(ln_f, head, x, labels, sk, dt):
    return jax.value_and_grad(
        lambda a, b, x: head_loss(a, b, x, labels, dict(sk), dt),
        argnums=(0, 1, 2))(ln_f, head, x)


@jax.jit
def _embed_grad(dx, tokens, V):
    return jnp.zeros((V.shape[0], dx.shape[-1]), jnp.float32).at[
        tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))


def _layer(p: dict, l: int, s: dict):
    """(prefix, index within the stack, moe?) of layer l."""
    if l < s["Ld"]:
        return "dense_layers", l, False
    return "layers", l - s["Ld"], True


#: a layer's leaves that the projections read (the rest: the other half)
PROJ = ("ln1", "wq", "wkv_a", "kv_norm", "wkv_b")


def _layer_params(p: dict, l: int, s: dict) -> tuple[dict, dict]:
    """(projection leaves, feed-forward-half leaves) of layer l, named
    without their prefix ("wq", "ffn/wi", ...)."""
    prefix, i, _ = _layer(p, l, s)
    a = {k[len(prefix) + 1:].replace("attn/", ""): v[i]
         for k, v in p.items() if k.startswith(prefix + "/")}
    return ({k: v for k, v in a.items() if k in PROJ},
            {k: v for k, v in a.items() if k not in PROJ})


def _chunks(S: int, n: int):
    n = min(n, S)
    return [(i, min(i + n, S)) for i in range(0, S, n)]


def loss_and_grads(p: dict, tokens, labels, s: dict, dt=None):
    """(loss, {name: host float32 gradient}) of one batch, the gradients
    taken in blocks (module docstring)."""
    sk = _hash(s)
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    S = tokens.shape[1]
    L = s["L"]
    x = p["embed"][tokens]
    xs, aux_sum, dist_sum = [x], 0.0, 0.0
    for l in range(L):
        a, b = _layer_params(p, l, s)
        q, k, v, c = _proj(a, x, sk, dt)
        o = jnp.concatenate([_attend(q[:, i:j], k, v, i, sk, dt)
                             for i, j in _chunks(S, s["q_chunk"])], axis=1)
        dist, _ = _dist_vg(p["kvq/rot_c"][l], p["kvq/cb_c"][l], c,
                           jnp.float32(0.0), sk, dt)
        x, aux = _ffn(b, x, o, sk, _layer(p, l, s)[2], dt)
        del q, k, v, c, o
        xs.append(x)
        aux_sum += float(aux)
        dist_sum += float(dist)
    xent, (g_lnf, g_head, dx) = _head_vg(p["ln_f"], p["head"], xs[-1],
                                         labels, sk, dt)
    loss = float(xent) + s["alpha"] * aux_sum + s["w"] / L * dist_sum
    grads = {"ln_f": np.asarray(g_lnf), "head": np.asarray(g_head)}
    per_layer: dict = {}
    for l in reversed(range(L)):
        a, b = _layer_params(p, l, s)
        moe = _layer(p, l, s)[2]
        x = xs[l]
        q, k, v, c = _proj(a, x, sk, dt)
        o = jnp.concatenate([_attend(q[:, i:j], k, v, i, sk, dt)
                             for i, j in _chunks(S, s["q_chunk"])], axis=1)
        g_ffn, dx_ffn, do = _ffn_bwd(b, x, o, (dx, jnp.float32(s["alpha"])),
                                     sk, moe, dt)
        del o
        dq = []
        dk, dv = jnp.zeros_like(k), jnp.zeros_like(v)
        for i, j in _chunks(S, s["q_chunk"]):
            gq, gk, gv = _attend_bwd(q[:, i:j], k, v, do[:, i:j], i, sk, dt)
            dq.append(gq)
            dk, dv = dk + gk, dv + gv
        dq = jnp.concatenate(dq, axis=1)
        _, (g_rot, g_cb, dc) = _dist_vg(p["kvq/rot_c"][l], p["kvq/cb_c"][l],
                                        c, jnp.float32(s["w"] / L), sk, dt)
        g_proj, dx_proj = _proj_bwd(a, x, (dq, dk, dv, dc), sk, dt)
        dx = dx_ffn + dx_proj
        del q, k, v, c, dq, dk, dv, do, dc
        g = {kk: np.asarray(vv) for kk, vv in (*g_ffn.items(),
                                                *g_proj.items())}
        g["rot_c"], g["cb_c"] = np.asarray(g_rot), np.asarray(g_cb)
        per_layer[l] = g
    grads["embed"] = np.asarray(_embed_grad(dx, tokens, p["embed"]))
    for key_ in p:
        if key_ in grads:
            continue
        prefix, leaf = key_.split("/", 1)
        if prefix == "kvq":
            rows = [per_layer[l][leaf] for l in range(L)]
        else:
            leaf = leaf.replace("attn/", "")
            rows = [per_layer[l][leaf] for l in range(L)
                    if _layer(p, l, s)[0] == prefix]
        grads[key_] = np.stack(rows).astype(np.float32)
    return loss, grads


# ---------------------------------------------------------------------------
# The optimizer: AdamW on every leaf but rot_c, GCD-G on each rot_c
# ---------------------------------------------------------------------------

def lr_at(step: int, opt: dict) -> float:
    warm = min(1.0, (step + 1.0) / max(opt["warmup_steps"], 1))
    frac = min(max(step / max(opt["total_steps"], 1), 0.0), 1.0)
    return opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * frac))


def greedy_pairs(A: np.ndarray):
    """GCD-G: take edges (i < j) by |A_ij| descending whenever both axes
    are still free, until n/2 disjoint pairs are chosen."""
    n = A.shape[0]
    iu, ju = np.triu_indices(n, 1)
    order = np.argsort(-np.abs(A[iu, ju]), kind="stable")
    used = np.zeros(n, bool)
    pi, pj = [], []
    for e in order:
        i, j = iu[e], ju[e]
        if not (used[i] or used[j]):
            used[i] = used[j] = True
            pi.append(i)
            pj.append(j)
            if len(pi) == n // 2:
                break
    return np.array(pi), np.array(pj)


def gcd_step(R: np.ndarray, G: np.ndarray, lr: float) -> np.ndarray:
    """One GCD-G step: A = GᵀR − RᵀG, greedy pairs, θ = −lr·A_ij/√2,
    R ← R·∏ R_ij(θ) (columns i, j rotated)."""
    R64, G64 = R.astype(np.float64), G.astype(np.float64)
    M = G64.T @ R64
    A = M - M.T
    pi, pj = greedy_pairs(A)
    theta = -lr * A[pi, pj] / math.sqrt(2.0)
    c, sn = np.cos(theta), np.sin(theta)
    ci, cj = R64[:, pi].copy(), R64[:, pj].copy()
    R64[:, pi] = c * ci + sn * cj
    R64[:, pj] = c * cj - sn * ci
    return R64.astype(np.float32)


def first_steps(seed: int, cfg: dict, opt: dict, batches,
                dtype=jnp.float32) -> dict:
    """Run the reference over ``batches`` (host (tokens, labels) pairs, one
    per step). Returns the per-step losses, the first step's clipped
    gradient per leaf (host arrays; Adam's leaves only), the change norm of
    each leaf after the last step, and the final rotations."""
    s = sizes(cfg)
    dt = None if dtype == jnp.float32 else dtype
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        p = {k: np.asarray(v) for k, v in init(key, s).items()}
        m = {k: np.zeros_like(v) for k, v in p.items()}
        v2 = {k: np.zeros_like(v) for k, v in p.items()}
        losses, first_grads = [], None
        b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
        for step, (tok, lab) in enumerate(batches):
            dev = {k: jnp.asarray(x) for k, x in p.items()}
            loss, g = loss_and_grads(dev, tok, lab, s, dt)
            del dev
            losses.append(loss)
            gn = math.sqrt(sum(float(np.sum(np.square(x, dtype=np.float64)))
                               for x in g.values()))
            clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            if step == 0:
                first_grads = {k: x * np.float32(clip) for k, x in g.items()
                               if k != "kvq/rot_c"}
            lr, t = lr_at(step, opt), step + 1.0
            for k in p:
                gk = g[k] * np.float32(clip)
                if k == "kvq/rot_c":
                    p[k] = np.stack([gcd_step(R, G, opt["rotation_lr"])
                                     for R, G in zip(p[k], gk)])
                    continue
                m[k] = b1 * m[k] + (1 - b1) * gk
                v2[k] = b2 * v2[k] + (1 - b2) * gk * gk
                upd = (m[k] / (1 - b1 ** t)) / (np.sqrt(v2[k] / (1 - b2 ** t))
                                                + eps)
                p[k] = (p[k] - lr * upd).astype(np.float32)
            del g
        del m, v2
        p0 = init(key, s)
        change = {k: float(np.linalg.norm(
            (p[k] - np.asarray(p0[k])).ravel().astype(np.float64)))
            for k in p}
    return {"losses": losses, "grads": first_grads, "change_norms": change,
            "rot_c": p["kvq/rot_c"]}
