"""Plain reference of the two-tower trainer's first steps: the model of
arXiv 2203.05082 §3.2 (two towers, the index layer T(X) = φ(XR)Rᵀ with a
straight-through product quantizer, cosine scores, in-batch hinge loss with
the distortion term of Eq. 1), AdamW on every leaf but R, and one greedy
Givens-coordinate-descent step on R (the paper's Algorithm 2, GCD-G).

Written from the paper and the configuration alone, in straightforward
``jax.numpy`` at float32 with ``precision="highest"``; it imports nothing
of the program and takes nothing the program made. Parameters are drawn
from the seed with the same key schedule the trainer uses (leaves in
sorted-name order, one key each; the index layer from ``fold_in(key, 1)``),
so both start from the same point. ``dtype=bfloat16`` computes and stores
everything in bfloat16: the control.

Sizes come from the configuration file:
``item_vocab, embed_dim, tower_dims, hist_len, hinge_margin,
index.{dim, num_subspaces, num_codewords, distortion_weight}``; the
optimizer settings from the workload's ``optimizer`` block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, init scale or None for zeros)."""
    e = cfg["embed_dim"]
    dims = (e, *cfg["tower_dims"])
    out = {"item_table": ((cfg["item_vocab"], e), 0.01)}
    for tower in ("user", "item"):
        for i in range(len(dims) - 1):
            out[f"{tower}{i}_w"] = ((dims[i], dims[i + 1]),
                                    1.0 / math.sqrt(dims[i]))
            out[f"{tower}{i}_b"] = ((dims[i + 1],), None)
    return out


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    params = {}
    for k, name in zip(keys, names):
        shape, scale = shapes[name]
        params[name] = (jnp.zeros(shape, dtype) if scale is None else
                        (scale * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype))
    ix = cfg["index"]
    n, D, K = ix["dim"], ix["num_subspaces"], ix["num_codewords"]
    cb = 0.01 * jax.random.normal(jax.random.fold_in(key, 1),
                                  (D, K, n // D), dtype=dtype)
    params["index_R"] = jnp.eye(n, dtype=dtype)
    params["index_codebooks"] = cb
    return params


def _mlp(p, x, tower: str, depth: int):
    for i in range(depth):
        x = _mm(x, p[f"{tower}{i}_w"]) + p[f"{tower}{i}_b"]
        if i < depth - 1:
            x = jax.nn.relu(x)
    return x


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def loss(p, hist, pos, cfg: dict):
    depth = len(cfg["tower_dims"])
    table = p["item_table"]
    valid = hist >= 0
    rows = jnp.where(valid[..., None], table[jnp.maximum(hist, 0)], 0)
    cnt = jnp.maximum(valid.sum(axis=1), 1).astype(table.dtype)
    u = _mlp(p, rows.sum(axis=1) / cnt[:, None], "user", depth)
    v = _mlp(p, table[pos], "item", depth)
    # index layer: rotate, quantize per subspace (nearest codeword,
    # straight-through), rotate back; distortion of the rotated vectors
    R, cb = p["index_R"], p["index_codebooks"]
    D, K, sub = cb.shape
    XR = _mm(v, R)
    Xs = XR.reshape(len(XR), D, sub)
    d2 = (jnp.sum(Xs * Xs, axis=-1)[..., None]
          - 2.0 * jnp.einsum("bds,dks->bdk", Xs, cb, precision=HI)
          + jnp.sum(cb * cb, axis=-1)[None])
    code = jax.lax.stop_gradient(jnp.argmin(d2, axis=-1))
    q = cb[jnp.arange(D)[None, :], code].reshape(XR.shape)
    tx = _mm(XR + jax.lax.stop_gradient(q - XR), R.T)
    dist = jnp.mean(jnp.sum((XR - q) ** 2, axis=-1))
    s = _mm(_unit(u), _unit(tx).T).astype(jnp.float32)
    B = s.shape[0]
    hinge = jnp.maximum(0.0, cfg["hinge_margin"] + s - jnp.diag(s)[:, None])
    hinge = jnp.where(jnp.eye(B, dtype=bool), 0.0, hinge)
    return (jnp.sum(hinge) / (B * (B - 1.0))
            + cfg["index"]["distortion_weight"] * dist.astype(jnp.float32))


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


@functools.partial(jax.jit, static_argnames=("cfgkey",))
def _value_and_grad(p, hist, pos, cfgkey):
    return jax.value_and_grad(loss)(p, hist, pos, _thaw(cfgkey))


def _freeze(cfg: dict):
    """Hashable view of the configuration for jit."""
    def f(x):
        if isinstance(x, dict):
            return tuple(sorted((k, f(v)) for k, v in x.items()))
        if isinstance(x, list):
            return tuple(f(v) for v in x)
        return x
    return f(cfg)


def _thaw(x):
    if isinstance(x, tuple) and x and all(
            isinstance(t, tuple) and len(t) == 2 and isinstance(t[0], str)
            for t in x):
        return {k: _thaw(v) for k, v in x}
    return x


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam(p, g, m, v, clip, lr, t, b1, b2, eps):
    out_p, out_m, out_v = {}, {}, {}
    for k in p:
        if k == "index_R":
            out_p[k], out_m[k], out_v[k] = p[k], m[k], v[k]
            continue
        dt = p[k].dtype
        gk = (g[k] * clip).astype(dt)
        mk = (b1 * m[k] + (1 - b1) * gk).astype(dt)
        vk = (b2 * v[k] + (1 - b2) * gk * gk).astype(dt)
        upd = (mk / (1 - b1 ** t)) / (jnp.sqrt(vk / (1 - b2 ** t)) + eps)
        out_p[k] = (p[k] - lr * upd).astype(dt)
        out_m[k], out_v[k] = mk, vk
    return out_p, out_m, out_v


@jax.jit
def _directional(G, R):
    M = _mm(G.T, R)
    return M - M.T


@jax.jit
def _givens(R, pi, pj, theta):
    c, s = jnp.cos(theta).astype(R.dtype), jnp.sin(theta).astype(R.dtype)
    ci, cj = R[:, pi], R[:, pj]
    return R.at[:, pi].set(c * ci + s * cj).at[:, pj].set(c * cj - s * ci)


def first_steps(seed: int, cfg: dict, opt: dict, batches,
                dtype=jnp.float32) -> dict:
    """Run the reference over ``batches`` (host (hist, pos) pairs, one per
    step). Returns per-step losses, the first step's clipped gradient norm
    per leaf, and the change of each leaf after the last step, plus R."""
    key = jax.random.PRNGKey(seed)
    cfgkey = _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        p = init(key, cfg, dtype)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, grad_norms = [], None
        for step, (hist, pos) in enumerate(batches):
            value, g = _value_and_grad(p, jnp.asarray(hist), jnp.asarray(pos),
                                       cfgkey)
            losses.append(float(value))
            gn = float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                    for x in g.values())))
            clip = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
            if step == 0:
                grad_norms = {k: float(x) * clip
                              for k, x in _norms(g).items()}
            # the rotation: one greedy Givens step from the clipped gradient
            G = (g["index_R"] * clip).astype(jnp.float32)
            A = np.asarray(_directional(G, p["index_R"].astype(jnp.float32)))
            pi, pj = greedy_pairs(A)
            theta = -opt["rotation_lr"] * A[pi, pj] / math.sqrt(2.0)
            R_new = _givens(p["index_R"], jnp.asarray(pi), jnp.asarray(pj),
                            jnp.asarray(theta, jnp.float32))
            del g["index_R"]
            g["index_R"] = jnp.zeros_like(R_new)
            p, m, v = _adam(p, g, m, v, jnp.float32(clip),
                            jnp.float32(lr_at(step, opt)),
                            jnp.float32(step + 1), opt["beta1"], opt["beta2"],
                            opt["eps"])
            p["index_R"] = R_new
            del g
        del m, v
        # the start point is drawn again rather than kept: one table fewer
        # on the device while the steps run
        change = change_norms(p, init(key, cfg, dtype))
        R = np.asarray(p["index_R"], np.float32)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "R": R}


def change_norms(p, p0) -> dict:
    """‖p − p0‖ of each leaf, in float32."""
    out = _diff_norms(p, p0)
    return {k: float(x) for k, x in out.items()}


@jax.jit
def _diff_norms(p, p0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k].astype(jnp.float32)
                                           - p0[k].astype(jnp.float32))))
            for k in p}
