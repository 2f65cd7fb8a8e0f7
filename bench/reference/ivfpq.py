"""Plain float32 IVF-PQ search in numpy: the reference every served answer
is compared with.

It reads the index as data (codes, ids, list offsets, coarse centroids,
residual codebooks and the build rotation R0, all frozen at build) and
recomputes, query by query and with none of the program's code:

    QR     = q · R0                       (rotated query)
    coarse = QR · Cᵀ,  probe the ``nprobe`` best lists
    LUT    = per subspace d: (QR · qdelta)_d · CB[d]ᵀ
    score  = coarse[list(row)] + Σ_d LUT[d, code[row, d]]   (live rows)
    top-k  of the live rows of the probed lists

``qdelta`` is the identity for an index served as built. For an index the
trainer refreshed with Givens rotation deltas it is the reference's own
``live_transform`` of those deltas, never the program's table.

``lowered`` rounds every operand and intermediate to bfloat16 (sums stay in
float32, as on a matrix unit): the control that must come out as not
correct.
"""
from __future__ import annotations

import numpy as np

BIG = 1e9     # a gap that stands for "no such answer"


def bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


class Index:
    """Host copy of an IVF-PQ index (numpy arrays; see module docstring)."""

    def __init__(self, *, codes, ids, offsets, centroids, codebooks, R0,
                 qdelta=None):
        self.codes = np.asarray(codes)
        self.ids = np.asarray(ids)
        self.offsets = np.asarray(offsets).astype(np.int64)
        self.centroids = np.asarray(centroids, np.float32)
        self.codebooks = np.asarray(codebooks, np.float32)
        self.R0 = np.asarray(R0, np.float32)
        self.qdelta = None if qdelta is None else np.asarray(qdelta,
                                                             np.float32)
        L = len(self.offsets) - 1
        cap = len(self.ids)
        self.row_list = np.clip(np.searchsorted(
            self.offsets, np.arange(cap), side="right") - 1, 0, L - 1)
        live = np.nonzero(self.ids >= 0)[0]
        self.id_to_row = np.full(int(self.ids.max()) + 1, -1, np.int64)
        self.id_to_row[self.ids[live]] = live

    @classmethod
    def from_state(cls, state, qdelta=None) -> "Index":
        """From a ``search`` ADC state (ivf backend), its build-time index
        copied to the host; ``qdelta`` is the reference's query transform
        (None: the index as built)."""
        ix = state.index
        return cls(codes=np.asarray(ix.codes), ids=np.asarray(ix.ids),
                   offsets=np.asarray(ix.list_offsets),
                   centroids=np.asarray(ix.coarse.centroids),
                   codebooks=np.asarray(ix.quantizer.codebooks),
                   R0=np.asarray(ix.R), qdelta=qdelta)

    def tables(self, Q: np.ndarray, lowered: bool = False):
        """(coarse scores (b, L), LUTs (b, D, K)) of a query batch."""
        r = bf16 if lowered else (lambda x: x)
        D, K, sub = self.codebooks.shape
        QR = r(r(np.asarray(Q, np.float32)) @ r(self.R0))
        coarse = r(QR @ r(self.centroids).T)
        QL = QR if self.qdelta is None else r(QR @ r(self.qdelta))
        lut = r(np.einsum("bds,dks->bdk", QL.reshape(len(QL), D, sub),
                          r(self.codebooks)))
        return coarse, lut

    def row_scores(self, coarse_q, lut_q, rows) -> np.ndarray:
        D = lut_q.shape[0]
        adc = lut_q[np.arange(D)[None, :], self.codes[rows].astype(np.int64)]
        return coarse_q[self.row_list[rows]] + adc.sum(axis=1,
                                                        dtype=np.float32)

    def search(self, Q, *, nprobe: int, k: int, lowered: bool = False):
        """Top-k (scores (b, k) descending, ids (b, k)) of each query."""
        coarse, lut = self.tables(Q, lowered)
        b = len(coarse)
        scores = np.full((b, k), -np.inf, np.float32)
        ids = np.full((b, k), -1, np.int64)
        for q in range(b):
            lists = np.argsort(-coarse[q], kind="stable")[:nprobe]
            rows = np.concatenate([np.arange(self.offsets[l],
                                             self.offsets[l + 1])
                                   for l in lists])
            rows = rows[self.ids[rows] >= 0]
            s = self.row_scores(coarse[q], lut[q], rows)
            top = np.argsort(-s, kind="stable")[:k]
            scores[q, :len(top)] = s[top]
            ids[q, :len(top)] = self.ids[rows[top]]
        return scores, ids

    def score_of(self, Q, ids) -> np.ndarray:
        """Reference score of given item ids for each query (-inf for an
        id that names no live row)."""
        coarse, lut = self.tables(Q)
        ids = np.asarray(ids)
        out = np.full(ids.shape, -np.inf, np.float32)
        for q in range(len(ids)):
            ok = (ids[q] >= 0) & (ids[q] < len(self.id_to_row))
            rows = np.full(ids[q].shape, -1)
            rows[ok] = self.id_to_row[ids[q][ok]]
            have = rows >= 0
            out[q, have] = self.row_scores(coarse[q], lut[q], rows[have])
        return out


def pair_rotations(n: int, deltas, keep=None) -> np.ndarray:
    """The (n, n) float64 product, in step order, of every Givens delta's
    plane rotations: a delta (pi, pj, theta) of disjoint pairs turns column
    i of a matrix it right-multiplies into cos·x_i + sin·x_j and column j
    into cos·x_j − sin·x_i. ``keep(pi, pj)`` selects the pairs applied (a
    pair left out rotates by 0)."""
    M = np.eye(n)
    for pi, pj, theta in deltas:
        pi, pj = np.asarray(pi, np.int64), np.asarray(pj, np.int64)
        theta = np.asarray(theta, np.float64)
        if keep is not None:
            theta = np.where(keep(pi, pj), theta, 0.0)
        c, s = np.cos(theta), np.sin(theta)
        xi, xj = M[:, pi].copy(), M[:, pj]
        M[:, pi] = c * xi + s * xj
        M[:, pj] = c * xj - s * xi
    return M


def within(sub: int):
    """The pairs whose two coordinates lie in one PQ subspace of ``sub``."""
    return lambda pi, pj: (pi // sub) == (pj // sub)


def live_transform(R0, deltas, sub: int) -> dict:
    """The live matrices of an index refreshed on the query side, in
    float64, from its build rotation and the trainer's Givens deltas:
    the whole delta product Δ, the live rotation ``rot`` = R0·Δ, the
    within-subspace product ``wacc`` = W, and the query-side transform
    ``qdelta`` = Δ·Wᵀ. Scores of q·R0·qdelta against the frozen codebooks
    equal those of the index rebuilt under R0·Δ with its codebooks turned
    by W, since Wᵀ is block-diagonal by subspace."""
    R0 = np.asarray(R0, np.float64)
    n = len(R0)
    delta = pair_rotations(n, deltas)
    W = pair_rotations(n, deltas, keep=within(sub))
    return {"delta": delta, "rot": R0 @ delta, "wacc": W,
            "qdelta": delta @ W.T}


def refresh_gap(got: dict, ref: dict) -> float:
    """Widest entry gap of the live matrices (``rot``, ``wacc``,
    ``qdelta``) against the reference's, as a share of the widest entry
    by which the reference's whole delta Δ departs from the identity: the
    learned rotation moves entries by as little as 1e-7 in a window, so an
    absolute gap would read every refresh, or none, as right."""
    scale = np.max(np.abs(ref["delta"] - np.eye(len(ref["delta"]))))
    gap = max(float(np.max(np.abs(np.asarray(got[k], np.float64) - ref[k])))
              for k in ("rot", "wacc", "qdelta"))
    return gap / scale if scale > 0 else (0.0 if gap == 0 else BIG)


#: how far from the nprobe-th list's coarse score a list may lie and still
#: be probed or not by a correct program: the program forms the coarse term
#: with float32 matrix products at the TPU's default precision (one
#: bfloat16 pass), which moves it by ~1e-4; a list clear of this margin
#: must be probed
PROBE_MARGIN = 1e-3


def must_probe(coarse_q: np.ndarray, nprobe: int) -> np.ndarray:
    """Lists every correct probe of this query includes."""
    cut = np.sort(coarse_q)[::-1][nprobe] if nprobe < len(coarse_q) \
        else -np.inf
    return np.nonzero(coarse_q > cut + PROBE_MARGIN)[0]


def compare(index: Index, Q, got_scores, got_ids, *, nprobe: int,
            k: int) -> dict:
    """The two numbers served top-k answers are judged by, against the
    float32 reference over the same index:

    ``margin_err``  widest gap between a served margin — how far below the
                    query's first served score the score served at each
                    rank lies — and the reference's margin between the same
                    two ids. The ranking rests on these margins alone; a
                    shift common to all of a query's scores (the program
                    rounds the query to bfloat16 in its default-precision
                    matrix products) leaves them be.
    ``rank_gap``    widest amount by which a served id's reference score
                    lies below the k-th best live row of the lists every
                    correct probe includes (``must_probe``).

    An id that names no live row, or a missing answer, counts as ``BIG``.
    """
    got_scores = np.asarray(got_scores, np.float32)
    got_ids = np.asarray(got_ids)
    Q = np.asarray(Q, np.float32)
    coarse, lut = index.tables(Q)
    served = index.score_of(Q, got_ids)
    kth = np.full(len(Q), -np.inf, np.float32)
    for q in range(len(Q)):
        lists = must_probe(coarse[q], nprobe)
        if len(lists):
            rows = np.concatenate([np.arange(index.offsets[l],
                                             index.offsets[l + 1])
                                   for l in lists])
            rows = rows[index.ids[rows] >= 0]
            s = np.sort(index.row_scores(coarse[q], lut[q], rows))[::-1]
            if len(s) >= k:
                kth[q] = s[k - 1]
    ok = np.isfinite(served) & np.isfinite(got_scores)
    margin = ((got_scores[:, :1] - got_scores) - (served[:, :1] - served))
    margin_err = np.where(ok & ok[:, :1], np.abs(margin), BIG)
    regret = np.where(ok, kth[:, None] - served, BIG)
    return {"margin_err": float(min(np.max(margin_err), BIG)),
            "rank_gap": float(min(max(0.0, np.max(regret)), BIG))}
