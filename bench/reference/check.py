"""The plain reference and the comparison that decides ``correct``.

Everything here is float64 numpy (and scipy's connected components) on the
host, written from the paper's definitions; it imports nothing of the
program under test and takes nothing the program made except the answer it
checks.

* Partition (Theorem 1, eq. (4)): vertices i and j are joined by an edge iff
  |S_ij| > lam, strictly; the reference partition is the connected
  components of that graph, with S the centered Gram of X over n computed in
  float64, block of columns by block of columns at large p.
* KKT (eqs. (11)-(12)): with W = inv(Theta), a solution satisfies
  W_ii = S_ii + lam, |W_ij - S_ij| <= lam where Theta_ij = 0, and
  W_ij = S_ij + lam * sign(Theta_ij) where Theta_ij != 0.  The residual is
  the largest violation, relative to max(1, max |S_block|).
* Assembly: Theta is block-diagonal on the partition; any entry that joins
  two components is a fault.

``check_solution`` returns the numbers a run compares, each against its
limit in ``LIMITS``; the numbers are worst cases over every checked answer.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

#: entries of Theta at or below this magnitude count as zero in the KKT
#: conditions (the same cut the program's own host KKT uses)
ZERO_TOL = 1e-12
#: columns per block of the float64 Gram
GRAM_BLOCK = 2048


class Covariance:
    """S = (X - mean)'(X - mean) / n in float64, never formed whole at
    large p: edges and blocks are computed from the centered columns."""

    def __init__(self, X: np.ndarray):
        X = np.asarray(X, dtype=np.float64)
        self.n, self.p = X.shape
        self.Xc = X - X.mean(axis=0)
        self.diag = np.einsum("ij,ij->j", self.Xc, self.Xc) / self.n

    def block(self, cols: np.ndarray) -> np.ndarray:
        Z = self.Xc[:, cols]
        return Z.T @ Z / self.n

    def edges_above(self, thr: float) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(i, j, |S_ij|) for every i < j with |S_ij| > thr."""
        out_i, out_j, out_w = [], [], []
        for c0 in range(0, self.p, GRAM_BLOCK):
            c1 = min(c0 + GRAM_BLOCK, self.p)
            G = np.abs(self.Xc[:, c0:c1].T @ self.Xc[:, c0:] / self.n)
            r, c = np.nonzero(G > thr)
            gi, gj = r + c0, c + c0
            keep = gi < gj
            out_i.append(gi[keep])
            out_j.append(gj[keep])
            out_w.append(G[r[keep], c[keep]])
        return np.concatenate(out_i), np.concatenate(out_j), np.concatenate(out_w)


def components(p: int, i: np.ndarray, j: np.ndarray) -> np.ndarray:
    """Component label of every vertex of the graph with edges (i, j)."""
    A = coo_matrix((np.ones(i.size, np.int8), (i, j)), shape=(p, p))
    return connected_components(A, directed=False)[1]


class Reference:
    """Reference partitions at every lambda of a grid from one edge pass."""

    def __init__(self, X: np.ndarray, lambdas):
        self.cov = Covariance(X)
        self.lambdas = sorted({float(v) for v in lambdas}, reverse=True)
        self._edges = self.cov.edges_above(self.lambdas[-1])
        self._labels = {}

    def labels(self, lam: float) -> np.ndarray:
        lam = float(lam)
        if lam not in self._labels:
            i, j, w = self._edges
            on = w > lam
            self._labels[lam] = components(self.cov.p, i[on], j[on])
        return self._labels[lam]

    def largest(self, lam: float) -> int:
        return int(np.bincount(self.labels(lam)).max())


def same_partition(a: np.ndarray, b: np.ndarray) -> bool:
    """True when two labelings define the same partition of the vertices."""
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        return False
    pairs = np.unique(np.stack([a, b]), axis=1).shape[1]
    return pairs == np.unique(a).size == np.unique(b).size


def kkt_residual(S: np.ndarray, lam: float, Theta: np.ndarray) -> float:
    """Largest violation of the glasso optimality conditions (absolute)."""
    S = np.asarray(S, dtype=np.float64)
    Theta = np.asarray(Theta, dtype=np.float64)
    if not np.isfinite(Theta).all():
        return float("inf")
    sign, _ = np.linalg.slogdet(Theta)
    if sign <= 0:
        return float("inf")
    W = np.linalg.inv(Theta)
    off = ~np.eye(S.shape[0], dtype=bool)
    zero = np.abs(Theta) <= ZERO_TOL
    v_zero = np.where(zero & off, np.maximum(np.abs(W - S) - lam, 0.0), 0.0).max()
    v_act = np.where(~zero & off, np.abs(W - S - lam * np.sign(Theta)), 0.0).max()
    v_diag = np.abs(np.diag(W) - np.diag(S) - lam).max()
    return float(max(v_zero, v_act, v_diag))


def _groups(labels: np.ndarray) -> list[np.ndarray]:
    order = np.argsort(labels, kind="stable")
    cuts = np.nonzero(np.diff(labels[order]))[0] + 1
    return np.split(order, cuts)


def solution_blocks(Theta, want: np.ndarray):
    """(members, dense block) for every component of an answer, and whether
    its assembly is sound.

    A block-sparse answer (any object with ``blocks()``, ``isolated`` and
    ``isolated_values``) gives its blocks as stored; they have to be the
    components of the reference partition ``want``.  A dense Theta is cut
    along ``want``, and every entry joining two components has to be an
    exact zero."""
    p = want.size
    if hasattr(Theta, "blocks"):
        blocks = [(np.asarray(c), np.asarray(b, np.float64)) for c, b in Theta.blocks()]
        iso = np.asarray(Theta.isolated)
        vals = np.asarray(Theta.isolated_values, np.float64)
        blocks += [(np.array([v]), np.array([[t]])) for v, t in zip(iso, vals)]
        got = np.full(p, -1, dtype=np.int64)
        for k, (c, _) in enumerate(blocks):
            got[c] = k
        sound = not (got < 0).any() and same_partition(got, want)
        return blocks, sound
    T = np.asarray(Theta, dtype=np.float64)
    cross = want[:, None] != want[None, :]
    sound = T.shape == (p, p) and not np.any(T[cross] != 0)
    return [(c, T[np.ix_(c, c)]) for c in _groups(want)], sound


def check_solution(ref: Reference, lam: float, labels, Theta, *, kkt: bool = True) -> dict:
    """The numbers one answer (labels and Theta at lam) reads.

    ``partition``: 1 when the answer's labels differ from the reference
    partition; ``offblock``: 1 when Theta is not block-diagonal on the
    reference partition (an assembly fault: a block missing, merged, split
    or misplaced, or a nonzero joining two components); ``kkt``: the worst
    KKT residual over the blocks, each relative to max(1, max |S_block|)
    (0 when ``kkt`` is False: the answer is outside the KKT sample)."""
    want = ref.labels(lam)
    blocks, sound = solution_blocks(Theta, want)
    worst = 0.0
    if not kkt:
        blocks = []
    iso = [(c[0], b[0, 0]) for c, b in blocks if c.size == 1]
    if iso:
        v = np.array([k for k, _ in iso])
        t = np.array([x for _, x in iso], dtype=np.float64)
        with np.errstate(divide="ignore"):
            res = np.where(t > 0, np.abs(1.0 / t - ref.cov.diag[v] - lam), np.inf)
        worst = float((res / np.maximum(1.0, ref.cov.diag[v])).max())
    for c, blk in blocks:
        if c.size == 1:
            continue
        S = ref.cov.block(c)
        rel = kkt_residual(S, lam, blk) / max(1.0, float(np.abs(S).max()))
        worst = max(worst, rel) if rel == rel else float("inf")
    return {
        "partition": int(not same_partition(np.asarray(labels), want)),
        "offblock": int(not sound),
        "kkt": worst,
    }


def merge_checks(readings: list[dict]) -> dict:
    """Worst case of each number over many answers."""
    out = {"partition": 0, "offblock": 0, "kkt": 0.0}
    for r in readings:
        out["partition"] += r["partition"]
        out["offblock"] += r["offblock"]
        out["kkt"] = max(out["kkt"], r["kkt"])
    return out
