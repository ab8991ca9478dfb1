"""Plain references, independent of the program: they import nothing of it
and take only the graph data the benchmark generated.

- `hops`: level-synchronous BFS hop counts in numpy.
- `pagerank`: the power method in float64, dangling mass dropped, N counting
  every vertex id, as the engine's program states it.
- `ebv_regret`: the paper's EBV rule checked edge by edge. The stream is the
  edges in ascending degree-sum order (stable, so ties keep input order);
  the state before each edge (which endpoints each part holds, its edge and
  vertex counts) is rebuilt from the assignment of the edges before it, and
  each edge's part is scored against every part with
  I(u not in P_i) + I(v not in P_i) + alpha*e_i*p/|E| + beta*v_i*p/|V|.
  The regret of an edge is its part's score minus the lowest score; a
  sequential EBV pass gives 0 up to float32 rounding of the scores.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def csr(src: np.ndarray, dst: np.ndarray, n: int, weight=None) -> sp.csr_matrix:
    data = np.ones(src.shape[0], np.float64) if weight is None else np.asarray(weight, np.float64)
    return sp.csr_matrix((data, (np.asarray(src, np.int64), np.asarray(dst, np.int64))), shape=(n, n))


def hops(adj: sp.csr_matrix, root: int) -> np.ndarray:
    """Hop counts from `root` over the arcs of `adj`; unreached: inf."""
    indptr, indices = adj.indptr, adj.indices
    dist = np.full(adj.shape[0], np.inf)
    dist[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        starts, counts = indptr[frontier], np.diff(indptr)[frontier]
        offsets = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = indices[offsets + np.arange(offsets.size)]
        nbrs = np.unique(nbrs[np.isinf(dist[nbrs])])
        level += 1
        dist[nbrs] = level
        frontier = nbrs
    return dist


def pagerank(src, dst, n: int, *, damping: float, num_iters: int) -> np.ndarray:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    share_of = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    rank = np.full(n, 1.0 / n)
    for _ in range(num_iters):
        agg = np.bincount(dst, weights=(rank * share_of)[src], minlength=n)
        rank = (1.0 - damping) / n + damping * agg
    return rank


def degree_sum_order(src, dst, n: int) -> np.ndarray:
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    return np.argsort(deg[src] + deg[dst], kind="stable")


def ebv_regret(src, dst, part, n: int, p: int, *, alpha: float = 1.0, beta: float = 1.0,
               block: int = 1 << 20) -> np.ndarray:
    """Per-edge regret of an EBV assignment (stream order; see module doc).
    Parts outside [0, p) read inf."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    part = np.asarray(part, np.int64)
    E = src.shape[0]
    if part.shape != (E,) or part.min(initial=0) < 0 or part.max(initial=0) >= p:
        return np.full(E, np.inf)
    order = degree_sum_order(src, dst, n)
    su, sv, sp_ = src[order], dst[order], part[order]
    # first[i, x]: stream position of the first edge in part i touching x
    # (E where none does); replicas are interleaved u, v per edge.
    keys = np.stack([sp_ * n + su, sp_ * n + sv], axis=1).ravel()
    uniq, at = np.unique(keys, return_index=True)
    first = np.full(p * n, E, np.int64)
    first[uniq] = at // 2
    first = first.reshape(p, n)
    m = np.arange(E)
    new_replicas = (first[sp_, su] == m).astype(np.int64) + (first[sp_, sv] == m)
    inv_e, inv_v = p / E, p / n
    e_count = np.zeros(p, np.int64)
    v_count = np.zeros(p, np.int64)
    regret = np.empty(E)
    for lo in range(0, E, block):
        hi = min(lo + block, E)
        mb = m[lo:hi]
        onehot = np.zeros((hi - lo, p), np.int64)
        onehot[np.arange(hi - lo), sp_[lo:hi]] = 1
        e_before = e_count + np.cumsum(onehot, axis=0) - onehot
        added = onehot * new_replicas[lo:hi, None]
        v_before = v_count + np.cumsum(added, axis=0) - added
        miss = (first[:, su[lo:hi]].T >= mb[:, None]).astype(np.float64)
        miss += first[:, sv[lo:hi]].T >= mb[:, None]
        score = miss + alpha * e_before * inv_e + beta * v_before * inv_v
        regret[lo:hi] = score[np.arange(hi - lo), sp_[lo:hi]] - score.min(axis=1)
        e_count += onehot.sum(axis=0)
        v_count += added.sum(axis=0)
    out = np.empty(E)
    out[order] = regret
    return out
