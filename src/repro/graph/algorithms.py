"""The benchmark algorithms on the BSP engine + numpy host oracles.

Every algorithm is a `VertexProgram` executed by the ONE generic engine
driver (`repro.graph.engine.run_bsp`); the named wrappers below just fix
the program and unwrap the dump slot. `run_program` accepts any program —
a registered name or a custom `VertexProgram` instance.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.core.types import Graph
from repro.graph.build import SubgraphSet
from repro.graph.engine import BFS, CC, PR, REACH, SSSP, BSPStats, run_bsp

_I32_INF = np.int64(2**31 - 1)


def run_program(
    sub: SubgraphSet, program, *, num_vertices: int = 0, source=None, **kw
) -> tuple[np.ndarray, BSPStats]:
    """Run any `VertexProgram` (instance or registered name) and return
    values indexed by (part, local) with the dump slot stripped."""
    val, stats = run_bsp(sub, program, num_vertices=num_vertices, source=source, **kw)
    with obs.span("engine.fetch"):
        return np.asarray(val[:, :-1]), stats


def connected_components(sub: SubgraphSet, **kw) -> tuple[np.ndarray, BSPStats]:
    """Min-label propagation CC. Returns labels indexed by (part, local)."""
    return run_program(sub, CC, **kw)


def sssp(sub: SubgraphSet, source: int, **kw) -> tuple[np.ndarray, BSPStats]:
    return run_program(sub, SSSP, source=source, **kw)


def bfs(sub: SubgraphSet, source: int, **kw) -> tuple[np.ndarray, BSPStats]:
    """Hop counts from `source` (min-plus over unit weights, int32)."""
    return run_program(sub, BFS, source=source, **kw)


def reachability(sub: SubgraphSet, **kw) -> tuple[np.ndarray, BSPStats]:
    """Max-label propagation: every vertex converges to the largest vertex
    id reachable from it over the undirected view (max-combine program,
    executed on the min-plus kernels via negation)."""
    return run_program(sub, REACH, **kw)


def pagerank(
    sub: SubgraphSet,
    num_vertices: int,
    *,
    damping: float = 0.85,
    num_iters: int = 20,
    tol: float = 0.0,
    **kw,
) -> tuple[np.ndarray, BSPStats]:
    prog = PR if damping == PR.damping else dataclasses.replace(PR, damping=float(damping))
    return run_program(
        sub, prog, num_vertices=num_vertices, max_supersteps=num_iters, tol=tol, **kw
    )


# ------------------------------------------------------------ host oracles


def cc_reference(graph: Graph) -> np.ndarray:
    """Min-label CC on the undirected view (numpy label propagation)."""
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    labels = np.arange(graph.num_vertices, dtype=np.int64)
    while True:
        a = np.minimum.reduce([labels[src], labels[dst]])
        new = labels.copy()
        np.minimum.at(new, src, a)
        np.minimum.at(new, dst, a)
        if np.array_equal(new, labels):
            return labels
        labels = new


def sssp_reference(graph: Graph, source: int, weights: np.ndarray | None = None) -> np.ndarray:
    """Bellman-Ford (numpy, directed)."""
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    w = np.ones(src.shape[0], np.float64) if weights is None else weights.astype(np.float64)
    dist = np.full(graph.num_vertices, np.inf)
    dist[source] = 0.0
    while True:
        cand = dist[src] + w
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist, equal_nan=True) or np.allclose(new, dist, equal_nan=True):
            return dist
        dist = new


def bfs_reference(graph: Graph, source: int) -> np.ndarray:
    """Hop counts from `source` over DIRECTED edges (numpy relaxation).
    Unreachable vertices hold INF_I32 (the engine's int32 infinity)."""
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    dist = np.full(graph.num_vertices, _I32_INF, np.int64)
    dist[source] = 0
    while True:
        cand = np.where(dist[src] < _I32_INF, dist[src] + 1, _I32_INF)
        new = dist.copy()
        np.minimum.at(new, dst, cand)
        if np.array_equal(new, dist):
            return dist
        dist = new


def reachability_reference(graph: Graph) -> np.ndarray:
    """Max-label propagation on the undirected view (numpy)."""
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    labels = np.arange(graph.num_vertices, dtype=np.int64)
    while True:
        a = np.maximum.reduce([labels[src], labels[dst]])
        new = labels.copy()
        np.maximum.at(new, src, a)
        np.maximum.at(new, dst, a)
        if np.array_equal(new, labels):
            return labels
        labels = new


def pagerank_reference(graph: Graph, *, damping: float = 0.85, num_iters: int = 20) -> np.ndarray:
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    N = graph.num_vertices
    outdeg = np.bincount(src, minlength=N).astype(np.float64)
    rank = np.full(N, 1.0 / N)
    for _ in range(num_iters):
        share = np.where(outdeg > 0, rank / np.maximum(outdeg, 1), 0.0)
        agg = np.zeros(N)
        np.add.at(agg, dst, share[src])
        rank = (1 - damping) / N + damping * agg
    return rank


def scatter_to_global(sub: SubgraphSet, local_vals: np.ndarray, num_vertices: int, reduce: str = "min") -> np.ndarray:
    """Collect per-(part, local) values into a global array via masters."""
    gid = np.asarray(sub.gid)
    is_m = np.asarray(sub.is_master)
    out = np.full(num_vertices, np.inf if reduce == "min" else 0.0)
    sel = is_m & (gid >= 0)
    out[gid[sel]] = local_vals[sel]
    return out
