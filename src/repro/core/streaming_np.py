"""Pure-numpy oracle for the streaming EdgeScorer core.

ONE reference loop covers every registered scorer (EBV, HDRF, Greedy, and
custom instances): float32 state mutated in the same op order as the JAX
drivers in `repro.core.streaming`, so both implementations resolve
near-ties identically and the parity tests can assert exact equality.
XLA's CPU backend contracts each multiply-add of the score into one fused
multiply-add (a single rounding); the oracle rounds those the same way,
or a tie such as 1.25 + 5·(1/6) vs 1.75 + 2·(1/6) splits by one ulp.
"""
from __future__ import annotations

from typing import Optional, Union

import numpy as np

from repro.core.order import degree_sum_order
from repro.core.streaming import EdgeScorer, edge_weights_np, get_scorer
from repro.core.types import Graph, PartitionResult


def _fma32(a, b, c):
    """float32 a·b + c rounded once: the f32 product is exact in float64,
    and the float64 sum is exact at the score magnitudes the loop meets."""
    return (np.asarray(a, np.float64) * np.float64(b) + c).astype(np.float32)


def streaming_partition_np(
    graph: Graph,
    num_parts: int,
    scorer: Union[str, EdgeScorer],
    *,
    ce: Optional[float] = None,
    cv: Optional[float] = None,
    eps: Optional[float] = None,
    order: Optional[np.ndarray] = None,
    sort_edges: Optional[bool] = None,
) -> PartitionResult:
    sc = get_scorer(scorer)
    ce, cv, eps = sc.coefficients(ce, cv, eps)
    if sort_edges is None:
        sort_edges = sc.sort_edges
    if order is None and sort_edges:
        order = degree_sum_order(graph)
    src = np.asarray(graph.src, dtype=np.int64)
    dst = np.asarray(graph.dst, dtype=np.int64)
    if order is not None:
        src, dst = src[order], dst[order]
    E, V, p = src.shape[0], graph.num_vertices, num_parts
    w = edge_weights_np(sc, graph, src, dst)
    keep = np.zeros((p, V), dtype=bool)
    # float32 state in the same op order as the JAX scan, so both
    # implementations resolve near-ties identically.
    e_count = np.zeros((p,), dtype=np.float32)
    v_count = np.zeros((p,), dtype=np.float32)
    part = np.empty((E,), dtype=np.int32)
    inv_e = np.float32(p) / np.float32(E)
    inv_v = np.float32(p) / np.float32(V)
    ce = np.float32(ce)
    cv = np.float32(cv)
    eps = np.float32(eps)
    static = sc.balance == "static"
    for m in range(E):
        u, v = src[m], dst[m]
        mu = (~keep[:, u]).astype(np.float32)
        mv = (~keep[:, v]).astype(np.float32)
        base = w[0][m] * mu + w[1][m] * mv if w is not None else mu + mv
        norm = inv_e if static else np.float32(1.0) / (eps + (e_count.max() - e_count.min()))
        score = _fma32(cv * v_count, inv_v, _fma32(ce * e_count, norm, base))
        i = int(np.argmin(score))
        part[m] = i
        e_count[i] += 1
        v_count[i] += mu[i] + mv[i]
        keep[i, u] = True
        keep[i, v] = True
    return PartitionResult(part=part, num_parts=p, order=None if order is None else np.asarray(order))


def ebg_partition_np(
    graph: Graph,
    num_parts: int,
    *,
    alpha: float = 1.0,
    beta: float = 1.0,
    order: Optional[np.ndarray] = None,
    sort_edges: bool = True,
) -> PartitionResult:
    """EBV oracle — the generic loop with the stock "ebv" scorer."""
    return streaming_partition_np(
        graph, num_parts, "ebv", ce=alpha, cv=beta, order=order, sort_edges=sort_edges
    )
