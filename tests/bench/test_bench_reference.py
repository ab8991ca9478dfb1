"""The plain references, the EBV check, and the lower-bound byte count,
against the program's own numpy oracles and hand counts."""
from __future__ import annotations

import numpy as np
import pytest
from scipy.sparse import csgraph

from bench import reference, run, work
from bench_tiny import REPO
from repro.api import GraphPipeline
from repro.core.streaming_np import ebg_partition_np
from repro.core.types import Graph
from repro.graph import algorithms as alg

N = 1 << 10


@pytest.fixture(scope="module")
def g500():
    graph500 = run.Bench(REPO).load("generators", "graph500")
    g = graph500.generate(5, scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)
    return g, Graph(src=g["src"], dst=g["dst"], num_vertices=N)


def test_hops_match_scipy_and_the_program_oracle(g500):
    g, graph = g500
    root = int(g["src"][0])
    adj = reference.csr(g["src"], g["dst"], N)
    got = reference.hops(adj, root)
    assert np.array_equal(got, csgraph.shortest_path(adj, unweighted=True, indices=root))
    oracle = alg.bfs_reference(graph, root).astype(np.float64)
    assert np.array_equal(got, np.where(oracle >= 2**31 - 1, np.inf, oracle))


def test_pagerank_matches_the_program_oracle(g500):
    g, graph = g500
    got = reference.pagerank(g["src"], g["dst"], N, damping=0.85, num_iters=20)
    np.testing.assert_allclose(got, alg.pagerank_reference(graph), rtol=1e-12)


def test_degree_sum_order_matches_the_program(g500):
    from repro.core.order import degree_sum_order

    g, graph = g500
    assert np.array_equal(reference.degree_sum_order(g["src"], g["dst"], N), degree_sum_order(graph))


def test_ebv_regret_is_zero_for_sequential_ebv(g500):
    g, graph = g500
    part = ebg_partition_np(graph, 8).part_in_input_order()
    assert reference.ebv_regret(g["src"], g["dst"], part, N, 8, block=1000).max() == 0.0
    window = GraphPipeline(graph).partition("ebg_chunked", parts=8, block=256, commit="window")
    assert np.array_equal(window.result.part_in_input_order(), part)


def test_ebv_regret_sees_frozen_commits_and_altered_answers(g500):
    g, graph = g500
    frozen = GraphPipeline(graph).partition("ebg_chunked", parts=8, block=256, commit="frozen")
    assert reference.ebv_regret(g["src"], g["dst"], frozen.result.part_in_input_order(), N, 8).max() > 0.5
    part = ebg_partition_np(graph, 8).part_in_input_order()
    order = reference.degree_sum_order(g["src"], g["dst"], N)
    mid = order[order.size // 2]
    altered = part.copy()
    altered[mid] = (altered[mid] + 1) % 8
    regret = reference.ebv_regret(g["src"], g["dst"], altered, N, 8)
    assert regret[mid] > 1e-4
    altered[mid] = 8
    assert np.isinf(reference.ebv_regret(g["src"], g["dst"], altered, N, 8)).all()


def test_lower_bound_bytes_by_hand():
    src = np.array([0, 1, 2, 0], np.int32)
    dst = np.array([1, 2, 0, 3], np.int32)
    # 4 arcs, 4 covered vertices of 6: ids 8 B (+4 B weight) per arc, 4 B in and out per vertex.
    assert work.engine_job_bytes(src, dst, 6, symmetrize=False, weighted=False) == 4 * 8 + 4 * 8
    assert work.engine_job_bytes(src, dst, 6, symmetrize=True, weighted=False) == 8 * 8 + 4 * 8
    assert work.engine_job_bytes(src, dst, 6, symmetrize=False, weighted=True) == 4 * 12 + 4 * 8
    # The program's tiny build holds as many real edge slots and master vertices.
    pipe = GraphPipeline(Graph(src=src, dst=dst, num_vertices=6)).partition(
        "ebg_chunked", parts=2, commit="window", block=4)
    for sym, arcs in ((False, 4), (True, 8)):
        sub = pipe.subgraphs_for(symmetrize=sym)
        assert int(np.asarray(sub.edge_mask).sum()) == arcs
        assert int((np.asarray(sub.is_master) & np.asarray(sub.vmask)).sum()) == 4
