"""Scaling-past-2^24 suite: on-disk edge shards, the out-of-core
partition pipeline, the streamed builder, and two-level addressing.

Everything here runs on downscaled twins of the large-graph pipeline —
the oracles are the in-memory implementations, asserted bit-for-bit:

  * shard store roundtrip / external degrees / external §IV-C order
  * out-of-core partition == in-memory chunked partition (per scorer,
    backend, commit mode; sharded state layout == replicated)
  * streamed two-pass builder == vectorized in-memory builder (bitwise)
  * end-to-end: shards -> partition -> streamed build -> CC == in-memory
  * the 2^24 guard boundary: flat addressing raises at exactly 2^24,
    passes at 2^24 - 1; two-level passes both on every backend
  * vectorized generators == their legacy samplers (fixed seed)
  * resilient crash/resume carries the two-level value codec through the
    checkpoint
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import outofcore as oc
from repro.core.streaming import degree_sum_order, streaming_chunked_partition
from repro.data import edgeshards as es
from repro.graph import engine as eng
from repro.graph.build import build_subgraphs
from repro.graph.build_stream import build_subgraphs_stream
from repro.graph.generate import barabasi, barabasi_legacy, rmat

V, E, P = 1 << 10, 1 << 12, 4


@pytest.fixture(scope="module")
def graph():
    return rmat(V, E, seed=3)


@pytest.fixture(scope="module")
def store(graph, tmp_path_factory):
    path = tmp_path_factory.mktemp("shards") / "store"
    return es.write_graph(graph, path, shard_edges=500)  # >= 4 shards


# ------------------------------------------------------------ shard store


def test_store_roundtrip_and_manifest(graph, store):
    assert store.num_shards >= 4
    g2 = es.load_graph(store)
    np.testing.assert_array_equal(np.asarray(graph.src, np.int64), g2.src)
    np.testing.assert_array_equal(np.asarray(graph.dst, np.int64), g2.dst)
    assert g2.num_vertices == V
    # manifest: shard edge counts sum to E; every shard carries its
    # log2-bucketed degree histogram (#distinct endpoints, bucketed)
    assert sum(s["num_edges"] for s in store.shards) == graph.num_edges
    for s in store.shards:
        assert sum(s["degree_hist"]) >= 1


def test_iter_blocks_spans_shards(graph, store):
    ss, ii = [], []
    for s, d, i in store.iter_blocks(333):  # not a divisor of shard size
        assert s.shape == d.shape == i.shape
        ss.append(s)
        ii.append(i)
    np.testing.assert_array_equal(np.concatenate(ss), np.asarray(graph.src, np.int64))
    np.testing.assert_array_equal(np.concatenate(ii), np.arange(graph.num_edges))


def test_degrees_from_shards(graph, store):
    np.testing.assert_array_equal(es.degrees_from_shards(store), graph.degrees())


def test_external_degree_sum_order(graph, store, tmp_path):
    stream = es.degree_sum_stream(store, workdir=tmp_path / "order")
    try:
        assert stream.num_buckets >= 1
        np.testing.assert_array_equal(
            stream.permutation(), np.asarray(degree_sum_order(graph), np.int64)
        )
    finally:
        stream.cleanup()


def test_rmat_to_store_deterministic_and_valid(tmp_path):
    s1 = es.rmat_to_store(tmp_path / "r1", V, E, seed=7, shard_edges=700, chunk=900)
    s2 = es.rmat_to_store(tmp_path / "r2", V, E, seed=7, shard_edges=700, chunk=900)
    ga, gb = es.load_graph(s1), es.load_graph(s2)
    np.testing.assert_array_equal(np.asarray(ga.src), np.asarray(gb.src))
    np.testing.assert_array_equal(np.asarray(ga.dst), np.asarray(gb.dst))
    assert ga.num_edges == E
    key = np.asarray(ga.src, np.int64) * V + np.asarray(ga.dst, np.int64)
    assert np.all(np.diff(key) > 0)  # key-sorted, deduped, no self loops
    assert np.all(key // V != key % V)


# -------------------------------------------- out-of-core == in-memory


@pytest.mark.parametrize("commit", ("frozen", "window"))
@pytest.mark.parametrize(
    "scorer,backend",
    [("ebv", "xla"), ("ebv", "ref"), ("ebv", "pallas"), ("hdrf", "xla"), ("hdrf", "ref"),
     ("hdrf", "pallas"), ("greedy", "xla")],
)
def test_partition_store_matches_in_memory(graph, store, tmp_path, scorer, backend, commit):
    r_mem = streaming_chunked_partition(
        graph, P, scorer, block=128, compute_backend=backend, commit=commit
    )
    r_oc = oc.partition_store(
        store, P, scorer, block=128, compute_backend=backend, commit=commit,
        order_workdir=tmp_path / "order",
    )
    np.testing.assert_array_equal(np.asarray(r_mem.part), np.asarray(r_oc.result.part))
    np.testing.assert_array_equal(
        np.asarray(r_mem.part_in_input_order()),
        np.asarray(r_oc.result.part_in_input_order()),
    )
    assert r_oc.replication_factor >= 1.0


def test_sharded_state_layout_matches_replicated(store, tmp_path):
    r_rep = oc.partition_store(store, P, "ebv", block=128, order_workdir=tmp_path / "a")
    r_sh = oc.partition_store(
        store, P, "ebv", block=128, state_layout="sharded", order_workdir=tmp_path / "b"
    )
    np.testing.assert_array_equal(np.asarray(r_rep.result.part), np.asarray(r_sh.result.part))
    np.testing.assert_array_equal(r_rep.e_count, r_sh.e_count)
    np.testing.assert_array_equal(r_rep.v_count, r_sh.v_count)


def test_edge_part_stream_replays_every_edge(graph, store, tmp_path):
    r_oc = oc.partition_store(store, P, "ebv", block=128, order_workdir=tmp_path / "o")
    total = 0
    for s, d, pt in r_oc.edge_part_stream(200):
        assert s.shape == d.shape == pt.shape
        assert pt.min() >= 0 and pt.max() < P
        total += s.shape[0]
    assert total == graph.num_edges


# ------------------------------------------------------ streamed builder


@pytest.mark.parametrize("symmetrize", (False, True))
def test_build_stream_bitwise_equals_in_memory(graph, store, tmp_path, symmetrize):
    r_oc = oc.partition_store(store, P, "ebv", block=128, order_workdir=tmp_path / "o")
    part_in = r_oc.result.part_in_input_order().astype(np.int64)

    def factory():
        for s, d, i in store.iter_blocks(300):
            yield s, d, part_in[i]

    a = build_subgraphs(graph, r_oc.result, symmetrize=symmetrize)
    b = build_subgraphs_stream(factory, V, P, symmetrize=symmetrize)
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, (int, str)):
            assert va == vb, f.name
        else:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb), err_msg=f.name)
    assert b.addressing == "two_level"
    l2g = b.local_to_global
    assert l2g.dtype == np.int64 and l2g.shape == (P, b.max_v)


def test_end_to_end_out_of_core_cc_matches_in_memory(graph, store, tmp_path):
    """shards -> external order -> out-of-core partition -> streamed build
    -> CC, against the fully in-memory pipeline on the same graph."""
    r_mem = streaming_chunked_partition(graph, P, "ebv", block=128)
    sub_mem = build_subgraphs(graph, r_mem, symmetrize=True)
    val_mem, stats_mem = eng.run_bsp(sub_mem, "cc")

    r_oc = oc.partition_store(store, P, "ebv", block=128, order_workdir=tmp_path / "o")
    part_in = r_oc.result.part_in_input_order().astype(np.int64)

    def factory():
        for s, d, i in store.iter_blocks(300):
            yield s, d, part_in[i]

    sub_oc = build_subgraphs_stream(factory, V, P, symmetrize=True)
    val_oc, stats_oc = eng.run_bsp(sub_oc, "cc")
    np.testing.assert_array_equal(np.asarray(val_mem), np.asarray(val_oc))
    assert stats_mem.supersteps == stats_oc.supersteps


# ----------------------------------------------------- the 2^24 boundary


@pytest.fixture(scope="module")
def boundary_subs():
    """The same tiny subgraph set with gids shifted so max(gid) sits at
    exactly 2^24 - 1 (`below`) and exactly 2^24 (`at`)."""
    g = rmat(256, 1024, seed=3)
    res = streaming_chunked_partition(g, P, "ebv")
    sub = build_subgraphs(g, res, symmetrize=True)
    maxg = int(jnp.max(sub.gid))
    out = {}
    for name, top in (("below", (1 << 24) - 1), ("at", 1 << 24)):
        shift = top - maxg
        out[name] = dataclasses.replace(
            sub, gid=jnp.where(sub.vmask, sub.gid + shift, sub.gid)
        )
    return out


@pytest.mark.parametrize("backend", ("xla", "ref", "pallas"))
def test_flat_guard_boundary(boundary_subs, backend):
    """Flat addressing: ids up to 2^24 - 1 pass every backend; the first
    id at 2^24 raises the named ValueError on kernel backends only."""
    below = dataclasses.replace(boundary_subs["below"], addressing="flat")
    at = dataclasses.replace(boundary_subs["at"], addressing="flat")
    val, _ = eng.run_bsp(below, "cc", compute_backend=backend)
    assert int(jnp.max(jnp.where(below.vmask, val[:, : below.max_v], 0))) < 1 << 24
    if backend == "xla":
        eng.run_bsp(at, "cc", compute_backend=backend)  # xla is exact: no guard
    else:
        with pytest.raises(ValueError, match="vertex ids"):
            eng.run_bsp(at, "cc", compute_backend=backend)


@pytest.mark.parametrize("backend", ("xla", "ref", "pallas"))
def test_two_level_passes_boundary(boundary_subs, backend):
    """Two-level addressing: the same 2^24-id graph runs clean on every
    backend and agrees with the exact xla labels bit-for-bit."""
    at = boundary_subs["at"]
    assert at.addressing == "two_level"
    val, _ = eng.run_bsp(at, "cc", compute_backend=backend)
    val_x, _ = eng.run_bsp(at, "cc", compute_backend="xla")
    np.testing.assert_array_equal(np.asarray(val), np.asarray(val_x))


def test_two_level_bfs_value_bound(boundary_subs):
    """BFS on big gids: hop counts stay tiny, so two-level runs clean on
    kernel backends where the flat gid guard would refuse."""
    at = boundary_subs["at"]
    val_r, _ = eng.run_bsp(at, "bfs", source=0, compute_backend="ref")
    val_x, _ = eng.run_bsp(at, "bfs", source=0, compute_backend="xla")
    np.testing.assert_array_equal(np.asarray(val_r), np.asarray(val_x))


def test_builder_rejects_past_engine_ceiling():
    with pytest.raises(ValueError, match="engine ceiling"):
        build_subgraphs_stream(lambda: iter(()), (1 << 31) + 8, P)


# ------------------------------------------------- vectorized generators


@pytest.mark.parametrize("v,attach,seed", [(200, 8, 0), (500, 4, 7), (300, 16, 2)])
def test_barabasi_matches_legacy(v, attach, seed):
    g1 = barabasi(v, attach, seed=seed)
    g2 = barabasi_legacy(v, attach, seed=seed)
    np.testing.assert_array_equal(np.asarray(g1.src), np.asarray(g2.src))
    np.testing.assert_array_equal(np.asarray(g1.dst), np.asarray(g2.dst))
    assert g1.num_vertices == g2.num_vertices


# ------------------------------------------- codec through checkpoints


def test_resilient_resume_restores_value_codec(boundary_subs, tmp_path):
    """Crash/resume on a 2^24-id two-level run: the rank codec rides in
    the checkpoint, so the resumed kernel-backend run decodes to the
    uninterrupted labels."""
    from repro.resilience import FaultPlan, WorkerCrashError
    from repro.resilience.bsp import resume_bsp

    at = boundary_subs["at"]
    base_val, base_stats = eng.run_bsp(at, "cc", compute_backend="ref")
    crash_at = max(1, base_stats.supersteps // 2)
    ckpt = tmp_path / "ckpt"
    with pytest.raises(WorkerCrashError):
        eng.run_bsp(
            at, "cc", compute_backend="ref", checkpoint_every=1, ckpt_dir=ckpt,
            fault_plan=FaultPlan(seed=3, crash_at_superstep=crash_at),
        )
    val, stats = resume_bsp(at, ckpt_dir=ckpt, compute_backend="ref")
    np.testing.assert_array_equal(np.asarray(val), np.asarray(base_val))
    assert stats.supersteps == base_stats.supersteps
