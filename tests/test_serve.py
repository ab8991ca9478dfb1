"""Serving-tier correctness (PR 6 tentpole).

The batched fused driver answers a [B] batch of point queries in ONE
dispatch over shared subgraph structure; convergence masking freezes
finished queries while stragglers run. The contract pinned here: every
query's values AND stats are bit-identical to a single-source `run_bsp`
call — across programs × drivers × compute backends, through the AOT
`BatchExecutable` path, and through the full `GraphQueryServer` loop
(admission queue, bucket padding, executable cache).
"""
import doctest

import numpy as np
import pytest

import repro.graph.engine as eng
import repro.serve.padding as padding
from repro import obs
from repro.graph import algorithms as alg
from repro.serve.cache import ExecutableCache
from repro.serve.padding import DEFAULT_BUCKETS, bucket_size, pad_batch_rows, pad_items, padding_waste
from repro.serve.queue import AdmissionQueue, Query
from repro.serve.trace import synthetic_trace

from tests.test_drivers import assert_stats_equal

SOURCE_PROGRAMS = ("sssp", "bfs")
FREE_PROGRAMS = ("cc", "reach")


def _sources(graph, n: int) -> list:
    """n covered vertices spanning the degree range (hub first, leaf last)
    so batched queries converge at different supersteps."""
    cov = graph.covered_vertices()
    order = cov[np.argsort(-graph.degrees()[cov])]
    idx = np.linspace(0, len(order) - 1, n).astype(int)
    return [int(v) for v in order[idx]]


def _singles(sub, prog, sources=None, batch=None, driver="fused", backend="xla", **kw):
    if sources is not None:
        return [
            eng.run_bsp(sub, prog, source=s, driver=driver, compute_backend=backend, **kw)
            for s in sources
        ]
    return [
        eng.run_bsp(sub, prog, driver=driver, compute_backend=backend, **kw)
        for _ in range(batch)
    ]


def assert_batch_matches_singles(vals, stats, singles):
    assert vals.shape[0] == len(singles)
    for b, (v1, s1) in enumerate(singles):
        np.testing.assert_array_equal(np.asarray(vals[b]), np.asarray(v1), err_msg=f"query {b}")
        assert_stats_equal(stats[b], s1)


# ------------------------------------------------------------- padding


def test_padding_doctests():
    """The bucket-boundary examples in the docstrings are executable."""
    failures, tried = doctest.testmod(padding)
    assert failures == 0 and tried > 0


def test_bucket_size_boundaries():
    assert [bucket_size(n) for n in (1, 2, 3, 4, 5, 8, 9, 64)] == [1, 2, 4, 4, 8, 8, 16, 64]
    with pytest.raises(ValueError, match="64"):
        bucket_size(65)
    with pytest.raises(ValueError):
        bucket_size(0)
    assert bucket_size(3, buckets=(2, 6)) == 6


def test_padding_waste():
    assert padding_waste(8, 8) == 0.0
    assert padding_waste(3, 4) == pytest.approx(0.25)
    assert padding_waste(5, 8) == pytest.approx(3 / 8)


def test_pad_items_repeats_last_real_item():
    assert pad_items([7, 9], 4) == [7, 9, 9, 9]
    assert pad_items([1], 1) == [1]
    with pytest.raises(ValueError):
        pad_items([], 4)


def test_pad_batch_rows():
    x = np.arange(6).reshape(3, 2)
    y = pad_batch_rows(x, 4)
    assert y.shape == (4, 2)
    np.testing.assert_array_equal(y[:3], x)
    np.testing.assert_array_equal(y[3], x[2])  # last real row repeated
    np.testing.assert_array_equal(pad_batch_rows(x, 3), x)  # already at bucket


# ------------------------------------------- source validation (satellite)


def test_init_source_out_of_range_names_argument(built_small):
    g, _, sub = built_small
    for bad in (-1, g.num_vertices, 10**7):
        with pytest.raises(ValueError, match="source"):
            alg.sssp(sub, bad, num_vertices=g.num_vertices)
        with pytest.raises(ValueError, match="source"):
            alg.bfs(sub, bad, num_vertices=g.num_vertices)


def test_batched_bad_source_fails_fast(built_small):
    """One bad source in a batch fails BEFORE any init is built or any
    dispatch happens — it cannot poison the rest of the batch."""
    g, _, sub = built_small
    good = _sources(g, 2)
    before = obs.counters()["engine.dispatch.batch"]
    with pytest.raises(ValueError, match=f"source={g.num_vertices}"):
        eng.run_bsp_batch(sub, "bfs", good + [g.num_vertices], num_vertices=g.num_vertices)
    assert obs.counters()["engine.dispatch.batch"] == before


def test_batch_init_argument_errors(built_small):
    _, sub, _ = built_small
    with pytest.raises(ValueError, match="sources"):
        eng.batch_init("sssp", sub)  # source-rooted without sources
    with pytest.raises(ValueError, match="batch"):
        eng.batch_init("cc", sub)  # source-free without a batch size
    assert eng.batch_init("cc", sub, batch=3).shape[0] == 3


def test_batched_driver_rejects_staleness(built_small):
    _, sub, _ = built_small
    with pytest.raises(ValueError, match="exchange_period"):
        eng.run_bsp_batch(sub, "cc", batch=2, exchange_period=3)


# ------------------------------------------------------- batched parity


@pytest.mark.parametrize("B", (1, 3, 8))
@pytest.mark.parametrize("prog", SOURCE_PROGRAMS + FREE_PROGRAMS)
@pytest.mark.parametrize("driver", ("fused", "host"))
def test_batch_matches_singles_xla(built_small, prog, B, driver):
    """values + per-query stats bit-identical to B single runs, vs BOTH
    single-query drivers (which are themselves pinned equal)."""
    g, sub_sym, sub_dir = built_small
    sub = sub_dir if prog in SOURCE_PROGRAMS else sub_sym
    srcs = _sources(g, B) if prog in SOURCE_PROGRAMS else None
    vals, stats = eng.run_bsp_batch(
        sub, prog, srcs, batch=B, num_vertices=g.num_vertices
    )
    singles = _singles(sub, prog, srcs, batch=B, driver=driver, num_vertices=g.num_vertices)
    assert_batch_matches_singles(vals, stats, singles)


@pytest.mark.parametrize("backend", ("ref", "pallas"))
@pytest.mark.parametrize("prog", ("cc", "sssp"))
def test_batch_matches_singles_kernel_backends(built_small, prog, backend):
    g, sub_sym, sub_dir = built_small
    sub = sub_dir if prog in SOURCE_PROGRAMS else sub_sym
    srcs = _sources(g, 3) if prog in SOURCE_PROGRAMS else None
    vals, stats = eng.run_bsp_batch(
        sub, prog, srcs, batch=3, num_vertices=g.num_vertices, compute_backend=backend
    )
    singles = _singles(sub, prog, srcs, batch=3, backend=backend, num_vertices=g.num_vertices)
    assert_batch_matches_singles(vals, stats, singles)


def test_batch_pagerank_fixed_iters(built_small):
    """f32 whole-graph program: batched lanes bitwise-match single runs."""
    g, sub, _ = built_small
    vals, stats = eng.run_bsp_batch(
        sub, "pr", batch=3, max_supersteps=10, num_vertices=g.num_vertices
    )
    singles = _singles(sub, "pr", batch=3, max_supersteps=10, num_vertices=g.num_vertices)
    assert_batch_matches_singles(vals, stats, singles)


def test_masking_lets_stragglers_run(built_small):
    """A batch whose queries converge at DIFFERENT supersteps: each query
    reports the steps IT paid (not the batch max), finished queries stop
    sending messages, and values still bitwise-match single runs."""
    g, _, sub = built_small
    srcs = _sources(g, 4)
    singles = _singles(sub, "bfs", srcs, num_vertices=g.num_vertices)
    step_counts = [s.supersteps for _, s in singles]
    assert len(set(step_counts)) > 1, step_counts  # precondition: real straggler
    vals, stats = eng.run_bsp_batch(sub, "bfs", srcs, num_vertices=g.num_vertices)
    assert [s.supersteps for s in stats] == step_counts
    assert_batch_matches_singles(vals, stats, singles)
    # A finished query's message series is exactly its single-run series:
    # masking zeroed its lanes afterwards and assembly truncated them away.
    fastest = int(np.argmin(step_counts))
    np.testing.assert_array_equal(
        stats[fastest].messages_per_step, singles[fastest][1].messages_per_step
    )


def test_batch_single_dispatch(built_small):
    g, _, sub = built_small
    srcs = _sources(g, 3)
    eng.run_bsp_batch(sub, "bfs", srcs, num_vertices=g.num_vertices)  # warm
    base = obs.counters()
    eng.run_bsp_batch(sub, "bfs", srcs, num_vertices=g.num_vertices)
    after = obs.counters()
    assert after["engine.dispatch.batch"] == base["engine.dispatch.batch"] + 1
    assert after["engine.dispatch.fused"] == base["engine.dispatch.fused"]
    assert after["engine.dispatch.host"] == base["engine.dispatch.host"]


# ------------------------------------------------------ AOT executables


def test_compiled_executable_matches_run_bsp_batch(built_small):
    g, _, sub = built_small
    srcs = _sources(g, 4)
    exe = eng.compile_batch_executable(sub, "bfs", 4, num_vertices=g.num_vertices)
    assert exe.compile_s > 0
    init = eng.batch_init("bfs", sub, srcs, num_vertices=g.num_vertices)
    vals, stats = exe.run(init)
    singles = _singles(sub, "bfs", srcs, num_vertices=g.num_vertices)
    assert_batch_matches_singles(vals, stats, singles)


def test_executable_rejects_wrong_batch(built_small):
    g, _, sub = built_small
    exe = eng.compile_batch_executable(sub, "bfs", 4, num_vertices=g.num_vertices)
    init = eng.batch_init("bfs", sub, _sources(g, 2), num_vertices=g.num_vertices)
    with pytest.raises(ValueError, match="pad the batch"):
        exe.run(init)


# ------------------------------------------------- queue / cache units


def _q(qid, t, program="bfs", source=0):
    return Query(qid=qid, program=program, source=source, t_arrival=t)


def test_admission_queue_full_flush():
    q = AdmissionQueue(max_batch=2, max_delay_s=1.0)
    q.push(_q(0, 0.0))
    assert q.pop_full() == []  # one query: lane not full yet
    q.push(_q(1, 0.1))
    (batch,) = q.pop_full()
    assert [x.qid for x in batch] == [0, 1]
    assert len(q) == 0


def test_admission_queue_deadline_flush():
    q = AdmissionQueue(max_batch=8, max_delay_s=0.5)
    q.push(_q(0, 0.0))
    q.push(_q(1, 0.2, program="cc", source=None))
    assert q.next_deadline() == pytest.approx(0.5)  # oldest head + delay
    assert q.pop_due(0.4) == []  # nobody has waited max_delay yet
    due = q.pop_due(0.5)
    assert [[x.qid for x in b] for b in due] == [[0]]  # bfs lane due, cc lane not
    assert len(q) == 1
    assert q.next_deadline() == pytest.approx(0.7)


def test_admission_queue_pop_all_and_program_lanes():
    q = AdmissionQueue(max_batch=8, max_delay_s=1.0)
    q.push(_q(0, 0.0, program="bfs"))
    q.push(_q(1, 0.0, program="sssp"))
    q.push(_q(2, 0.0, program="bfs"))
    batches = q.pop_all()
    assert sorted(sorted(x.qid for x in b) for b in batches) == [[0, 2], [1]]
    assert q.next_deadline() is None and len(q) == 0


def test_executable_cache_builds_once():
    cache = ExecutableCache()
    built = []
    for _ in range(5):
        cache.get(("bfs", 4), lambda: built.append(1) or object())
    assert len(built) == 1
    assert cache.misses == 1 and cache.hits == 4
    assert cache.hit_rate == pytest.approx(0.8)
    stats = cache.stats()
    assert stats["keys"] == 1 and stats["compiles_per_key_max"] == 1
    cache.get(("bfs", 8), lambda: object())
    assert cache.stats()["keys"] == 2
    assert cache.stats()["compiles_per_key_max"] == 1


# --------------------------------------------------------------- server


@pytest.fixture(scope="module")
def served_pipeline(small_powerlaw):
    from repro.api import GraphPipeline

    return GraphPipeline(small_powerlaw).partition("ebg", parts=4)


def test_server_answers_match_single_runs(served_pipeline):
    g = served_pipeline.graph
    srcs = _sources(g, 3)
    server = served_pipeline.serve(max_batch=4, max_delay_s=0.01)
    qids = [server.submit("bfs", s, at=0.0) for s in srcs]
    qid_cc = server.submit("cc", at=0.001)
    assert server.pump(now=1.0) == 4  # both lanes past deadline
    for qid, s in zip(qids, srcs):
        r = server.result(qid)
        single = served_pipeline.run("bfs", source=s)
        np.testing.assert_array_equal(r.values, single.values)  # padding lane discarded
        assert r.supersteps == single.stats.supersteps
        assert r.batch == 3 and r.bucket == 4  # padded 3 -> 4
        assert r.latency_s > 0
    np.testing.assert_array_equal(
        server.result(qid_cc).values, served_pipeline.run("cc").values
    )


def test_server_admission_validation(served_pipeline):
    server = served_pipeline.serve()
    with pytest.raises(ValueError, match="source"):
        server.submit("bfs", served_pipeline.graph.num_vertices)
    with pytest.raises(ValueError, match="whole-graph"):
        server.submit("cc", 5)
    assert len(server.queue) == 0  # rejected queries never enter the queue
    with pytest.raises(KeyError, match="still queued"):
        qid = server.submit("bfs", _sources(served_pipeline.graph, 1)[0])
        server.result(qid)


def test_server_full_batch_flushes_immediately(served_pipeline):
    srcs = _sources(served_pipeline.graph, 2)
    server = served_pipeline.serve(max_batch=2, max_delay_s=1e9)
    for s in srcs:
        server.submit("bfs", s, at=0.0)
    assert server.pump(now=0.0) == 2  # full lane fires with no deadline wait
    assert server.drain() == 0


def test_server_bucket_ladder_and_warm(served_pipeline):
    server = served_pipeline.serve(max_batch=8)
    assert server.buckets == (1, 2, 4, 8)
    compile_s = server.warm(["bfs"])
    assert compile_s > 0 and len(server.cache) == 4
    server.warm(["bfs"])  # second warm is all cache hits
    assert server.cache.stats()["compiles_per_key_max"] == 1
    with pytest.raises(ValueError, match="bucket"):
        served_pipeline.serve(max_batch=8, buckets=(1, 2, 4))


def test_run_trace_report(served_pipeline):
    g = served_pipeline.graph
    server = served_pipeline.serve(max_batch=4, max_delay_s=0.002)
    trace = synthetic_trace(g, 24, rate_qps=2000.0, mix=(("bfs", 0.7), ("cc", 0.3)), seed=1)
    assert len(trace) == 24 and all(t2 >= t1 for (t1, _, _), (t2, _, _) in zip(trace, trace[1:]))
    report = server.run_trace(trace)
    row = report.row()
    assert row["queries"] == 24
    assert row["throughput_qps"] > 0
    assert 0 <= row["latency_p50_s"] <= row["latency_p99_s"]
    assert 0 <= row["padding_waste"] < 1
    assert row["cache"]["compiles_per_key_max"] <= 1  # warm replay never recompiles
    assert row["batches"] >= 24 / 4
    # Trace answers are the same bits a cold single run produces.
    r = next(r for r in server._results.values() if r.program == "bfs")
    np.testing.assert_array_equal(
        r.values, served_pipeline.run("bfs", source=r.source).values
    )


# --------------------------------------------------------------- facade


def test_pipeline_run_batch_facade(served_pipeline):
    g = served_pipeline.graph
    srcs = _sources(g, 3)
    batch = served_pipeline.run_batch("bfs", srcs)
    assert len(batch) == 3 and batch.sources == tuple(srcs)
    singles = [served_pipeline.run("bfs", source=s) for s in srcs]
    for i in range(3):
        np.testing.assert_array_equal(batch.values[i], singles[i].values)
        assert_stats_equal(batch.stats[i], singles[i].stats)
        # query(i) is a full PipelineRun view, global scatter included.
        np.testing.assert_array_equal(
            batch.query(i).to_global(), singles[i].to_global()
        )
    np.testing.assert_array_equal(
        batch.supersteps_per_query, [s.stats.supersteps for s in singles]
    )


def test_pipeline_run_batch_validates_sources(served_pipeline):
    with pytest.raises(ValueError, match="source"):
        served_pipeline.run_batch("bfs", [0, -3])
