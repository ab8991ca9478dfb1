"""Chaos suite for the fault-tolerance layer (repro.resilience).

The load-bearing claim: a run that crashes at superstep s and resumes
from its checkpoint directory finishes bit-identical — values AND
BSPStats — to the run that never crashed, for fixpoint and
fixed-iteration programs on both sim drivers. Around it: deterministic
fault draws, retry-then-success serving, named timeout/shed failures,
circuit-breaker degradation parity, AsyncCheckpointer error surfacing,
and streaming-partitioner intake validation.
"""
import numpy as np
import pytest

from repro.api import GraphPipeline
from repro.core.streaming import validate_edge_stream
from repro.core.types import Graph
from repro.graph import engine as eng
from repro.resilience import (
    CircuitBreaker,
    FaultPlan,
    LoadShedError,
    RetryPolicy,
    TransientBackendError,
    WorkerCrashError,
    resume_bsp,
    run_bsp_resilient,
)

from tests.test_drivers import assert_stats_equal

# (program, run_bsp kwargs) — cc/reach need the symmetrized build,
# sssp roots at a source, pr runs its fixed-iteration mode.
CASES = (
    ("cc", dict()),
    ("sssp", dict(source=0)),
    ("pr", dict(max_supersteps=8)),
)


def _sub_for(built_small, name):
    _, sub_sym, sub_dir = built_small
    return sub_sym if name in ("cc", "reach") else sub_dir


def _kw(graph, name, kw):
    out = dict(kw)
    if name == "pr":
        out["num_vertices"] = graph.num_vertices
    return out


# ------------------------------------------------------------ fault plans


def test_fault_plan_draws_replay():
    plan = FaultPlan(seed=7, transient_error_prob=0.5)
    a = [plan.draw("x", i) for i in range(16)]
    b = [FaultPlan(seed=7, transient_error_prob=0.5).draw("x", i) for i in range(16)]
    assert a == b
    assert [plan.draw("y", i) for i in range(16)] != a  # streams are independent


def test_fault_plan_max_transient_ledger():
    plan = FaultPlan(seed=1, transient_error_prob=1.0, max_transient_faults=3)
    fired = [plan.transient_fault(i) for i in range(6)]
    assert fired == [True, True, True, False, False, False]
    # Replaying the same attempt indices gives the same answers.
    assert [plan.transient_fault(i) for i in range(6)] == fired


def test_fault_plan_targeting():
    plan = FaultPlan(seed=2, transient_error_prob=1.0, transient_target_backend="pallas")
    assert plan.transient_fault(0, backend="pallas")
    assert not plan.transient_fault(0, backend="xla")
    plan = FaultPlan(seed=2, transient_error_prob=1.0, transient_target_driver="batch")
    assert plan.transient_fault(0, driver="batch")
    assert not plan.transient_fault(0, driver="host")


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan(transient_error_prob=1.5)
    with pytest.raises(ValueError):
        FaultPlan(crash_at_superstep=-1)
    with pytest.raises(ValueError):
        FaultPlan(straggler_delay_s=-0.1)


# ----------------------------------------------------- checkpoint/resume


@pytest.mark.parametrize("driver", ("fused", "host"))
@pytest.mark.parametrize("name,kw", CASES, ids=[c[0] for c in CASES])
def test_crash_resume_bit_parity(built_small, tmp_path, name, kw, driver):
    """Crash at mid-run superstep s, resume from the checkpoint dir, and
    land bit-identical (values + stats) to the uninterrupted run."""
    graph = built_small[0]
    sub = _sub_for(built_small, name)
    kw = _kw(graph, name, kw)
    base_val, base_stats = eng.run_bsp(sub, name, driver=driver, **kw)
    crash_at = max(1, base_stats.supersteps // 2)
    ckpt_dir = tmp_path / f"{name}_{driver}"
    with pytest.raises(WorkerCrashError):
        eng.run_bsp(
            sub, name, driver=driver, checkpoint_every=1, ckpt_dir=ckpt_dir,
            fault_plan=FaultPlan(seed=3, crash_at_superstep=crash_at), **kw
        )
    val, stats = resume_bsp(sub, ckpt_dir=ckpt_dir)
    np.testing.assert_array_equal(np.asarray(val), np.asarray(base_val))
    assert_stats_equal(stats, base_stats)


@pytest.mark.parametrize("name,kw", CASES, ids=[c[0] for c in CASES])
def test_checkpointed_run_matches_plain(built_small, tmp_path, name, kw):
    """Checkpointing alone (no crash) must not perturb values or stats."""
    graph = built_small[0]
    sub = _sub_for(built_small, name)
    kw = _kw(graph, name, kw)
    base_val, base_stats = eng.run_bsp(sub, name, **kw)
    val, stats = eng.run_bsp(
        sub, name, checkpoint_every=2, ckpt_dir=tmp_path / name, **kw
    )
    np.testing.assert_array_equal(np.asarray(val), np.asarray(base_val))
    assert_stats_equal(stats, base_stats)


def test_resume_crash_resume_chain(built_small, tmp_path):
    """Two successive crashes, two resumes — still bit-identical. PageRank
    runs a fixed 6 supersteps, so both crash points are guaranteed live."""
    graph, _, sub = built_small
    kw = dict(max_supersteps=6, num_vertices=graph.num_vertices)
    base_val, base_stats = eng.run_bsp(sub, "pr", **kw)
    assert base_stats.supersteps == 6
    ckpt = tmp_path / "chain"
    with pytest.raises(WorkerCrashError):
        eng.run_bsp(sub, "pr", checkpoint_every=1, ckpt_dir=ckpt,
                    fault_plan=FaultPlan(crash_at_superstep=2), **kw)
    with pytest.raises(WorkerCrashError):
        resume_bsp(sub, ckpt_dir=ckpt, fault_plan=FaultPlan(crash_at_superstep=4))
    val, stats = resume_bsp(sub, ckpt_dir=ckpt)
    np.testing.assert_array_equal(np.asarray(val), np.asarray(base_val))
    assert_stats_equal(stats, base_stats)


def test_resume_without_checkpoint_raises(built_small, tmp_path):
    _, sub, _ = built_small
    with pytest.raises(FileNotFoundError):
        resume_bsp(sub, ckpt_dir=tmp_path / "nothing_here")


def test_resume_rejects_mismatched_subgraphs(built_small, tmp_path):
    """Resuming against a different partition is an error, not garbage."""
    graph, sub_sym, _ = built_small
    ckpt = tmp_path / "mismatch"
    with pytest.raises(WorkerCrashError):
        eng.run_bsp(sub_sym, "cc", checkpoint_every=1, ckpt_dir=ckpt,
                    fault_plan=FaultPlan(crash_at_superstep=1))
    from repro.core import PARTITIONERS
    from repro.graph.build import build_subgraphs

    other = build_subgraphs(graph, PARTITIONERS["ebg"](graph, 2), symmetrize=True)
    with pytest.raises(ValueError, match="checkpoint"):
        resume_bsp(other, ckpt_dir=ckpt)


def test_checkpoint_args_validated(built_small, tmp_path):
    _, sub, _ = built_small
    with pytest.raises(ValueError, match="checkpoint_every"):
        eng.run_bsp(sub, "cc", checkpoint_every=0, ckpt_dir=tmp_path / "x")
    with pytest.raises(ValueError, match="ckpt_dir"):
        eng.run_bsp(sub, "cc", checkpoint_every=2)
    with pytest.raises(ValueError, match="exchange_period"):
        run_bsp_resilient(sub, "cc", checkpoint_every=3, ckpt_dir=tmp_path / "y",
                          exchange_period=2)


def test_distributed_stepper_crash_hook(small_powerlaw):
    """fault_plan on make_distributed_stepper caps the superstep budget at
    the crash point and raises instead of silently finishing."""
    from repro.core import PARTITIONERS
    from repro.graph.build import build_subgraphs
    from repro.graph.engine import CC, init_cc, make_distributed_stepper, subgraphs_to_arrays
    from repro.launch.mesh import make_mesh

    res = PARTITIONERS["ebg"](small_powerlaw, 1)
    sub = build_subgraphs(small_powerlaw, res, symmetrize=True)
    mesh = make_mesh((1,), ("workers",))
    arrays, statics = subgraphs_to_arrays(sub)
    crashy = make_distributed_stepper(
        mesh, "workers", CC, statics, num_supersteps=10, inner_cap=100,
        fault_plan=FaultPlan(crash_at_superstep=1),
    )
    with pytest.raises(WorkerCrashError, match="superstep 1"):
        crashy(arrays, init_cc(sub))
    # Without a plan, the same config completes past the crash point.
    ok = make_distributed_stepper(
        mesh, "workers", CC, statics, num_supersteps=10, inner_cap=100
    )
    _, _, steps, _, _ = ok(arrays, init_cc(sub))
    assert int(steps) > 1


# --------------------------------------------------- async checkpointer


def test_async_checkpointer_surfaces_thread_errors(tmp_path):
    """Regression: a failed async save must raise on wait()/next save(),
    never be silently treated as durable."""
    from repro.checkpoint.ckpt import AsyncCheckpointer

    blocker = tmp_path / "not_a_dir"
    blocker.write_text("a file where the checkpoint dir should be")
    ckpt = AsyncCheckpointer(blocker)
    ckpt.save(0, {"x": np.zeros((4,), np.float32)})
    with pytest.raises(RuntimeError, match="checkpoint save"):
        ckpt.wait()
    # The error is consumed once surfaced; a save to a good dir recovers.
    ok = AsyncCheckpointer(tmp_path / "good")
    ok.save(0, {"x": np.zeros((4,), np.float32)})
    ok.save(1, {"x": np.ones((4,), np.float32)})
    ok.wait()


def test_async_checkpointer_raises_on_next_save(tmp_path):
    from repro.checkpoint.ckpt import AsyncCheckpointer

    blocker = tmp_path / "still_a_file"
    blocker.write_text("x")
    ckpt = AsyncCheckpointer(blocker)
    ckpt.save(0, {"x": np.zeros((2,), np.float32)})
    with pytest.raises(RuntimeError, match="checkpoint save"):
        ckpt.save(1, {"x": np.zeros((2,), np.float32)})


# ------------------------------------------------ edge intake validation


def test_validate_edge_stream_names_field_and_row():
    src = np.array([0, 1, 2], np.int32)
    with pytest.raises(ValueError, match=r"dst\[1\] = 9"):
        validate_edge_stream(src, np.array([1, 9, 0], np.int32), num_vertices=3)
    with pytest.raises(ValueError, match=r"src\[2\] = -1"):
        validate_edge_stream(np.array([0, 1, -1], np.int32),
                             np.array([1, 2, 0], np.int32), num_vertices=3)
    with pytest.raises(ValueError, match=r"self-loop at edge row 1"):
        validate_edge_stream(np.array([0, 1, 2], np.int32),
                             np.array([1, 1, 0], np.int32), num_vertices=3)
    with pytest.raises(ValueError, match=r"weights\[1\]"):
        validate_edge_stream(src, np.array([1, 2, 0], np.int32), num_vertices=3,
                             weights=np.array([1.0, np.nan, 1.0]))
    with pytest.raises(ValueError, match=r"weights\[0\]"):
        validate_edge_stream(src, np.array([1, 2, 0], np.int32), num_vertices=3,
                             weights=np.array([-2.0, 1.0, 1.0]))
    with pytest.raises(ValueError, match="same shape"):
        validate_edge_stream(src, np.array([1, 2], np.int32), num_vertices=3)
    # Clean stream passes.
    validate_edge_stream(src, np.array([1, 2, 0], np.int32), num_vertices=3,
                         weights=np.array([1.0, 0.5, 2.0]))


@pytest.mark.parametrize("partitioner", ("ebg", "ebg_chunked"))
def test_streaming_partitioners_reject_bad_streams(partitioner):
    from repro.core import PARTITIONERS

    bad_id = Graph(src=np.array([0, 1], np.int32),
                   dst=np.array([1, 5], np.int32), num_vertices=3)
    with pytest.raises(ValueError, match=r"dst\[1\]"):
        PARTITIONERS[partitioner](bad_id, 2)
    loops = Graph(src=np.array([0, 1], np.int32),
                  dst=np.array([1, 1], np.int32), num_vertices=3)
    with pytest.raises(ValueError, match="self-loop"):
        PARTITIONERS[partitioner](loops, 2)


# ------------------------------------------------------ resilient serving


@pytest.fixture(scope="module")
def serve_pipe(built_small):
    graph = built_small[0]
    return GraphPipeline(graph).partition("ebg", parts=4)


def test_serving_retry_then_success_parity(serve_pipe):
    """Two injected transient faults, then success — answers and stats
    bit-identical to a fault-free server."""
    plain = serve_pipe.serve(max_batch=4, max_delay_s=0.001)
    chaos = serve_pipe.serve(
        max_batch=4, max_delay_s=0.001,
        fault_plan=FaultPlan(seed=5, transient_error_prob=1.0, max_transient_faults=2),
        retry=RetryPolicy(max_retries=3),
    )
    for srv in (plain, chaos):
        for s in (0, 3, 7):
            srv.submit("sssp", s)
        srv.drain()
    for qid in range(3):
        a, b = plain.result(qid), chaos.result(qid)
        assert b.ok
        np.testing.assert_array_equal(a.values, b.values)
        assert_stats_equal(a.stats, b.stats)
    counters = chaos.resilience_counters()
    assert counters["retries"] == 2 and counters["faults_injected"] == 2
    assert counters["terminated"] == counters["answered"] == 3


def test_serving_retries_exhausted_named_failure(serve_pipe):
    srv = serve_pipe.serve(
        max_batch=2, max_delay_s=0.001,
        fault_plan=FaultPlan(seed=1, transient_error_prob=1.0),
        retry=RetryPolicy(max_retries=1),
        breaker=CircuitBreaker(threshold=100),  # pin level 0: exhaust, don't degrade
    )
    qid = srv.submit("cc")
    srv.drain()
    r = srv.result(qid)
    assert not r.ok and r.error == "retries_exhausted" and r.retries == 1
    assert srv.resilience_counters()["terminated"] == 1


def test_serving_deadline_expiry_named_timeout(serve_pipe):
    """A straggler delay pushes past the per-query deadline — the query
    terminates with the named timeout failure, not an answer."""
    srv = serve_pipe.serve(
        max_batch=4, max_delay_s=0.001, deadline_s=0.002,
        fault_plan=FaultPlan(seed=9, straggler_prob=1.0, straggler_delay_s=0.05),
    )
    qid = srv.submit("cc", at=0.0)
    srv.drain()
    r = srv.result(qid)
    assert not r.ok and r.error == "deadline_exceeded"
    assert r.latency_s <= 0.06


def test_serving_load_shed_bounded_queue(serve_pipe):
    srv = serve_pipe.serve(max_batch=8, max_delay_s=10.0, max_queue=2)
    qids = [srv.submit("cc") for _ in range(4)]
    for qid in qids[:2]:
        with pytest.raises(KeyError):
            srv.result(qid)  # still queued, not lost
    for qid in qids[2:]:
        r = srv.result(qid)
        assert not r.ok and r.error == "load_shed"
    assert len(srv.queue) == 2
    srv.drain()
    assert all(srv.result(q).ok for q in qids[:2])
    c = srv.resilience_counters()
    assert c["load_shed"] == 2 and c["terminated"] == 4


def test_queue_push_raises_load_shed():
    from repro.serve.queue import AdmissionQueue, Query

    q = AdmissionQueue(max_batch=4, max_queue=1)
    q.push(Query(qid=0, program="cc", source=None, t_arrival=0.0))
    with pytest.raises(LoadShedError, match="reject-newest"):
        q.push(Query(qid=1, program="cc", source=None, t_arrival=0.0))


def test_serving_breaker_degrades_backend_with_parity(serve_pipe):
    """Persistent faults targeting the pallas batch path walk the breaker
    down to xla — transparently, with bit-identical answers."""
    plain = serve_pipe.serve(max_batch=2, max_delay_s=0.001)
    srv = serve_pipe.serve(
        max_batch=2, max_delay_s=0.001, compute_backend="pallas",
        fault_plan=FaultPlan(seed=4, transient_error_prob=1.0,
                             transient_target_backend="pallas"),
        retry=RetryPolicy(max_retries=4),
        breaker=CircuitBreaker(threshold=1, max_level=2),
    )
    for s in (0, 3):
        plain.submit("sssp", s)
        srv.submit("sssp", s)
    plain.drain()
    srv.drain()
    for qid in range(2):
        a, b = plain.result(qid), srv.result(qid)
        assert b.ok
        np.testing.assert_array_equal(a.values, b.values)
        assert_stats_equal(a.stats, b.stats)
    c = srv.resilience_counters()
    assert c["breaker_level"] >= 1 and c["degraded_batches"] >= 1
    assert ("degrade", 0, 1) in srv.breaker.transitions


def test_serving_breaker_degrades_to_host_driver_with_parity(serve_pipe):
    """Faults targeting the batch driver (any backend) degrade all the
    way to the per-query host path — still bit-identical."""
    plain = serve_pipe.serve(max_batch=2, max_delay_s=0.001)
    srv = serve_pipe.serve(
        max_batch=2, max_delay_s=0.001, compute_backend="xla",
        fault_plan=FaultPlan(seed=6, transient_error_prob=1.0,
                             transient_target_driver="batch"),
        retry=RetryPolicy(max_retries=4),
        breaker=CircuitBreaker(threshold=1, max_level=1),
    )
    for s in (0, 5):
        plain.submit("bfs", s)
        srv.submit("bfs", s)
    plain.drain()
    srv.drain()
    for qid in range(2):
        a, b = plain.result(qid), srv.result(qid)
        assert b.ok
        np.testing.assert_array_equal(a.values, b.values)
        assert_stats_equal(a.stats, b.stats)
    assert srv.levels[srv.breaker.level] == ("xla", "host")


def test_serving_breaker_probe_recovery(serve_pipe):
    """After the faults stop, the probe re-tries the healthy level and the
    breaker promotes back to level 0."""
    srv = serve_pipe.serve(
        max_batch=2, max_delay_s=0.001,
        fault_plan=FaultPlan(seed=8, transient_error_prob=1.0, max_transient_faults=3),
        retry=RetryPolicy(max_retries=10),
        breaker=CircuitBreaker(threshold=2, probe_after=1, max_level=1),
    )
    for s in (0, 1, 2, 3, 4, 5):
        srv.submit("sssp", s)
        srv.drain()
    assert srv.breaker.level == 0
    assert ("degrade", 0, 1) in srv.breaker.transitions
    assert ("recover", 1, 0) in srv.breaker.transitions
    assert all(srv.result(q).ok for q in range(6))


def test_serving_malformed_batch_retries(serve_pipe):
    srv = serve_pipe.serve(
        max_batch=2, max_delay_s=0.001,
        fault_plan=FaultPlan(seed=12, malformed_batch_prob=1.0, ),
        retry=RetryPolicy(max_retries=0),
        breaker=CircuitBreaker(threshold=100),
    )
    qid = srv.submit("cc")
    srv.drain()
    r = srv.result(qid)
    assert not r.ok and r.error == "retries_exhausted"
    assert srv.resilience_counters()["malformed_batches"] == 1


def test_serving_chaos_trace_every_query_terminates(serve_pipe):
    """The acceptance-criteria trace: injected faults + stragglers over a
    real trace, zero unhandled exceptions, every query answered within
    the retry budget or terminated with a named failure."""
    from repro.serve.trace import synthetic_trace

    graph = serve_pipe.graph
    trace = synthetic_trace(graph, 48, rate_qps=4000.0,
                            mix=(("cc", 0.3), ("sssp", 0.7)), seed=7)
    srv = serve_pipe.serve(
        max_batch=4, max_delay_s=0.002,
        fault_plan=FaultPlan(seed=11, transient_error_prob=0.3,
                             straggler_prob=0.2, straggler_delay_s=0.005),
        retry=RetryPolicy(max_retries=4), max_queue=64, deadline_s=10.0,
    )
    report = srv.run_trace(trace)
    c = report.resilience
    assert c["terminated"] == 48
    assert c["answered"] + c["failed"] == 48
    for qid in range(48):
        r = srv.result(qid)
        if not r.ok:
            assert r.error in ("deadline_exceeded", "retries_exhausted", "load_shed")
            assert r.retries <= 4
    assert report.row()["resilience"]["terminated"] == 48


def test_serving_chaos_replay_is_deterministic(serve_pipe):
    """Same FaultPlan seed → identical fault schedule and counters."""
    def run():
        srv = serve_pipe.serve(
            max_batch=2, max_delay_s=0.001,
            fault_plan=FaultPlan(seed=21, transient_error_prob=0.5),
            retry=RetryPolicy(max_retries=6),
            breaker=CircuitBreaker(threshold=3),
        )
        for s in (0, 1, 2, 3):
            srv.submit("sssp", s)
            srv.drain()
        c = srv.resilience_counters()
        return c["faults_injected"], c["retries"], srv.breaker.transitions

    assert run() == run()


def test_pipeline_serve_exposes_failure_type():
    from repro.serve import QueryFailure  # re-export surface

    f = QueryFailure(qid=0, program="cc", source=None, error="load_shed",
                     t_arrival=0.0, t_done=0.0)
    assert not f.ok and f.latency_s == 0.0
