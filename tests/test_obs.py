"""`repro.obs`: spans and counters off by default, on under `recording()` or
a profiler session, and the counts the program's instrumentation pins —
re-traces per run, dispatches, relaxation passes and the device program's
scope names."""
import functools
import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.graph.engine as eng
from repro import obs
from repro.api import GraphPipeline

SCOPES = ("bsp.local", "bsp.exchange", "bsp.apply")


@pytest.fixture(autouse=True)
def _fresh():
    obs.clear()
    yield
    obs.clear()


def test_off_records_nothing():
    assert not obs.enabled()
    with obs.span("test.off") as s:
        obs.count("test.off", 3)
    assert s is None  # the shared no-op context
    assert obs.spans() == [] and obs.instants() == []
    assert obs.counters()["test.off"] == 3  # counters count either way


def test_recording_nests_spans_under_their_parents():
    with obs.recording():
        assert obs.enabled()
        with obs.span("test.root"):
            with obs.span("test.a"):
                with obs.span("test.a1"):
                    pass
            with obs.span("test.b"):
                pass
        with obs.span("test.second_root"):
            pass
    assert not obs.enabled()
    by = {s.name: s for s in obs.spans()}
    assert [s.name for s in obs.spans()] == [
        "test.a1", "test.a", "test.b", "test.root", "test.second_root"]
    assert by["test.root"].parent_id == 0 and by["test.second_root"].parent_id == 0
    assert by["test.a"].parent_id == by["test.b"].parent_id == by["test.root"].span_id
    assert by["test.a1"].parent_id == by["test.a"].span_id
    assert len({s.span_id for s in obs.spans()}) == 5
    for s in obs.spans():
        assert s.t0_ns <= s.t1_ns
    assert by["test.root"].t0_ns <= by["test.a"].t0_ns and by["test.b"].t1_ns <= by["test.root"].t1_ns


def test_counters_and_instants_add_up():
    obs.count("test.n")
    with obs.recording():
        obs.count("test.n", 2)
        obs.count("test.n", 5)
        obs.count("test.m")
    obs.count("test.n", 7)
    assert obs.counters()["test.n"] == 15 and obs.counters()["test.m"] == 1
    assert obs.counters()["test.never"] == 0
    got = [(i.name, i.n) for i in obs.instants()]
    assert got == [("test.n", 2), ("test.n", 5), ("test.m", 1)]
    assert sum(i.n for i in obs.instants() if i.name == "test.n") == 7
    ts = [i.t_ns for i in obs.instants()]
    assert ts == sorted(ts)
    obs.clear()
    assert obs.counters()["test.n"] == 0 and obs.instants() == []


def test_profiler_session_records_into_its_xplane(tmp_path):
    """The check behind `enabled()` reads JAX's private profiler state
    (`jax._src.profiler._profile_state.profile_session`): pinned here."""
    from jax._src import profiler as jax_profiler
    from jax.profiler import ProfileData

    assert jax_profiler._profile_state.profile_session is None and not obs.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert jax_profiler._profile_state.profile_session is not None and obs.enabled()
        with obs.span("test.traced"):
            with obs.span("test.traced_inner"):
                jnp.arange(8).sum().block_until_ready()
            obs.count("test.traced")
    finally:
        jax.profiler.stop_trace()
    assert not obs.enabled()
    assert [s.name for s in obs.spans()] == ["test.traced_inner", "test.traced"]
    assert [i.name for i in obs.instants()] == ["test.traced"]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}
    assert {"test.traced", "test.traced_inner"} <= host


# ---------------------------------------------------- the program's spans


def test_partition_spans(tiny_powerlaw):
    with obs.recording():
        res = GraphPipeline(tiny_powerlaw).partition("ebg_chunked", parts=4).result
        res.part_in_input_order()
    spans = obs.spans()
    (root,) = [s for s in spans if s.name == "partition.run"]
    children = [s.name for s in spans if s.parent_id == root.span_id]
    assert children == ["partition.validate", "partition.order", "partition.upload",
                        "partition.commit"]
    (fetch,) = [s for s in spans if s.name == "partition.fetch"]
    assert fetch.parent_id == 0 and fetch.t0_ns >= root.t1_ns


def test_engine_spans_share_their_root(built_small_pipe):
    pipe, src = built_small_pipe
    pipe.run("bfs", source=src)  # warm
    obs.clear()
    with obs.recording():
        pipe.run("bfs", source=src)
    spans = obs.spans()
    (root,) = [s for s in spans if s.name == "engine.run"]
    assert root.parent_id == 0
    children = [s.name for s in spans if s.parent_id == root.span_id]
    assert children == ["engine.prepare", "engine.dispatch", "engine.fetch", "engine.fetch"]
    assert [(i.name, i.n) for i in obs.instants()] == [("engine.dispatch.fused", 1)]


@pytest.fixture(scope="module")
def built_small_pipe(small_powerlaw):
    pipe = GraphPipeline(small_powerlaw).partition("ebg", parts=4)
    cov = small_powerlaw.covered_vertices()
    return pipe, int(cov[len(cov) // 2])


def test_warm_sim_runs_do_not_retrace(built_small_pipe):
    pipe, src = built_small_pipe
    pipe.run("bfs", source=src)
    pipe.run("pr", num_iters=3)
    base = obs.counters()
    for _ in range(3):
        pipe.run("bfs", source=src)
        pipe.run("pr", num_iters=3)
    seen = obs.counters() - base
    assert seen["engine.trace"] == 0
    assert seen["engine.dispatch.fused"] == 6


# ------------------------------------------------------------ relax passes


def _counted_local_loop(prog, sub, val, inner_cap):
    """A copy of the engine's XLA local fixpoint loop that returns its trip
    count: the passes the local stage made over the edge slots."""
    relax = functools.partial(eng._relax_xla, prog, sub)

    def body(carry):
        v, _, trips = carry
        new = relax(v)
        return new, jnp.any(new != v, axis=1), trips + 1

    p = val.shape[0]
    carry = (val, jnp.ones((p,), bool), jnp.int32(0))
    _, _, trips = jax.lax.while_loop(
        lambda c: jnp.any(c[1]) & (c[2] < inner_cap), body, carry)
    return int(trips)


def _passes_step_by_step(prog, sub, val, steps, inner_cap, num_vertices):
    """Replays a run one superstep at a time, counting the local stage's
    passes before each superstep (a sweep is one pass)."""
    total = 0
    for _ in range(steps):
        if prog.local == "fixpoint":
            total += _counted_local_loop(prog, sub, val, inner_cap)
        else:
            total += 1
        val, *_ = eng._jit_superstep_sim(prog, sub, val, inner_cap, True, val, num_vertices)
    return total


@pytest.mark.parametrize("program,inner_cap", [("cc", 10_000), ("cc", 2), ("sssp", 10_000),
                                               ("pr", 10_000)])
def test_relax_passes_counts_the_local_loop(built_small, program, inner_cap):
    g, sub_sym, sub_dir = built_small
    sub = sub_sym if program == "cc" else sub_dir
    prog = eng.get_program(program)
    kw = dict(num_vertices=g.num_vertices, inner_cap=inner_cap)
    if prog.needs_source:
        kw["source"] = int(g.covered_vertices()[0])
    _, stats = eng.run_bsp(sub, prog, **kw)
    init = prog.init(sub, num_vertices=g.num_vertices, source=kw.get("source"))
    want = _passes_step_by_step(prog, sub, init, stats.supersteps, inner_cap, g.num_vertices)
    assert stats.relax_passes == want
    if prog.local == "fixpoint":
        assert stats.relax_passes > stats.supersteps  # more than one pass a superstep
    else:
        assert stats.relax_passes == stats.supersteps
    _, host = eng.run_bsp(sub, prog, driver="host", **kw)
    assert host.relax_passes == want


def test_relax_passes_per_query_in_a_batch(built_small):
    g, sub, _ = built_small
    cov = g.covered_vertices()
    sources = [int(cov[0]), int(cov[len(cov) // 2]), int(cov[-1])]
    _, stats = eng.run_bsp_batch(sub, "bfs", sources, num_vertices=g.num_vertices)
    for s, got in zip(sources, stats):
        _, one = eng.run_bsp(sub, "bfs", source=s, num_vertices=g.num_vertices)
        assert got.relax_passes == one.relax_passes > 0


# ---------------------------------------------------- device program names


def test_fused_program_carries_stage_scopes(built_small):
    """PageRank has ops in all three stages (`bsp.apply` is empty where a
    program's apply is "none")."""
    g, _, sub = built_small
    prog = eng.get_program("pr")
    val = prog.init(sub, num_vertices=g.num_vertices)
    text = eng._fused_bsp.lower(
        sub, val, prog=prog, max_supersteps=4, inner_cap=8, exchange_period=1, tol=0.0,
        num_vertices=g.num_vertices, backend="xla",
    ).as_text(debug_info=True)
    for name in SCOPES:
        assert name in text, name


def test_commit_carries_its_scope(tiny_powerlaw):
    from repro.core import streaming

    E = tiny_powerlaw.num_edges - tiny_powerlaw.num_edges % 64
    src = jnp.asarray(tiny_powerlaw.src[:E])
    dst = jnp.asarray(tiny_powerlaw.dst[:E])
    for backend in ("xla", "pallas"):
        text = streaming._streaming_chunked.lower(
            src, dst, jnp.ones((E,), bool), jnp.zeros((0,)), jnp.zeros((0,)), jnp.float32(E),
            num_parts=4, num_vertices=tiny_powerlaw.num_vertices, block=64, backend=backend,
            weighted=False, balance="static", ce=1.0, cv=1.0, eps=1.0, window=True,
        ).as_text(debug_info=True)
        assert "ebg.commit" in text, backend


# ------------------------------------- dist mode on four virtual devices


@pytest.fixture(scope="module")
def dist_counts():
    """Counts from mode='dist' runs on four virtual CPU devices. XLA fixes
    the device count at its first start, so they run in a subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", """
import json
from repro import obs
from repro.api import GraphPipeline
from repro.graph.generate import make_graph
from repro.launch.mesh import make_host_mesh

g = make_graph('tiny_powerlaw')
pipe = GraphPipeline(g).partition('ebg', parts=4)
mesh = make_host_mesh(4)
out = {'traces': [], 'passes': {}}
for _ in range(3):
    base = obs.counters()['engine.trace']
    pipe.run('pr', mode='dist', mesh=mesh, num_iters=5)
    out['traces'].append(obs.counters()['engine.trace'] - base)
for prog, kw in (('pr', dict(num_iters=5)), ('cc', dict(max_supersteps=30))):
    out['passes'][prog] = [pipe.run(prog, **kw).stats.relax_passes,
                           pipe.run(prog, mode='dist', mesh=mesh, **kw).stats.relax_passes]
with obs.recording():
    pipe.run('pr', mode='dist', mesh=mesh, num_iters=5)
out['spans'] = [[s.name, s.parent_id] for s in obs.spans()]
out['root'] = [s.span_id for s in obs.spans() if s.name == 'engine.run']
text = pipe.lower(mesh=mesh, program='pr', num_supersteps=2).lowered.as_text(debug_info=True)
out['scopes'] = [n for n in ('bsp.local', 'bsp.exchange', 'bsp.apply') if n in text]
print(json.dumps(out))
"""],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_dist_run_retraces_once(dist_counts):
    """Each mode='dist' run builds a new jit of a new stepper closure, so it
    traces again. Pinned so that the fix changes this test on purpose."""
    assert dist_counts["traces"] == [1, 1, 1]


def test_dist_spans_and_passes_match_sim(dist_counts):
    (root,) = dist_counts["root"]
    assert dist_counts["spans"] == [["engine.prepare", root], ["engine.dispatch", root],
                                    ["engine.fetch", root], ["engine.run", 0]]
    for prog, (sim, dist) in dist_counts["passes"].items():
        assert sim == dist > 0, prog
    assert dist_counts["passes"]["pr"] == [5, 5]


def test_dist_stepper_carries_stage_scopes(dist_counts):
    assert dist_counts["scopes"] == list(SCOPES)
