"""`VertexProgram` unification suite (PR 4 tentpole).

One generic superstep / fused driver / host driver / distributed stepper
run every program. Pins: the new programs (BFS hop-count, max-label
reachability) against numpy host oracles across all compute backends and
both sim drivers; the max-combine negation path; distributed PageRank
(previously rejected) matching sim-mode bit-for-bit with full stats
equality — including the previously-zeroed `comp_work_per_worker`; and the
program registry surface.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import repro.graph.engine as eng
from repro.graph import algorithms as alg
from repro.kernels import ops, ref

BACKENDS = ("xla", "ref", "pallas")

I32_INF = 2**31 - 1


def _source(g):
    cov = g.covered_vertices()
    return int(cov[np.argmax(g.degrees()[cov])])


def assert_stats_equal(a: eng.BSPStats, b: eng.BSPStats):
    assert a.supersteps == b.supersteps
    np.testing.assert_array_equal(a.messages_per_worker, b.messages_per_worker)
    np.testing.assert_array_equal(a.messages_per_step, b.messages_per_step)
    np.testing.assert_array_equal(a.messages_per_step_worker, b.messages_per_step_worker)
    np.testing.assert_array_equal(a.inner_iters_per_step, b.inner_iters_per_step)
    np.testing.assert_array_equal(a.comp_work_per_worker, b.comp_work_per_worker)


# ------------------------------------------------------------- registry


def test_registry_stock_programs():
    assert eng.program_names() == ("bfs", "cc", "pr", "reach", "sssp")
    assert eng.get_program("pagerank") is eng.PR
    assert eng.get_program("connected_components") is eng.CC
    assert eng.get_program("reachability") is eng.REACH
    assert eng.get_program(eng.BFS) is eng.BFS  # instances pass through
    with pytest.raises(ValueError, match="unknown program"):
        eng.get_program("not_a_program")
    with pytest.raises(ValueError, match="already registered"):
        eng.register_program(dataclasses.replace(eng.CC, aliases=()))


def test_rejected_registration_leaves_registry_untouched():
    """A later-alias collision must not half-register the program."""
    bad = dataclasses.replace(eng.CC, name="_pr4_tmp", aliases=("cc",))
    with pytest.raises(ValueError, match="already registered"):
        eng.register_program(bad)
    assert "_pr4_tmp" not in eng.PROGRAMS
    with pytest.raises(ValueError, match="unknown program"):
        eng.get_program("_pr4_tmp")


def test_pagerank_default_steps_is_twenty(built_small):
    """A bare facade/engine PageRank run keeps the classic 20-power-iteration
    default (not the generic 200-superstep fixpoint budget)."""
    g, _, sub = built_small
    assert eng.PR.default_steps == 20
    _, stats = alg.run_program(sub, eng.PR, num_vertices=g.num_vertices)
    assert stats.supersteps == 20


def test_pagerank_without_num_vertices_raises(built_small):
    g, _, sub = built_small
    with pytest.raises(ValueError, match="num_vertices"):
        alg.run_program(sub, eng.PR)


def test_source_rooted_program_without_source_raises(built_small):
    _, _, sub = built_small
    for prog in (eng.SSSP, eng.BFS):
        with pytest.raises(ValueError, match="source"):
            alg.run_program(sub, prog)


def test_registry_lookup_is_case_insensitive(built_small):
    """Registered keys are lowercased to match get_program's lookup, so a
    MixedCase custom name stays reachable."""
    mixed = dataclasses.replace(eng.CC, name="Pr4CaseCheck", aliases=())
    try:
        eng.register_program(mixed)
        assert eng.get_program("Pr4CaseCheck") is mixed
        assert eng.get_program("pr4casecheck") is mixed
    finally:
        eng.PROGRAMS.pop("pr4casecheck", None)


def test_vertex_program_validation():
    with pytest.raises(ValueError, match="combine"):
        eng.VertexProgram(name="x", dtype="int32", combine="xor")
    with pytest.raises(ValueError, match="dtype"):
        eng.VertexProgram(name="x", dtype="int8")
    with pytest.raises(ValueError, match="sweep"):
        eng.VertexProgram(name="x", dtype="float32", combine="sum", local="fixpoint")
    with pytest.raises(ValueError, match="sum"):
        eng.VertexProgram(name="x", dtype="float32", apply="pagerank", combine="min")


def test_program_identities():
    assert int(eng.CC.identity) == I32_INF
    assert int(eng.REACH.identity) == -I32_INF
    assert float(eng.PR.identity) == 0.0
    assert float(eng.SSSP.identity) == float(eng.INF_F32)


def test_exchange_period_rejected_for_sweep_programs(built_small):
    g, _, sub = built_small
    with pytest.raises(ValueError, match="exchange_period"):
        alg.pagerank(sub, g.num_vertices, exchange_period=2)


# --------------------------------------------- new programs vs host oracles


@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_matches_oracle(built_small, backend):
    g, _, sub = built_small
    src_v = _source(g)
    ref_hops = alg.bfs_reference(g, src_v)
    cov = g.covered_vertices()
    hops, stats = alg.bfs(sub, src_v, compute_backend=backend)
    glob = alg.scatter_to_global(sub, hops, g.num_vertices)
    np.testing.assert_array_equal(glob[cov].astype(np.int64), ref_hops[cov])
    assert stats.supersteps >= 1 and stats.total_messages > 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_reachability_matches_oracle(built_small, backend):
    g, sub, _ = built_small
    ref_lab = alg.reachability_reference(g)
    cov = g.covered_vertices()
    lab, stats = alg.reachability(sub, compute_backend=backend)
    glob = alg.scatter_to_global(sub, lab, g.num_vertices)
    np.testing.assert_array_equal(glob[cov].astype(np.int64), ref_lab[cov])
    assert stats.total_messages > 0


@pytest.mark.parametrize("prog", ["bfs", "reach"])
def test_new_programs_fused_matches_host(built_small, prog):
    g, sub_sym, sub_dir = built_small
    if prog == "bfs":
        run = lambda d: alg.bfs(sub_dir, _source(g), driver=d)
    else:
        run = lambda d: alg.reachability(sub_sym, driver=d)
    h, sh = run("host")
    f, sf = run("fused")
    np.testing.assert_array_equal(f, h)  # exact int32
    assert_stats_equal(sf, sh)


def test_reach_bounded_staleness_same_fixpoint(built_small):
    """Max-combine is monotone too: bounded staleness converges to the same
    fixpoint through the negation path."""
    _, sub, _ = built_small
    a, _ = alg.reachability(sub)
    b, stats = alg.reachability(sub, exchange_period=3, inner_cap=2)
    np.testing.assert_array_equal(a, b)
    assert stats.supersteps >= 1


def test_reach_labels_partition_like_cc(built_small):
    """Reachability labels induce the same vertex partition as CC labels
    (both are per-component constants on the undirected view)."""
    g, sub, _ = built_small
    cov = g.covered_vertices()
    cc = alg.scatter_to_global(sub, alg.connected_components(sub)[0], g.num_vertices)[cov]
    rc = alg.scatter_to_global(sub, alg.reachability(sub)[0], g.num_vertices)[cov]
    assert len(np.unique(cc)) == len(np.unique(rc))
    # same grouping: each CC label maps to exactly one reach label
    pairs = {(int(a), int(b)) for a, b in zip(cc, rc)}
    assert len(pairs) == len(np.unique(cc))


def test_run_program_accepts_names_and_instances(built_small):
    _, sub, _ = built_small
    by_name, _ = alg.run_program(sub, "cc")
    by_inst, _ = alg.run_program(sub, eng.CC)
    np.testing.assert_array_equal(by_name, by_inst)


def test_custom_program_through_generic_driver(built_small):
    """The abstraction holds for programs the repo never shipped: min-plus
    over DOUBLED edge weights is SSSP with distances scaled by 2."""
    g, _, sub = built_small
    src_v = _source(g)
    base, _ = alg.sssp(sub, src_v)
    doubled = dataclasses.replace(eng.SSSP, name="sssp2x")
    sub2 = dataclasses.replace(sub, weight=sub.weight * 2.0, weight_s=sub.weight_s * 2.0)
    got, _ = alg.run_program(sub2, doubled, source=src_v)
    fin = base < 1e38
    np.testing.assert_allclose(got[fin], base[fin] * 2.0)


# ----------------------------------------------------- facade integration


def test_pipeline_runs_new_programs(small_powerlaw):
    from repro.api import GraphPipeline

    pipe = GraphPipeline(small_powerlaw).partition("ebg", parts=4)
    cov = small_powerlaw.covered_vertices()
    b = pipe.run("bfs")  # default source = highest-degree covered vertex
    assert b.program == "bfs"
    glob = b.to_global()
    ref_hops = alg.bfs_reference(small_powerlaw, pipe.default_source())
    np.testing.assert_array_equal(glob[cov].astype(np.int64), ref_hops[cov])
    r = pipe.run("reach")
    glob = r.to_global()
    np.testing.assert_array_equal(
        glob[cov].astype(np.int64), alg.reachability_reference(small_powerlaw)[cov]
    )
    # reach symmetrizes by default (bidirectional), bfs keeps direction
    assert r.subgraphs is pipe.subgraphs_for(symmetrize=True)
    assert b.subgraphs is pipe.subgraphs_for(symmetrize=False)


# ------------------------------------------------------- max-combine kernel


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_segment_max_matches_numpy(impl):
    """ops.segment_max — the max-combine entry point — must agree with the
    numpy scatter-max oracle; it runs on the min-plus kernels via negation."""
    rng = np.random.default_rng(31)
    E, num_out = 200, 33
    ldst = np.sort(rng.integers(0, num_out - 1, E)).astype(np.int32)
    lsrc = rng.integers(0, num_out - 1, E).astype(np.int32)
    w = np.where(rng.random(E) < 0.2, float(ref.INF), 0.0).astype(np.float32)  # some pads
    val = ((rng.random(num_out) - 0.5) * 10).astype(np.float32)
    want = val.copy()
    live = w < float(ref.INF)
    np.maximum.at(want, ldst[live], val[lsrc[live]])
    got = ops.segment_max(
        jnp.array(lsrc), jnp.array(ldst), jnp.array(w), jnp.array(val),
        num_out=num_out, impl=impl, block_e=64,
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


# ------------------------------------------------- distributed (subprocess)


def test_distributed_any_program_matches_sim():
    """Distributed PageRank (previously `mode='dist' supports min-semiring
    programs only`), BFS, and reachability all run through the ONE
    distributed stepper and match sim-mode values AND stats exactly —
    including `comp_work_per_worker`, which dist mode used to zero out.
    Needs >1 device, so it runs in a subprocess (same mechanism as
    tests/test_system.py)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", """
import numpy as np
from repro.api import GraphPipeline
from repro.graph.generate import make_graph
from repro.launch.mesh import make_host_mesh

g = make_graph('tiny_powerlaw')
pipe = GraphPipeline(g).partition('ebg', parts=4)
mesh = make_host_mesh(4)

def stats_eq(a, b, what):
    assert a.supersteps == b.supersteps, what
    np.testing.assert_array_equal(a.messages_per_worker, b.messages_per_worker, err_msg=what)
    np.testing.assert_array_equal(a.messages_per_step_worker, b.messages_per_step_worker, err_msg=what)
    np.testing.assert_array_equal(a.inner_iters_per_step, b.inner_iters_per_step, err_msg=what)
    np.testing.assert_array_equal(a.comp_work_per_worker, b.comp_work_per_worker, err_msg=what)
    assert a.relax_passes == b.relax_passes, what
    assert a.comp_work_per_worker.sum() > 0, what  # the dist zeroing bug

sim = pipe.run('pr', num_iters=10)
dist = pipe.run('pr', mode='dist', mesh=mesh, num_iters=10)
np.testing.assert_array_equal(sim.values, dist.values)
stats_eq(sim.stats, dist.stats, 'pr')

for prog in ('cc', 'bfs', 'reach'):
    s = pipe.run(prog)
    d = pipe.run(prog, mode='dist', mesh=mesh, num_supersteps=30)
    np.testing.assert_array_equal(s.values, d.values, err_msg=prog)
    stats_eq(s.stats, d.stats, prog)

low = pipe.lower(mesh=mesh, program='pr', num_supersteps=2)
assert low.compiled.memory_analysis() is not None and low.program == 'pr'
print('OK')
"""],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout
