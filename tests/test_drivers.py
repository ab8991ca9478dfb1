"""Fused-vs-host driver equivalence (PR 3 tentpole).

The fused drivers run the whole BSP loop as one jitted lax.while_loop and
sync with the host once per run; the host drivers dispatch one jitted
superstep per Python iteration. Final values, superstep counts, and every
per-step / per-worker stat series must be identical across CC/SSSP/PR ×
compute backends — and the fused path must cost exactly one dispatch.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.graph.engine as eng
from repro import obs
from repro.graph import algorithms as alg

BACKENDS = ("xla", "ref", "pallas")


def assert_stats_equal(a: eng.BSPStats, b: eng.BSPStats):
    assert a.supersteps == b.supersteps
    np.testing.assert_array_equal(a.messages_per_worker, b.messages_per_worker)
    np.testing.assert_array_equal(a.messages_per_step, b.messages_per_step)
    np.testing.assert_array_equal(a.messages_per_step_worker, b.messages_per_step_worker)
    np.testing.assert_array_equal(a.inner_iters_per_step, b.inner_iters_per_step)
    np.testing.assert_array_equal(a.comp_work_per_worker, b.comp_work_per_worker)
    assert a.relax_passes == b.relax_passes


@pytest.mark.parametrize("backend", BACKENDS)
def test_cc_fused_matches_host(built_small, backend):
    _, sub, _ = built_small
    h, sh = alg.connected_components(sub, driver="host", compute_backend=backend)
    f, sf = alg.connected_components(sub, driver="fused", compute_backend=backend)
    np.testing.assert_array_equal(f, h)  # exact int32 labels
    assert_stats_equal(sf, sh)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sssp_fused_matches_host(built_small, backend):
    g, _, sub = built_small
    cov = g.covered_vertices()
    src_v = int(cov[np.argmax(g.degrees()[cov])])
    h, sh = alg.sssp(sub, src_v, driver="host", compute_backend=backend)
    f, sf = alg.sssp(sub, src_v, driver="fused", compute_backend=backend)
    np.testing.assert_array_equal(f, h)  # same op order -> bitwise equal f32
    assert_stats_equal(sf, sh)


@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_fused_matches_host(built_small, backend):
    g, _, sub = built_small
    h, sh = alg.pagerank(sub, g.num_vertices, num_iters=10, driver="host", compute_backend=backend)
    f, sf = alg.pagerank(sub, g.num_vertices, num_iters=10, driver="fused", compute_backend=backend)
    np.testing.assert_array_equal(f, h)
    assert_stats_equal(sf, sh)


def test_pagerank_tol_early_exit_matches(built_small):
    g, _, sub = built_small
    h, sh = alg.pagerank(sub, g.num_vertices, num_iters=50, tol=1e-4, driver="host")
    f, sf = alg.pagerank(sub, g.num_vertices, num_iters=50, tol=1e-4, driver="fused")
    assert sh.supersteps < 50  # tol actually fired
    np.testing.assert_array_equal(f, h)
    assert_stats_equal(sf, sh)


def test_bounded_staleness_fused_matches_host(built_small):
    _, sub, _ = built_small
    h, sh = alg.connected_components(sub, exchange_period=3, inner_cap=2, driver="host")
    f, sf = alg.connected_components(sub, exchange_period=3, inner_cap=2, driver="fused")
    np.testing.assert_array_equal(f, h)
    assert_stats_equal(sf, sh)


def test_fused_driver_single_dispatch(built_small):
    """The whole point of the fused driver: one device dispatch per run,
    vs one per superstep for the host driver."""
    g, sub, sub_dir = built_small
    # Warm the executable caches so the counted runs measure dispatches only.
    alg.connected_components(sub, driver="fused")
    base = obs.counters()
    _, stats = alg.connected_components(sub, driver="fused")
    seen = obs.counters() - base
    assert seen["engine.dispatch.fused"] == 1
    assert seen["engine.dispatch.host"] == 0  # fused path never host-steps

    base = obs.counters()
    _, stats_h = alg.connected_components(sub, driver="host")
    assert (obs.counters() - base)["engine.dispatch.host"] == stats_h.supersteps

    base = obs.counters()
    alg.pagerank(sub_dir, g.num_vertices, num_iters=5, driver="fused")
    assert (obs.counters() - base)["engine.dispatch.fused"] == 1


def _nested_jaxprs(v):
    if hasattr(v, "jaxpr"):  # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):  # Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _nested_jaxprs(x)


def _collect_converts(jaxpr, in_loop, out):
    """(eqn, in_loop) for every convert_element_type, recursing through
    nested jaxprs; in_loop flips once inside a while_loop's sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            out.append((eqn, in_loop))
        inside = in_loop or eqn.primitive.name == "while"
        for v in eqn.params.values():
            for j in _nested_jaxprs(v):
                _collect_converts(j, inside, out)


def _is_int_float_convert(eqn):
    src = eqn.invars[0].aval.dtype
    dst = eqn.params["new_dtype"]
    int_to_float = jnp.issubdtype(src, jnp.integer) and jnp.issubdtype(dst, jnp.floating)
    float_to_int = jnp.issubdtype(src, jnp.floating) and jnp.issubdtype(dst, jnp.integer)
    return int_to_float or float_to_int


def test_fused_no_inloop_remap(built_small):
    """Kernel backends run int32 programs in f32: the INF_I32 <-> INF_F32
    remap must be hoisted to the driver boundary (paid once per run), not
    traced into the fused while_loop body (paid once per superstep — the
    `reach` fused wall regression). bool->int32 converts for message
    counting are legitimate and must not trip this."""
    _, sub, _ = built_small
    prog = eng.get_program("reach")
    exec_prog, negate = eng._exec_view(prog)
    val = prog.init(sub, num_vertices=0, source=None)
    val = -val if negate else val
    closed = jax.make_jaxpr(
        functools.partial(
            eng._fused_bsp, prog=exec_prog, max_supersteps=8, inner_cap=4,
            exchange_period=1, tol=0.0, num_vertices=0, backend="ref",
        )
    )(sub, val)
    converts = []
    _collect_converts(closed.jaxpr, False, converts)
    remaps_outside = [e for e, in_loop in converts if not in_loop and _is_int_float_convert(e)]
    remaps_inside = [e for e, in_loop in converts if in_loop and _is_int_float_convert(e)]
    assert remaps_outside, "boundary remap vanished — is the trace still the int32 kernel path?"
    assert not remaps_inside, (
        "int32<->float32 remap traced inside the fused loop body: "
        + "; ".join(str(e) for e in remaps_inside)
    )


def test_messages_per_step_worker_consistent(built_small):
    """The new [steps, p] matrix marginalizes to the legacy fields."""
    _, sub, _ = built_small
    for driver in ("fused", "host"):
        _, stats = alg.connected_components(sub, driver=driver)
        m = stats.messages_per_step_worker
        assert m.shape == (stats.supersteps, sub.num_parts)
        np.testing.assert_array_equal(m.sum(axis=0), stats.messages_per_worker)
        np.testing.assert_array_equal(m.sum(axis=1), stats.messages_per_step)


def test_driver_validation(built_small):
    _, sub, _ = built_small
    with pytest.raises(ValueError, match="driver"):
        alg.connected_components(sub, driver="turbo")


def test_pipeline_surfaces_driver(small_powerlaw):
    from repro.api import GraphPipeline

    pipe = GraphPipeline(small_powerlaw).partition("ebg", parts=4)
    f = pipe.run("cc")  # fused is the default
    h = pipe.run("cc", driver="host")
    np.testing.assert_array_equal(f.values, h.values)
    assert_stats_equal(f.stats, h.stats)
    with pytest.raises(ValueError, match="driver"):
        pipe.run("cc", driver="turbo")
    with pytest.raises(ValueError, match="driver"):
        pipe.run("cc", mode="dist", driver="host", mesh=None)
