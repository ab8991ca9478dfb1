"""The xla local stage reduces sorted edge slots by a segmented min-scan.

`engine._sorted_segment_min` must equal `jax.ops.segment_min` bit for bit
(min is exact in any order), and the engine built on it must return the
same values and `BSPStats` as the scatter-min pass it replaced, kept here
as `_relax_scatter`, in the fused, host and batched drivers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.graph.engine as eng
from tests.test_drivers import _nested_jaxprs, assert_stats_equal

MIN_PROGRAMS = ("cc", "sssp", "bfs", "reach")


def _segment_min_ref(data, key, num_segments):
    return jax.vmap(
        lambda d, k: jax.ops.segment_min(d, k, num_segments=num_segments, indices_are_sorted=True)
    )(data, key)


def _sorted_min_rows(data, key, num_segments):
    start, end = jax.vmap(functools.partial(eng._sorted_segments, num_segments=num_segments))(key)
    return jax.vmap(eng._sorted_segment_min)(data, start, end)


def _rows(rng, p, e, nseg, dtype, empty_ends=False):
    """[p, e] sorted keys in [0, nseg) with pad slots (key nseg-1) at the
    end of each row, and values of `dtype` with INF entries. `empty_ends`
    leaves vertex 0 and the last real vertex, nseg-2, without slots."""
    hi = nseg - 2 if empty_ends else nseg - 1
    lo = 1 if empty_ends else 0
    key = np.sort(rng.integers(lo, hi, (p, e)), axis=1)
    key[:, e - e // 5:] = nseg - 1
    if dtype == "int32":
        data = rng.integers(-1000, 1000, (p, e)).astype(np.int32)
        data[rng.random((p, e)) < 0.3] = np.int32(eng.INF_I32)
    else:
        data = rng.normal(size=(p, e)).astype(np.float32)
        data[rng.random((p, e)) < 0.2] = np.float32(eng.INF_F32)
        data[rng.random((p, e)) < 0.1] = np.inf
    return key.astype(np.int32), data


def _case(name):
    rng = np.random.default_rng(sum(map(ord, name)))
    nseg = 41
    if name == "int32_inf":
        return _rows(rng, 3, 200, nseg, "int32") + (nseg,)
    if name == "float32_inf":
        return _rows(rng, 3, 200, nseg, "float32") + (nseg,)
    if name == "empty_segments":
        return _rows(rng, 3, 60, nseg, "int32", empty_ends=True) + (nseg,)
    if name == "all_pad_row":
        key, data = _rows(rng, 3, 64, nseg, "float32")
        key[1] = nseg - 1
        data[1] = np.float32(eng.INF_F32)
        return key, data, nseg
    if name == "one_segment":
        key, data = _rows(rng, 2, 64, nseg, "int32")
        key[:] = 7
        return key, data, nseg
    if name == "single_slot":
        key, data = _rows(rng, 3, 1, nseg, "float32")
        key[:, 0] = [0, 5, nseg - 1]
        return key, data, nseg
    raise KeyError(name)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("case", ["int32_inf", "float32_inf", "empty_segments", "all_pad_row",
                                  "one_segment", "single_slot", "batch_vmap"])
def test_sorted_segment_min_matches_segment_min(case):
    if case == "batch_vmap":
        # A leading [B] axis on the values, keys shared: as in run_bsp_batch.
        key, data, nseg = _case("int32_inf")
        rng = np.random.default_rng(5)
        batch = np.stack([data, rng.permutation(data, axis=1), np.full_like(data, eng.INF_I32)])
        start, end = jax.vmap(functools.partial(eng._sorted_segments, num_segments=nseg))(key)
        got = jax.vmap(lambda d: jax.vmap(eng._sorted_segment_min)(d, start, end))(batch)
        want = jax.vmap(lambda d: _segment_min_ref(d, key, nseg))(batch)
    else:
        key, data, nseg = _case(case)
        got = _sorted_min_rows(jnp.asarray(data), jnp.asarray(key), nseg)
        want = _segment_min_ref(jnp.asarray(data), jnp.asarray(key), nseg)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    if case == "empty_segments":  # the cases the test is named for did occur
        empty = np.asarray(want) == np.iinfo(np.int32).max
        assert empty[:, 0].all() and empty[:, nseg - 2].all()


def test_sorted_segments_end_is_last_slot():
    key, _, nseg = _case("empty_segments")
    start, end = jax.vmap(functools.partial(eng._sorted_segments, num_segments=nseg))(key)
    for row, s, e in zip(key, np.asarray(start), np.asarray(end)):
        right = np.searchsorted(row, np.arange(nseg), side="right") - 1
        has = np.isin(np.arange(nseg), row)
        np.testing.assert_array_equal(e, np.where(has, right, -1))
        np.testing.assert_array_equal(s, np.r_[True, row[1:] != row[:-1]])


# ------------------------------------------- engine against the scatter pass


def _relax_scatter(prog, sub, v, segs=None):
    """The xla local pass as it was: segment_min, a scatter-min into the
    destination rows. `segs` is accepted and ignored."""
    nseg = sub.max_v + 1
    seg_min = jax.vmap(
        lambda d, s: jax.ops.segment_min(d, s, num_segments=nseg, indices_are_sorted=True)
    )
    data = jnp.take_along_axis(v, sub.lsrc, axis=1)
    w = eng._edge_addend(prog, sub.weight, v.dtype)
    if w is not None:
        data = eng._add_saturating(prog, data, w)
    data = jnp.where(sub.edge_mask, data, prog.inf)
    new = jnp.minimum(v, seg_min(data, sub.ldst))
    if prog.bidirectional:
        data2 = jnp.take_along_axis(v, sub.ldst_s, axis=1)
        w2 = eng._edge_addend(prog, sub.weight_s, v.dtype)
        if w2 is not None:
            data2 = eng._add_saturating(prog, data2, w2)
        data2 = jnp.where(sub.edge_mask_s, data2, prog.inf)
        new = jnp.minimum(new, seg_min(data2, sub.lsrc_s))
    return new


def _with_scatter_relax(fn):
    """fn() with the engine's xla pass swapped for `_relax_scatter`. JAX's
    caches are cleared on both sides, so neither path reuses a program
    traced with the other."""
    jax.clear_caches()
    orig = eng._relax_xla
    eng._relax_xla = _relax_scatter
    try:
        return fn()
    finally:
        eng._relax_xla = orig
        jax.clear_caches()


def _program_args(built_small, program):
    g, sub_sym, sub_dir = built_small
    prog = eng.get_program(program)
    sub = sub_sym if prog.bidirectional else sub_dir
    cov = g.covered_vertices()
    sources = [int(cov[np.argmax(g.degrees()[cov])]), int(cov[0]), int(cov[len(cov) // 2])]
    return prog, sub, sources


@pytest.mark.parametrize("driver", ["fused", "host"])
@pytest.mark.parametrize("program", MIN_PROGRAMS)
def test_engine_matches_scatter_pass(built_small, program, driver):
    prog, sub, sources = _program_args(built_small, program)
    src = sources[0] if prog.needs_source else None

    def run():
        val, stats = eng.run_bsp(sub, prog, source=src, driver=driver)
        return np.asarray(val), stats

    want, want_stats = _with_scatter_relax(run)
    got, got_stats = run()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert_stats_equal(got_stats, want_stats)
    assert got_stats.relax_passes > got_stats.supersteps  # the local loop iterated


@pytest.mark.parametrize("program", MIN_PROGRAMS)
def test_batch_matches_scatter_pass(built_small, program):
    prog, sub, sources = _program_args(built_small, program)
    kw = dict(sources=sources) if prog.needs_source else dict(batch=2)

    def run():
        vals, stats = eng.run_bsp_batch(sub, prog, **kw)
        return np.asarray(vals), stats

    want, want_stats = _with_scatter_relax(run)
    got, got_stats = run()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert len(got_stats) == len(want_stats)
    for a, b in zip(got_stats, want_stats):
        assert_stats_equal(a, b)


# --------------------------------------------------- no scatter in the pass


def _scatters_by_loop_depth(jaxpr, depth, out):
    """(primitive name, while depth) of every scatter, recursing through
    nested jaxprs; depth counts the while loops around the equation."""
    for eqn in jaxpr.eqns:
        if "scatter" in eqn.primitive.name:
            out.append((eqn.primitive.name, depth))
        if eqn.primitive.name == "while":
            out.append(("while", depth + 1))
        inner = depth + (eqn.primitive.name == "while")
        for v in eqn.params.values():
            for j in _nested_jaxprs(v):
                _scatters_by_loop_depth(j, inner, out)


def _fused_scatters(sub, program, source):
    prog, negate = eng._exec_view(eng.get_program(program))
    val = prog.init(sub, num_vertices=0, source=source)
    closed = jax.make_jaxpr(
        functools.partial(
            eng._fused_bsp, prog=prog, max_supersteps=8, inner_cap=100,
            exchange_period=1, tol=0.0, num_vertices=0, backend="xla",
        )
    )(sub, -val if negate else val)
    found = []
    _scatters_by_loop_depth(closed.jaxpr, 0, found)
    return found


@pytest.mark.parametrize("program", ["bfs", "cc"])
def test_fused_pass_loop_has_no_scatter(built_small, program):
    """The superstep loop is the outer while and the local fixpoint the
    inner one: its body, run every pass, holds no scatter. The segments'
    scatter runs once per run, outside both loops. The scatter pass, traced
    the same way, trips the pin."""
    _, sub, sources = _program_args(built_small, program)
    found = _fused_scatters(sub, program, sources[0])
    assert ("while", 2) in found, found  # the pass loop was traced
    in_pass = [name for name, depth in found if depth >= 2 and name != "while"]
    assert not in_pass, f"scatter inside the local pass loop: {in_pass}"
    assert [name for name, depth in found if depth == 0 and name != "while"], found
    old = _with_scatter_relax(lambda: _fused_scatters(sub, program, sources[0]))
    assert [name for name, depth in old if depth >= 2 and name != "while"]
