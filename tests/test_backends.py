"""Backend-parity suite: compute_backend in {"xla", "ref", "pallas"} must
agree on the engine programs (exact for int32 CC, atol=1e-5 for f32) and on
chunked-EBG assignments, plus segment-reduce edge cases the shape sweeps in
test_kernels.py miss (runs spanning blocks, all-padded tail blocks,
non-multiple-of-block edge streams)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PARTITIONERS, ebg_partition_chunked
from repro.graph import algorithms as alg
from repro.graph.build import build_subgraphs
from repro.kernels import ops, ref

BACKENDS = ("xla", "ref", "pallas")

# small_powerlaw / built_small fixtures live in conftest.py (shared with
# tests/test_drivers.py).


# ------------------------------------------------- segment-reduce edge cases


@pytest.mark.parametrize("op", ["min", "sum"])
def test_dst_run_spans_two_blocks(op):
    """One destination's edge run crosses the block_e boundary — the kernel
    must merge the two per-block partials through the accumulator."""
    rng = np.random.default_rng(11)
    E, block = 256, 128
    num_out = 33
    # dst 5 owns edges [0, 100); dst 9 owns [100, 256) — spans blocks 0 and 1.
    ldst = np.concatenate([np.full(100, 5), np.full(156, 9)]).astype(np.int32)
    lsrc = rng.integers(0, 32, E).astype(np.int32)
    w = rng.random(E).astype(np.float32) + 0.1
    val = (rng.random(num_out) * 10).astype(np.float32)
    fn = ops.segment_min_plus if op == "min" else ops.segment_sum_scaled
    a = fn(jnp.array(lsrc), jnp.array(ldst), jnp.array(w), jnp.array(val),
           num_out=num_out, impl="ref")
    b = fn(jnp.array(lsrc), jnp.array(ldst), jnp.array(w), jnp.array(val),
           num_out=num_out, impl="pallas", block_e=block, interpret=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("op", ["min", "sum"])
def test_all_padded_tail_block(op):
    """A tail block of nothing but identity-weight pad edges must be a no-op."""
    rng = np.random.default_rng(12)
    E, block = 256, 128
    num_out = 65
    identity = float(ref.INF) if op == "min" else 0.0
    ldst = np.concatenate([
        np.sort(rng.integers(0, 64, 128)),
        np.full(128, num_out - 1),  # pads point at the dump slot
    ]).astype(np.int32)
    lsrc = np.concatenate([rng.integers(0, 64, 128), np.zeros(128)]).astype(np.int32)
    w = np.concatenate([
        rng.random(128).astype(np.float32) + 0.1,
        np.full(128, identity, np.float32),
    ])
    val = (rng.random(num_out) * 10).astype(np.float32)
    fn = ops.segment_min_plus if op == "min" else ops.segment_sum_scaled
    a = fn(jnp.array(lsrc), jnp.array(ldst), jnp.array(w), jnp.array(val),
           num_out=num_out, impl="ref")
    b = fn(jnp.array(lsrc), jnp.array(ldst), jnp.array(w), jnp.array(val),
           num_out=num_out, impl="pallas", block_e=block, interpret=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)
    # real slots other than the dump row are untouched by the pad block
    np.testing.assert_allclose(np.asarray(b)[:64],
                               np.asarray(a)[:64], rtol=1e-5, atol=1e-6)


def test_ops_pad_non_multiple_edge_stream():
    """The ops wrappers own block padding: E need not divide block_e."""
    rng = np.random.default_rng(13)
    E, num_out = 100, 17
    ldst = np.sort(rng.integers(0, 16, E)).astype(np.int32)
    lsrc = rng.integers(0, 16, E).astype(np.int32)
    w = rng.random(E).astype(np.float32) + 0.1
    val = (rng.random(num_out) * 10).astype(np.float32)
    a = ops.segment_min_plus(jnp.array(lsrc), jnp.array(ldst), jnp.array(w),
                             jnp.array(val), num_out=num_out, impl="ref")
    b = ops.segment_min_plus(jnp.array(lsrc), jnp.array(ldst), jnp.array(w),
                             jnp.array(val), num_out=num_out, impl="pallas", block_e=512)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)
    # membership wrapper pads and slices back too
    keep = rng.random((4, 64)) < 0.3
    kb = ops.pack_keep_bits(jnp.array(keep))
    u = rng.integers(0, 64, E).astype(np.int32)
    v = rng.integers(0, 64, E).astype(np.int32)
    ma = ops.ebg_membership(kb, jnp.array(u), jnp.array(v), impl="ref")
    mb = ops.ebg_membership(kb, jnp.array(u), jnp.array(v), impl="pallas", block_e=64)
    assert mb.shape == (4, E)
    np.testing.assert_array_equal(np.asarray(mb), np.asarray(ma))


def test_explicit_interpret_override():
    """`impl="pallas"` must not re-sniff the backend for interpret: an
    explicit interpret= wins, so compiled Pallas is forceable off-TPU."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    assert ops._resolve_impl("pallas", None) == ("pallas", not on_tpu)
    assert ops._resolve_impl("pallas", True) == ("pallas", True)
    assert ops._resolve_impl("pallas", False) == ("pallas", False)
    assert ops._resolve_impl(None, None) == (ops._default_impl(), not on_tpu)
    assert ops._resolve_impl("ref", False)[0] == "ref"
    with pytest.raises(ValueError, match="impl"):
        ops._resolve_impl("xla_is_not_a_kernel_impl", None)


# --------------------------------------------------- engine backend parity


def test_cc_parity_across_backends(built_small):
    g, sub, _ = built_small
    base, stats_base = alg.connected_components(sub, compute_backend="xla")
    for backend in ("ref", "pallas"):
        got, stats = alg.connected_components(sub, compute_backend=backend)
        np.testing.assert_array_equal(got, base)  # exact int32 labels
        assert stats.supersteps == stats_base.supersteps
        np.testing.assert_array_equal(stats.messages_per_worker,
                                      stats_base.messages_per_worker)
    glob = alg.scatter_to_global(sub, base, g.num_vertices)
    ref_labels = alg.cc_reference(g)
    cov = g.covered_vertices()
    np.testing.assert_array_equal(glob[cov], ref_labels[cov])


def test_sssp_parity_across_backends(built_small):
    g, _, sub = built_small
    cov = g.covered_vertices()
    src_v = int(cov[np.argmax(g.degrees()[cov])])
    base, _ = alg.sssp(sub, src_v, compute_backend="xla")
    for backend in ("ref", "pallas"):
        got, _ = alg.sssp(sub, src_v, compute_backend=backend)
        np.testing.assert_allclose(got, base, atol=1e-5)


def test_pagerank_parity_across_backends(built_small):
    g, _, sub = built_small
    base, _ = alg.pagerank(sub, g.num_vertices, num_iters=10, compute_backend="xla")
    for backend in ("ref", "pallas"):
        got, _ = alg.pagerank(sub, g.num_vertices, num_iters=10, compute_backend=backend)
        np.testing.assert_allclose(got, base, atol=1e-5)


def test_ref_backend_parity_on_benchmark_fixture(tiny_powerlaw):
    """xla vs ref on the standard benchmark-family fixture (pallas-interpret
    parity runs on the smaller graph above to keep the suite fast)."""
    res = PARTITIONERS["ebg"](tiny_powerlaw, 8)
    sub_sym = build_subgraphs(tiny_powerlaw, res, symmetrize=True)
    sub_dir = build_subgraphs(tiny_powerlaw, res, symmetrize=False)
    cc_x, _ = alg.connected_components(sub_sym, compute_backend="xla")
    cc_r, _ = alg.connected_components(sub_sym, compute_backend="ref")
    np.testing.assert_array_equal(cc_r, cc_x)
    cov = tiny_powerlaw.covered_vertices()
    src_v = int(cov[np.argmax(tiny_powerlaw.degrees()[cov])])
    d_x, _ = alg.sssp(sub_dir, src_v, compute_backend="xla")
    d_r, _ = alg.sssp(sub_dir, src_v, compute_backend="ref")
    np.testing.assert_allclose(d_r, d_x, atol=1e-5)
    p_x, _ = alg.pagerank(sub_dir, tiny_powerlaw.num_vertices, num_iters=10, compute_backend="xla")
    p_r, _ = alg.pagerank(sub_dir, tiny_powerlaw.num_vertices, num_iters=10, compute_backend="ref")
    np.testing.assert_allclose(p_r, p_x, atol=1e-5)


def test_engine_rejects_unknown_backend(built_small):
    _, sub, _ = built_small
    with pytest.raises(ValueError, match="compute_backend"):
        alg.connected_components(sub, compute_backend="cuda")


def test_cc_kernel_backend_rejects_huge_vertex_ids(built_small):
    """int32 CC labels ride through f32 on the kernel backends — under FLAT
    addressing ids at or above 2^24 would corrupt silently, so the driver
    must refuse them (two-level addressing rank-compresses instead;
    tests/test_scale.py pins its clean passage)."""
    import dataclasses

    _, sub, _ = built_small
    big = dataclasses.replace(
        sub, gid=jnp.where(sub.vmask, sub.gid + (1 << 24), sub.gid), addressing="flat"
    )
    with pytest.raises(ValueError, match="vertex ids"):
        alg.connected_components(big, compute_backend="ref")
    # the xla path holds full int32 precision and keeps working
    alg.connected_components(big, compute_backend="xla", max_supersteps=2)


def test_batch_kernel_backend_rejects_huge_vertex_ids(built_small):
    """The same 2^24 guard must fire on the batched driver and the AOT
    compile path BEFORE any f32 remap (or any lowering work) happens."""
    import dataclasses

    from repro.graph.engine import compile_batch_executable, run_bsp_batch

    _, sub, _ = built_small
    big = dataclasses.replace(
        sub, gid=jnp.where(sub.vmask, sub.gid + (1 << 24), sub.gid), addressing="flat"
    )
    with pytest.raises(ValueError, match="vertex ids"):
        run_bsp_batch(big, "cc", batch=2, compute_backend="ref")
    with pytest.raises(ValueError, match="vertex ids"):
        compile_batch_executable(big, "cc", 2, compute_backend="ref")
    # xla batch keeps full int32 precision
    run_bsp_batch(big, "cc", batch=2, compute_backend="xla", max_supersteps=2)


def test_distributed_stepper_rejects_huge_vertex_ids(small_powerlaw):
    """Eagerly calling the distributed stepper with a kernel backend and
    ids >= 2^24 must raise the named ValueError before the shard_map runs;
    under jit tracing the guard defers to the pipeline's concrete
    pre-check instead of breaking the trace."""
    import dataclasses

    from repro.core import PARTITIONERS
    from repro.graph.build import build_subgraphs
    from repro.graph.engine import (
        CC,
        init_cc,
        make_distributed_stepper,
        subgraphs_to_arrays,
    )
    from repro.launch.mesh import make_mesh

    res = PARTITIONERS["ebg"](small_powerlaw, 1)
    sub = build_subgraphs(small_powerlaw, res, symmetrize=True)
    big = dataclasses.replace(
        sub, gid=jnp.where(sub.vmask, sub.gid + (1 << 24), sub.gid), addressing="flat"
    )
    mesh = make_mesh((1,), ("workers",))
    arrays, statics = subgraphs_to_arrays(big)
    stepper = make_distributed_stepper(
        mesh, "workers", CC, statics, num_supersteps=4, inner_cap=100,
        compute_backend="ref",
    )
    with pytest.raises(ValueError, match="vertex ids"):
        stepper(arrays, init_cc(big))
    # the guard is backend-scoped: xla runs huge ids at full precision
    stepper_x = make_distributed_stepper(
        mesh, "workers", CC, statics, num_supersteps=2, inner_cap=8
    )
    val, _, steps, _, _ = stepper_x(arrays, init_cc(big))
    assert int(steps) == 2 and val.shape == init_cc(big).shape


def test_pipeline_surfaces_compute_backend(small_powerlaw):
    from repro.api import GraphPipeline

    pipe = GraphPipeline(small_powerlaw).partition("ebg", parts=4)
    base = pipe.run("cc")
    other = pipe.run("cc", compute_backend="ref")
    np.testing.assert_array_equal(other.values, base.values)
    with pytest.raises(ValueError, match="compute_backend"):
        pipe.run("cc", compute_backend="nope")


def test_registry_compute_backend_capability():
    from repro.api import COMPUTE_BACKENDS, get_partitioner

    assert get_partitioner("ebg_chunked").compute_backends == COMPUTE_BACKENDS
    assert get_partitioner("ebg").compute_backends == ("xla",)


# --------------------------------------------------- fused EBG block commit


def _commit_oracle_dense(keep_bool, e_count, v_count, u, v, valid, alpha, beta, inv_e, inv_v):
    """The pre-fusion in-engine commit path: dense (p, V) bool membership +
    per-edge fori_loop with separate scatter updates (exactly the old
    `_ebg_chunked` block body). Independent representation (bool table vs
    packed bitset), same jnp arithmetic — the fused op must match it
    bit-for-bit."""
    import jax

    @jax.jit
    def run(keep, e_c, v_c, ub, vb, valb):
        p = keep.shape[0]
        miss_u = ~keep[:, ub]
        miss_v = ~keep[:, vb]
        memb = miss_u.astype(jnp.float32) + miss_v.astype(jnp.float32)

        def body(j, carry):
            e_c, v_c, parts = carry
            score = memb[:, j] + alpha * e_c * inv_e + beta * v_c * inv_v
            i = jnp.argmin(score).astype(jnp.int32)
            live = valb[j].astype(jnp.float32)
            e_c = e_c.at[i].add(live)
            v_c = v_c.at[i].add(live * memb[i, j])
            return e_c, v_c, parts.at[j].set(jnp.where(valb[j], i, p))

        e_c, v_c, parts = jax.lax.fori_loop(
            0, ub.shape[0], body, (e_c, v_c, jnp.zeros(ub.shape, jnp.int32))
        )
        keep = keep.at[parts, ub].set(True, mode="drop")
        keep = keep.at[parts, vb].set(True, mode="drop")
        return keep, e_c, v_c, parts

    keep, e_c, v_c, parts = run(
        jnp.asarray(keep_bool), jnp.asarray(e_count), jnp.asarray(v_count),
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(valid),
    )
    return np.asarray(ops.pack_keep_bits(keep)), np.asarray(e_c), np.asarray(v_c), np.asarray(parts)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("block", [1, 64, 256])
def test_ebg_commit_block_matches_oracle(impl, block):
    """The fused op (membership + argmin + balance commit + bitset update in
    one kernel) is bit-identical to the unfused per-edge semantics,
    including pad edges, shared endpoint words, and warm-start counters."""
    rng = np.random.default_rng(21)
    p, V = 4, 100
    keep = rng.random((p, V)) < 0.2
    kb = ops.pack_keep_bits(jnp.array(keep))
    e_c = jnp.asarray(rng.integers(0, 50, p).astype(np.float32))
    v_c = jnp.asarray(rng.integers(0, 30, p).astype(np.float32))
    u = rng.integers(0, V, block).astype(np.int32)
    v = rng.integers(0, V, block).astype(np.int32)
    valid = rng.random(block) < 0.9  # some pad edges sprinkled in
    alpha, beta, inv_e, inv_v = 1.0, 1.0, p / 500.0, p / float(V)
    got = ops.ebg_commit_block(
        kb, e_c, v_c, jnp.asarray(u), jnp.asarray(v), jnp.asarray(valid),
        alpha=alpha, beta=beta, inv_e=inv_e, inv_v=inv_v, impl=impl,
    )
    want = _commit_oracle_dense(keep, e_c, v_c, u, v, valid, alpha, beta, inv_e, inv_v)
    for g, w, name in zip(got, want, ("keep_bits", "e_count", "v_count", "parts")):
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)


def test_ebg_commit_block_ref_pallas_identical():
    rng = np.random.default_rng(22)
    p, V, B = 8, 64, 128
    kb = ops.pack_keep_bits(jnp.array(rng.random((p, V)) < 0.3))
    e_c = jnp.zeros((p,), jnp.float32)
    v_c = jnp.zeros((p,), jnp.float32)
    u = jnp.asarray(rng.integers(0, V, B).astype(np.int32))
    v = jnp.asarray(rng.integers(0, V, B).astype(np.int32))
    valid = jnp.ones((B,), bool)
    kw = dict(alpha=1.0, beta=1.0, inv_e=p / 1000.0, inv_v=p / float(V))
    a = ops.ebg_commit_block(kb, e_c, v_c, u, v, valid, impl="ref", **kw)
    b = ops.ebg_commit_block(kb, e_c, v_c, u, v, valid, impl="pallas", **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- chunked EBG bitset parity


@pytest.mark.parametrize("block", [1, 64, 256])
def test_chunked_bitset_matches_dense(small_powerlaw, block):
    """The packed-bitset score phase assigns every edge exactly as the dense
    bool membership table does, for ref and (interpreted) pallas kernels."""
    dense = ebg_partition_chunked(small_powerlaw, 4, block=block, compute_backend="xla")
    for backend in ("ref", "pallas"):
        bits = ebg_partition_chunked(small_powerlaw, 4, block=block, compute_backend=backend)
        np.testing.assert_array_equal(np.asarray(dense.part), np.asarray(bits.part))


def test_chunked_bitset_block1_equals_faithful(small_powerlaw):
    from repro.core import ebg_partition

    a = ebg_partition(small_powerlaw, 4)
    b = ebg_partition_chunked(small_powerlaw, 4, block=1, compute_backend="ref")
    np.testing.assert_array_equal(np.asarray(a.part), np.asarray(b.part))


def test_chunked_config_surfaces_backend(small_powerlaw):
    from repro.api import GraphPipeline

    base = GraphPipeline(small_powerlaw).partition("ebg_chunked", parts=4, block=64)
    bits = GraphPipeline(small_powerlaw).partition(
        "ebg_chunked", parts=4, block=64, compute_backend="ref"
    )
    np.testing.assert_array_equal(
        base.result.part_in_input_order(), bits.result.part_in_input_order()
    )
    with pytest.raises(ValueError):
        GraphPipeline(small_powerlaw).partition("ebg_chunked", parts=4, compute_backend="tpu")
    # the unblocked scan does not take the knob — naming it must error
    with pytest.raises(ValueError, match="does not use"):
        GraphPipeline(small_powerlaw).partition("ebg", parts=4, compute_backend="ref")


# ------------------------------------------------------- engine bugfix pins


def test_init_pr_mirrors_start_at_global_init(built_small):
    """init_pr: every present replica (masters AND mirrors) starts at 1/N;
    absent slots and the dump slot are 0 (pins the dead-store fix)."""
    from repro.graph.engine import init_pr

    g, _, sub = built_small
    val = np.asarray(init_pr(sub, g.num_vertices))
    vmask = np.asarray(sub.vmask)
    mirrors = vmask & ~np.asarray(sub.is_master)
    assert mirrors.any()  # the partition does replicate something
    np.testing.assert_allclose(val[:, :-1][mirrors], 1.0 / g.num_vertices)
    np.testing.assert_allclose(val[:, :-1][vmask], 1.0 / g.num_vertices)
    np.testing.assert_allclose(val[:, :-1][~vmask], 0.0)
    np.testing.assert_allclose(val[:, -1], 0.0)


def test_bspstats_max_mean_single_definition():
    """BSPStats.max_mean is the paper's Table-V metric — one definition,
    repro.core.metrics.max_mean_ratio."""
    from repro.core.metrics import max_mean_ratio
    from repro.graph.engine import BSPStats

    msgs = np.array([10, 20, 30, 60], np.int64)
    stats = BSPStats(
        supersteps=1,
        messages_per_worker=msgs,
        messages_per_step=np.array([120]),
        comp_work_per_worker=np.zeros(4, np.int64),
        inner_iters_per_step=np.ones((1, 4), np.int64),
        messages_per_step_worker=msgs[None, :],
    )
    assert stats.max_mean == max_mean_ratio(msgs) == pytest.approx(2.0)
    zero = BSPStats(1, np.zeros(4, np.int64), np.zeros(1, np.int64),
                    np.zeros(4, np.int64), np.ones((1, 4), np.int64),
                    np.zeros((1, 4), np.int64))
    assert zero.max_mean == max_mean_ratio(np.zeros(4)) == 1.0
