"""Out-of-core streaming partition driver over an `EdgeShardStore`.

Feeds sharded edge files through the SAME chunked block-commit machinery
as the in-memory driver (`repro.core.streaming`), one block at a time:
the per-block score/commit arithmetic is the shared
`streaming._score_commit_loop` (dense path) or the fused
`ops.ebg_commit_block` kernel (bitset path), so `out_of_core ≡
in_memory` assignments are bit-identical by construction whenever the
edge stream order matches — and it does: `edgeshards.degree_sum_stream`
reproduces the §IV-C in-memory permutation exactly.

Partition state, not the edge list, is what stays resident:

  state_layout="replicated"  one device holds the whole membership table
                             (dense bool for "xla", packed uint32 bitset
                             for "ref"/"pallas" — p×⌈V/32⌉, 32x smaller;
                             "pallas" holds it in the commit kernel's
                             tiled layout for the whole stream, so a
                             per-block launch moves it HBM↔VMEM once
                             each way and nothing converts it).
  state_layout="sharded"     membership rows laid out along the worker
                             axis via jax.shard_map (mesh from
                             launch.mesh): each device holds p/d rows,
                             scores its rows locally, and an all_gather
                             of the per-block miss tables feeds the same
                             replicated commit loop — assignments
                             bit-identical to the replicated layout.

Memory: O(p·V/32 + block) for the bitset layout, O(p·V/d + block) per
device for the sharded layout; the edge list itself never materializes
(blocks stream from disk, the per-edge assignment is the only O(E) array
kept, int32).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import check_commit_mode, check_compute_backend
from repro.core import streaming
from repro.core.streaming import EdgeScorer, get_scorer, validate_edge_stream
from repro.core.types import PartitionResult
from repro.data.edgeshards import (
    EdgeShardStore,
    OrderedEdgeStream,
    degree_sum_stream,
    degrees_from_shards,
)
from repro.kernels import ops
from repro.kernels.ebg_commit import tiles_shape

STATE_LAYOUTS = ("replicated", "sharded")


def check_state_layout(layout) -> str:
    if layout not in STATE_LAYOUTS:
        raise ValueError(f"state_layout must be one of {STATE_LAYOUTS}, got {layout!r}")
    return layout


# ----------------------------------------------------- per-block jit steps


@functools.partial(
    jax.jit,
    static_argnames=("num_parts", "num_vertices", "backend", "weighted", "balance", "window"),
    donate_argnums=(0, 1, 2),
)
def _oc_block_step(
    keep, e_count, v_count, ub, vb, valb, wub, wvb, inv_e, ce, cv, eps, *,
    num_parts: int, num_vertices: int, backend: str, weighted: bool, balance: str,
    window: bool,
):
    """One streamed block against resident state — the same score/commit
    code paths as `streaming._streaming_chunked`, jitted per block with the
    state buffers donated (the carry never copies)."""
    p = num_parts
    inv_v = p / jnp.float32(num_vertices)
    if backend == "xla":
        mu0 = (~keep[:, ub]).astype(jnp.float32)
        mv0 = (~keep[:, vb]).astype(jnp.float32)
        e_count, v_count, parts = streaming._score_commit_loop(
            e_count, v_count, mu0, mv0, valb,
            wub if weighted else None, wvb if weighted else None,
            num_parts=p, weighted=weighted, balance=balance, window=window,
            ce=ce, cv=cv, eps=eps, inv_e=inv_e, inv_v=inv_v, ub=ub, vb=vb,
        )
        keep = keep.at[parts, ub].set(True, mode="drop")
        keep = keep.at[parts, vb].set(True, mode="drop")
        return keep, e_count, v_count, parts
    kw = dict(alpha=ce, beta=cv, inv_e=inv_e, inv_v=inv_v, eps=eps, balance=balance,
              wu=wub if weighted else None, wv=wvb if weighted else None, window=window)
    if backend == "pallas":  # `keep` stays in the kernel's tiled layout across blocks
        return ops.ebg_commit_tiles(keep, e_count, v_count, ub, vb, valb, num_parts=p, **kw)
    return ops.ebg_commit_block(keep, e_count, v_count, ub, vb, valb, impl=backend, **kw)


def _make_sharded_step(
    mesh, axis: str, *, num_parts: int, num_vertices: int, weighted: bool,
    balance: str, window: bool,
):
    """shard_map'd block step: membership rows sharded over `axis`, an
    extra per-device dump row absorbing commits owned by other devices.
    The per-block miss tables are all_gather'd so every device runs the
    IDENTICAL `_score_commit_loop` (replicated compute, sharded state) —
    assignments are bit-identical to the replicated dense path."""
    p = num_parts

    def step(keep_local, e_count, v_count, ub, vb, valb, wub, wvb, inv_e, ce, cv, eps):
        # keep_local: [p_local + 1, V] (last row = dump); counters replicated.
        p_local = keep_local.shape[0] - 1
        inv_v = p / jnp.float32(num_vertices)
        mu_l = (~keep_local[:p_local, ub]).astype(jnp.float32)
        mv_l = (~keep_local[:p_local, vb]).astype(jnp.float32)
        mu0 = jax.lax.all_gather(mu_l, axis, axis=0, tiled=True)  # [p, B]
        mv0 = jax.lax.all_gather(mv_l, axis, axis=0, tiled=True)
        e_count, v_count, parts = streaming._score_commit_loop(
            e_count, v_count, mu0, mv0, valb,
            wub if weighted else None, wvb if weighted else None,
            num_parts=p, weighted=weighted, balance=balance, window=window,
            ce=ce, cv=cv, eps=eps, inv_e=inv_e, inv_v=inv_v, ub=ub, vb=vb,
        )
        # Commit this device's rows; foreign rows (and the pad row p) land
        # in the local dump row.
        off = jax.lax.axis_index(axis) * p_local
        local = parts - off
        tgt = jnp.where((local >= 0) & (local < p_local), local, p_local)
        keep_local = keep_local.at[tgt, ub].set(True)
        keep_local = keep_local.at[tgt, vb].set(True)
        return keep_local, e_count, v_count, parts

    from jax.sharding import PartitionSpec as P

    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(axis), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2))


# --------------------------------------------------------------- the driver


@dataclasses.dataclass(frozen=True)
class OutOfCoreResult:
    """Out-of-core partition output. `result.part` is aligned with the
    streamed (possibly degree-sum-ordered) edge order; `result.order`
    carries the original store positions, so `part_in_input_order()`
    recovers store alignment. `edge_part_stream` re-streams
    (src, dst, part) blocks in partition order — what the streamed
    builder (`repro.graph.build_stream`) consumes."""

    result: PartitionResult
    e_count: np.ndarray  # [p] f32 committed edge counts
    v_count: np.ndarray  # [p] f32 committed new-vertex counts (= |V(i)|)
    covered: int  # vertices with degree > 0
    num_blocks: int
    edge_part_stream: Callable[[int], Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]]

    @property
    def replication_factor(self) -> float:
        """Paper RF: total vertex replicas over covered vertices — exact,
        from the commit counters alone (no part array scan)."""
        return float(self.v_count.sum() / max(self.covered, 1))


def partition_store(
    store: EdgeShardStore,
    num_parts: int,
    scorer: Union[str, EdgeScorer] = "ebv",
    *,
    ce: Optional[float] = None,
    cv: Optional[float] = None,
    eps: Optional[float] = None,
    block: int = 4096,
    sort_edges: Optional[bool] = None,
    compute_backend: str = "xla",
    commit: str = "frozen",
    state_layout: str = "replicated",
    mesh=None,
    degrees: Optional[np.ndarray] = None,
    ordered: Optional[OrderedEdgeStream] = None,
    order_workdir=None,
    validate: bool = True,
) -> OutOfCoreResult:
    """Partition a sharded on-disk edge store without materializing its
    edge list: blocks stream from disk through the chunked commit machinery
    (same arithmetic as `streaming_chunked_partition`, so results on a
    small graph are bit-identical to the in-memory driver given the same
    stream order — and the external degree-sum sort emits exactly the
    in-memory §IV-C order).

    `compute_backend` picks the membership state: "xla" dense bool,
    "ref"/"pallas" packed uint32 bitsets through `ops.ebg_commit_block`.
    `state_layout="sharded"` shards the dense membership rows over a mesh
    worker axis (requires compute_backend="xla"; `mesh` defaults to
    `launch.mesh.make_host_mesh()`); num_parts must divide evenly over
    the mesh devices. `commit` is the chunked commit mode ("window" makes
    any block size bit-identical to the one-edge scan). Pass precomputed
    `degrees` / an `ordered` stream to reuse external passes.
    """
    check_compute_backend(compute_backend)
    check_commit_mode(commit)
    check_state_layout(state_layout)
    sc = get_scorer(scorer)
    ce, cv, eps = sc.coefficients(ce, cv, eps)
    if sort_edges is None:
        sort_edges = sc.sort_edges
    if block < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    p = int(num_parts)
    V = store.num_vertices
    E = store.num_edges
    if V > np.iinfo(np.int32).max:
        raise ValueError(
            f"streaming state addresses vertices in int32: num_vertices={V} >= 2^31"
        )
    if degrees is None and (sort_edges or sc.weighted):
        degrees = degrees_from_shards(store)
    deg32 = degrees.astype(np.float32) if sc.weighted else None

    if sort_edges:
        if ordered is None:
            ordered = degree_sum_stream(store, degrees, workdir=order_workdir)
        block_iter = lambda b: ordered.iter_blocks(b)  # noqa: E731
    else:
        block_iter = lambda b: store.iter_blocks(b)  # noqa: E731

    window = commit == "window"
    if state_layout == "sharded":
        if compute_backend != "xla":
            raise ValueError(
                "state_layout='sharded' shards the dense membership table; "
                f"it requires compute_backend='xla', got {compute_backend!r}"
            )
        if mesh is None:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh()
        axis = mesh.axis_names[0]
        ndev = int(np.prod(mesh.devices.shape))
        if p % ndev != 0:
            raise ValueError(f"num_parts={p} must divide evenly over {ndev} mesh devices")
        step = _make_sharded_step(
            mesh, axis, num_parts=p, num_vertices=V, weighted=sc.weighted,
            balance=sc.balance, window=window,
        )
        keep = jnp.zeros((p + ndev, V), jnp.bool_)  # p rows + one dump row per device
    else:
        step = functools.partial(
            _oc_block_step, num_parts=p, num_vertices=V, backend=compute_backend,
            weighted=sc.weighted, balance=sc.balance, window=window,
        )
        if compute_backend == "xla":
            keep = jnp.zeros((p, V), jnp.bool_)
        elif compute_backend == "pallas":
            keep = jnp.zeros(tiles_shape(p, (V + 31) // 32), jnp.int32)
        else:
            keep = jnp.zeros((p, (V + 31) // 32), jnp.uint32)

    e_count = jnp.zeros((p,), jnp.float32)
    v_count = jnp.zeros((p,), jnp.float32)
    inv_e = jnp.float32(p) / jnp.float32(E)
    one = np.ones((block,), np.float32)
    zero_w = jnp.zeros((0,), jnp.float32)
    parts_out: list[np.ndarray] = []
    order_out: list[np.ndarray] = []
    num_blocks = 0

    for bsrc, bdst, bidx in block_iter(block):
        n = bsrc.shape[0]
        if validate:
            validate_edge_stream(bsrc, bdst, num_vertices=V)
        ub = np.zeros(block, np.int32)
        vb = np.zeros(block, np.int32)
        ub[:n] = bsrc
        vb[:n] = bdst
        valb = np.zeros(block, bool)
        valb[:n] = True
        if sc.weighted:
            # Same f32 formula as streaming.edge_weights_np, blockwise.
            du, dv = deg32[bsrc], deg32[bdst]
            tot = du + dv
            wub, wvb = one.copy(), one.copy()
            wub[:n] = np.float32(2.0) - du / tot
            wvb[:n] = np.float32(2.0) - dv / tot
            wub, wvb = jnp.asarray(wub), jnp.asarray(wvb)
        else:
            wub = wvb = zero_w
        keep, e_count, v_count, parts = step(
            keep, e_count, v_count, jnp.asarray(ub), jnp.asarray(vb), jnp.asarray(valb),
            wub, wvb, inv_e, jnp.float32(ce), jnp.float32(cv), jnp.float32(eps),
        )
        parts_out.append(np.asarray(parts[:n], np.int32))
        order_out.append(np.asarray(bidx, np.int64))
        num_blocks += 1

    part_np = np.concatenate(parts_out) if parts_out else np.zeros(0, np.int32)
    order_np = np.concatenate(order_out) if order_out else np.zeros(0, np.int64)
    e_np, v_np = np.asarray(e_count), np.asarray(v_count)
    covered = int((degrees > 0).sum()) if degrees is not None else int(
        np.unique(np.concatenate([s for s, _ in store.iter_shards()] or [np.zeros(0)])).size
    )
    result = PartitionResult(
        part=part_np, num_parts=p, order=order_np if sort_edges else None
    )

    def edge_part_stream(b: int) -> Iterator[tuple[np.ndarray, np.ndarray, np.ndarray]]:
        off = 0
        for s, d, _ in block_iter(b):
            yield s, d, part_np[off: off + s.shape[0]].astype(np.int64)
            off += s.shape[0]

    return OutOfCoreResult(
        result=result,
        e_count=e_np,
        v_count=v_np,
        covered=covered,
        num_blocks=num_blocks,
        edge_part_stream=edge_part_stream,
    )
