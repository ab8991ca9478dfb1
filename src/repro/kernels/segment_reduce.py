"""Pallas TPU kernel: destination-sorted segmented reduction over edges.

This is the compute hot spot of the subgraph-centric BSP engine: one local
relaxation is `out[dst] ⊕= val[src] (+ w)` over all edges of the subgraph,
with ⊕ ∈ {min, +}.

TPU adaptation (see DESIGN.md §3): TPUs have no efficient random scatter, so
the engine sorts edges by destination ONCE at build time and the kernel
performs a *segmented* reduction:

  - the vertex-value vector `val` stays resident in VMEM for the whole grid
    (EBG's vertex balance is what bounds max_v per device — the paper's
    balance objective directly controls this kernel's VMEM footprint);
  - edges are streamed from HBM in blocks of BLOCK_E (src, dst, w);
  - within a block, equal-dst runs are rank-compressed with a boundary
    cumsum, partials are computed with a rank-onehot masked reduction
    (VPU-friendly: a [BLOCK_E, BLOCK_E] compare+select tree), and
  - at most BLOCK_E compressed partials are committed to the VMEM
    accumulator with a scalar loop of dynamic stores (runs, not edges —
    on power-law graphs hub vertices compress thousands of edges per block
    into one store).

The sequential TPU grid makes cross-block accumulation into `out_ref` safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import default_interpret

INF = 3.0e38  # plain float: jnp constants would be captured by the kernel tracer


def _segment_reduce_kernel(
    lsrc_ref, ldst_ref, w_ref, val_ref, out_ref, *, block_e: int, is_min: bool
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        if is_min:
            out_ref[...] = val_ref[...]
        else:
            out_ref[...] = jnp.zeros_like(out_ref)

    lsrc = lsrc_ref[...]
    ldst = ldst_ref[...]
    w = w_ref[...]

    vals = val_ref[lsrc]  # gather from VMEM-resident vertex values
    if is_min:
        contrib = vals + w  # min-plus semiring; padded edges carry w=INF
    else:
        contrib = vals * w  # sum-times; padded edges carry w=0

    # Rank-compress equal-dst runs (dst-sorted within the block).
    boundary = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (ldst[1:] != ldst[:-1]).astype(jnp.int32)]
    )
    rank = jnp.cumsum(boundary) - 1  # [block_e] in [0, nruns)

    # Rank-onehot partial reduction: partial[r] = ⊕ contrib[rank == r].
    ranks = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 0)
    hit = ranks == rank[None, :]
    if is_min:
        partial = jnp.min(jnp.where(hit, contrib[None, :], INF), axis=1)
    else:
        partial = jnp.sum(jnp.where(hit, contrib[None, :], 0.0), axis=1)

    # dst of each rank = dst at the first edge of the run; scatter-free via
    # the same rank-onehot matrix (min over hit of edge index).
    iota_e = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_e), 1)
    run_start = jnp.min(jnp.where(hit, iota_e, block_e - 1), axis=1)
    dst_of_rank = ldst[run_start]
    nruns = rank[-1] + 1

    def commit(r, _):
        d = pl.ds(dst_of_rank[r], 1)
        cur = out_ref[d]
        out_ref[d] = jnp.minimum(cur, partial[r]) if is_min else cur + partial[r]
        return _

    jax.lax.fori_loop(0, nruns, commit, 0)


@functools.partial(
    jax.jit, static_argnames=("num_out", "block_e", "op", "interpret")
)
def segment_reduce_pallas(
    lsrc: jax.Array,
    ldst: jax.Array,
    weight: jax.Array,
    val: jax.Array,
    *,
    num_out: int,
    block_e: int = 512,
    op: str = "min",
    interpret: bool | None = None,
):
    """⊕-reduce edge contributions into destinations.

    lsrc/ldst: [E] int32, destination-sorted; padded edges must point at the
    dump slot (ldst == num_out - 1 is fine as long as callers ignore it) and
    carry identity weight (INF for min / 0 for sum — matching ref.py masks).
    val: [V] f32 (V >= num_out).
    Returns out: [num_out] f32; for op=="min", out is pre-seeded with val.
    """
    interpret = default_interpret(interpret)
    E = lsrc.shape[0]
    assert E % block_e == 0, "pad edges to a multiple of block_e"
    is_min = op == "min"
    grid = (E // block_e,)
    return pl.pallas_call(
        functools.partial(_segment_reduce_kernel, block_e=block_e, is_min=is_min),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((block_e,), lambda i: (i,)),
            pl.BlockSpec((val.shape[0],), lambda i: (0,)),  # val resident
        ],
        out_specs=pl.BlockSpec((num_out,), lambda i: (0,)),  # accumulator resident
        out_shape=jax.ShapeDtypeStruct((num_out,), jnp.float32),
        interpret=interpret,
    )(lsrc, ldst, weight, val)
