"""Pallas TPU megakernel: one whole BSP local-compute stage per worker.

The per-superstep hot loop of the subgraph-centric engine is a chain of
relaxation passes `acc[dst] ⊕= val[src] (+ w)` over the worker's edges,
⊕ ∈ {min, +}. This kernel runs the ENTIRE local-compute stage of a
superstep for one worker in a single launch:

  - the worker's vertex values live in VMEM for the whole stage, laid out
    as (rows, 128) lanes: vertex x sits at row x >> 7, lane x & 127 (EBG's
    vertex balance bounds max_v, i.e. this kernel's VMEM footprint — the
    paper's balance objective is what makes the values fit);
  - the edge stream (src, dst, weight) is DMA'd from HBM into SMEM in
    blocks of `block_e` edges, double-buffered — block b+1's copy is in
    flight while block b is relaxed. SMEM gives the scalar core the
    per-edge ids for free;
  - each edge reads its source value with a dynamic row load plus a lane
    mask, and commits into the destination row with a masked
    read-modify-write — the only vector addressing Mosaic offers for
    random access (no VMEM gathers, no dynamic lane offsets);
  - min-fixpoint programs (CC/SSSP/BFS/negated reach) iterate passes to
    LOCAL convergence inside the kernel: a pass reads the pre-pass values
    (`prev`) and writes `acc` (Jacobi, exactly the XLA pass), the
    per-worker convergence flag is a VMEM compare of the two, and the
    per-worker inner-iteration count is the kernel's second output;
  - sweep programs (PageRank) run one accumulation pass over the
    out-degree shares, adding edge by edge in stream order.

Values touch HBM once per superstep in each direction: one DMA in, one
DMA out. Grid = one step per worker; the sequential TPU grid keeps each
worker's edge stream private to its accumulator.

Bit-parity contract: identical values AND inner-iteration counts to the
batched XLA while-loop in `repro.graph.engine._local_fixpoint` (the
change-passes of a monotone relax form a prefix, so the per-worker loop
here and the any-worker batched loop there agree on both values and
iteration counts — pinned by tests/test_megakernel.py and the driver
parity suites). Sums accumulate in stream order, which is `segment_sum`'s
order when the stream is globally dst-sorted.

Batching: `vmap` over a leading query axis (the serving tier's batched
driver) folds the queries into the worker grid — query b's worker i is
grid step b·p + i and reads stream row i — so a batch of B queries is
ONE launch over B·p workers sharing the subgraph's edge streams.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import default_interpret

INF = 3.0e38  # plain float: jnp constants would be captured by the kernel tracer
LANES = 128
SUBLANES = 8
# HBM tiles a 1-D int32/f32 array in 1024-element chunks: a compiled DMA
# slice of the flat edge stream must start and end on that grid.
DMA_ALIGN = 1024
UNROLL = 8  # edges per scalar-loop iteration (Mosaic only unrolls fully)
# Whole-array vector work (seeding `acc`, the convergence compare) loops
# over row chunks: Mosaic unrolls a vector op over every vreg it touches.
CHUNK_ROWS = 512
VMEM_FLOOR = 32 * 2**20


def value_rows(num_out: int) -> int:
    """Rows of the (rows, 128) VMEM value layout: sublane-aligned, and a
    whole number of `CHUNK_ROWS` chunks once it spans more than one."""
    rows = -(-num_out // LANES)
    unit = CHUNK_ROWS if rows > CHUNK_ROWS else SUBLANES
    return -(-rows // unit) * unit


def vmem_bytes(num_out: int) -> int:
    """VMEM the kernel asks for: the `prev` and `acc` value buffers plus
    headroom for the compiler's own scratch."""
    return max(VMEM_FLOOR, 2 * value_rows(num_out) * LANES * 4 + 4 * 2**20)


def _bsp_superstep_kernel(
    src_hbm, dst_hbm, w_hbm, val_hbm, out_hbm, it_ref,
    prev, acc, sbuf, dbuf, wbuf, esems, vsem,
    *, combine: str, block_e: int, nblk: int, inner_cap: int, stream_rows: int,
):
    worker = pl.program_id(0)
    base = jax.lax.rem(worker, stream_rows) * (nblk * block_e)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    load = pltpu.make_async_copy(val_hbm.at[worker], prev, vsem.at[0])
    load.start()
    load.wait()

    def edge_dmas(slot, b):
        """The three async copies moving block b into buffer `slot`."""
        src = pl.ds(base + b * block_e, block_e)
        dst = pl.ds(slot * block_e, block_e)
        return (
            pltpu.make_async_copy(src_hbm.at[src], sbuf.at[dst], esems.at[0, slot]),
            pltpu.make_async_copy(dst_hbm.at[src], dbuf.at[dst], esems.at[1, slot]),
            pltpu.make_async_copy(w_hbm.at[src], wbuf.at[dst], esems.at[2, slot]),
        )

    def relax_edge(o):
        s, d = sbuf[o], dbuf[o]
        w = jnp.full((1, 1), wbuf[o], jnp.float32)
        row = prev[pl.ds(s >> 7, 1), :]
        if combine == "sum":
            g = jnp.max(jnp.where(lane == (s & 127), row, float("-inf")), axis=1, keepdims=True)
            contrib = jnp.where(w != 0.0, g * w, 0.0)  # pads carry w = 0
        else:
            g = jnp.min(jnp.where(lane == (s & 127), row, INF), axis=1, keepdims=True)
            contrib = jnp.where(w < INF, g + w, INF)  # pads carry w = INF
        rows = pl.ds(d >> 7, 1)
        cur = acc[rows, :]
        new = cur + contrib if combine == "sum" else jnp.minimum(cur, contrib)
        acc[rows, :] = jnp.where(lane == (d & 127), new, cur)

    unroll = math.gcd(block_e, UNROLL)
    chunk = min(prev.shape[0], CHUNK_ROWS)

    def over_rows(fn, init):
        """fori over the value rows in `chunk`-row slices."""
        return jax.lax.fori_loop(
            0, prev.shape[0] // chunk,
            lambda c, carry: fn(pl.ds(pl.multiple_of(c * chunk, chunk), chunk), carry), init,
        )

    def one_pass():
        """Stream every edge block through the double buffer into `acc`.
        One pass = one relaxation (min) / the whole sweep (sum)."""
        def seed(rows, c):
            if combine == "sum":
                acc[rows, :] = jnp.zeros((chunk, LANES), jnp.float32)
            else:
                acc[rows, :] = prev[rows, :]  # min is seeded with the current values
            return c

        over_rows(seed, 0)
        for dma in edge_dmas(0, 0):
            dma.start()

        def block_body(b, carry):
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < nblk)
            def _prefetch():
                for dma in edge_dmas(1 - slot, b + 1):
                    dma.start()

            for dma in edge_dmas(slot, b):
                dma.wait()
            first = slot * block_e

            def group(g, c):
                for k in range(unroll):
                    relax_edge(first + g * unroll + k)
                return c

            jax.lax.fori_loop(0, block_e // unroll, group, 0)
            return carry

        jax.lax.fori_loop(0, nblk, block_body, 0)

    if combine == "sum":
        one_pass()
        result = acc
        it_ref[worker] = jnp.int32(1)
    else:
        # Per-worker fixpoint: iterate passes until a pass changes nothing
        # (fused convergence flag) or the inner cap hits. Identical values
        # and counts to the batched driver loop: change-passes of the
        # monotone relax form a prefix, so iters = min(#changing, cap).
        def cond(carry):
            changed, it = carry
            return changed & (it < inner_cap)

        def commit(rows, changed):
            new = acc[rows, :]
            changed |= jnp.any(new != prev[rows, :])
            prev[rows, :] = new
            return changed

        def body(carry):
            _, it = carry
            one_pass()
            changed = over_rows(commit, jnp.bool_(False))
            return changed, it + changed.astype(jnp.int32)

        _, iters = jax.lax.while_loop(cond, body, (jnp.bool_(True), jnp.int32(0)))
        result = prev
        it_ref[worker] = iters

    store = pltpu.make_async_copy(result, out_hbm.at[worker], vsem.at[0])
    store.start()
    store.wait()


def _superstep_call(lsrc, ldst, weight, vals, *, num_out, combine, inner_cap, block_e,
                    interpret):
    """One launch over W = vals.shape[0] workers; worker w streams edge row
    w % p (p = lsrc.shape[0]), so W = B·p runs B queries over shared
    streams. lsrc/ldst/weight: [p, E] with E % block_e == 0; vals: [W,
    num_out]."""
    p, E = lsrc.shape
    W = vals.shape[0]
    rows = value_rows(num_out)
    fill = 0.0 if combine == "sum" else INF
    v3 = jnp.pad(vals, ((0, 0), (0, rows * LANES - num_out)), constant_values=fill)
    v3 = v3.reshape(W, rows, LANES)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    out, iters = pl.pallas_call(
        functools.partial(
            _bsp_superstep_kernel, combine=combine, block_e=block_e, nblk=E // block_e,
            inner_cap=inner_cap, stream_rows=p,
        ),
        grid=(W,),
        in_specs=[any_space] * 4,
        out_specs=[any_space, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((W, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((W,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),  # prev (values / shares)
            pltpu.VMEM((rows, LANES), jnp.float32),  # acc
            pltpu.SMEM((2 * block_e,), jnp.int32),  # double-buffered src ids
            pltpu.SMEM((2 * block_e,), jnp.int32),  # double-buffered dst ids
            pltpu.SMEM((2 * block_e,), jnp.float32),  # double-buffered weights
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_bytes(num_out),
        ),
        interpret=interpret,
    )(lsrc.reshape(-1), ldst.reshape(-1), weight.reshape(-1), v3)
    return out.reshape(W, rows * LANES)[:, :num_out], iters


def _batched_superstep(**statics):
    """`_superstep_call` whose vmap folds the query axis into the worker
    grid instead of batching the pallas_call (whose HBM refs are indexed
    by the worker id)."""

    @jax.custom_batching.custom_vmap
    def call(lsrc, ldst, weight, vals):
        return _superstep_call(lsrc, ldst, weight, vals, **statics)

    @call.def_vmap
    def _fold(axis_size, in_batched, lsrc, ldst, weight, vals):
        if any(in_batched[:3]):
            raise NotImplementedError("bsp_superstep batches queries over SHARED edge streams")
        if not in_batched[3]:
            vals = jnp.broadcast_to(vals, (axis_size,) + vals.shape)
        B, p = vals.shape[:2]
        out, iters = call(lsrc, ldst, weight, vals.reshape((B * p,) + vals.shape[2:]))
        return (out.reshape(vals.shape), iters.reshape(B, p)), (True, True)

    return call


@functools.partial(
    jax.jit, static_argnames=("num_out", "combine", "inner_cap", "block_e", "interpret")
)
def bsp_superstep_pallas(
    lsrc: jax.Array,  # [p, E] int32
    ldst: jax.Array,  # [p, E] int32 (sum: globally dst-sorted, see module doc)
    weight: jax.Array,  # [p, E] f32; pads carry INF (min) / 0 (sum)
    val: jax.Array,  # [p, num_out] f32
    out_degree: jax.Array | None = None,  # [p, num_out] f32, combine="sum" only
    *,
    num_out: int,
    combine: str = "min",
    inner_cap: int = 1,
    block_e: int = 512,
    interpret: bool | None = None,
):
    """Whole-local-stage BSP superstep: returns (new_val [p, num_out] f32,
    inner iteration counts [p] int32).

    Streams of any length are padded here with identity-weight no-op
    edges at the dump slot num_out-1 to a multiple of the block size. The
    compiled kernel rounds `block_e` up to a multiple of `DMA_ALIGN`; the
    interpreter keeps it as given (clamped to the stream length), so the
    tests exercise multi-block streaming at small sizes. Values never
    depend on the block size.
    """
    interpret = default_interpret(interpret)
    if combine not in ("min", "sum"):
        raise ValueError(f"combine must be 'min' or 'sum', got {combine!r}")
    if combine == "sum":
        if out_degree is None:
            raise ValueError("combine='sum' needs out_degree")
        # The push-sum share, term for term the engine's XLA sweep.
        val = jnp.where(out_degree > 0, val / out_degree, 0.0)
    p, E = lsrc.shape
    assert val.shape == (p, num_out)
    if interpret:
        block_e = max(min(block_e, E), 1)
    else:
        block_e = -(-block_e // DMA_ALIGN) * DMA_ALIGN
    pad = (-E) % block_e
    if pad:
        identity = 0.0 if combine == "sum" else INF
        lsrc = jnp.concatenate([lsrc, jnp.zeros((p, pad), lsrc.dtype)], axis=1)
        ldst = jnp.concatenate([ldst, jnp.full((p, pad), num_out - 1, ldst.dtype)], axis=1)
        weight = jnp.concatenate([weight, jnp.full((p, pad), identity, weight.dtype)], axis=1)
    call = _batched_superstep(
        num_out=num_out, combine=combine, inner_cap=inner_cap, block_e=block_e,
        interpret=interpret,
    )
    return call(lsrc.astype(jnp.int32), ldst.astype(jnp.int32), weight.astype(jnp.float32), val)
