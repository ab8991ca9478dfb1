"""Pallas TPU kernel: fused streaming-scorer block commit (score + argmin
+ commit) for the chunked vertex-cut partitioners.

One launch streams a whole sequence of edge blocks (grid = one step per
block) through the sequential per-edge pipeline

  1. membership of the edge's two endpoints in every subgraph's packed
     bitset, optionally weighted by the scorer's per-edge degree term
     (HDRF's 2−θ streams),
  2. the argmin over subgraphs + exact balance-term commit,
  3. the winner's bitset updates,

with the (p,) e/v counters and the packed bitset resident in VMEM for the
whole stream — HBM sees one bitset read and one write per launch.

Layout: the bitset rides as [⌈Vw/128⌉, P8, 128] int32 — word w of
subgraph i sits at [w >> 7, i, w & 127], P8 = p rounded up to 8 — so one
dynamic leading-index load gives every subgraph's word for an endpoint in
a single (8, 128) tile, and a lane mask picks the word. `to_tiles` /
`from_tiles` convert from and to the [p, Vw] uint32 bitset; a caller that
carries the bitset across launches (the out-of-core driver, one launch per
block) keeps it tiled and calls `ebg_commit_tiles_pallas`. The block's
edge ids, validity and weights are pipelined into SMEM, where the scalar
core reads them per edge.

`window=False` (frozen commit): every edge of a block is scored against
the block-start bitset — the block's bits are committed only after its
last edge is scored. `window=True` (speculative window commit): each
committed edge's bits land before the next edge is scored, which is
exactly the one-edge-at-a-time scan — assignments are bit-identical to it
at any block size. Both are bit-identical to the oracle
`repro.kernels.ref.ebg_commit_block_ref`. Pad edges (valid=False) are
never committed; their assignment is the out-of-bounds row p.

The scorer's coefficients ride in as a (5,) f32 vector — ce (edge-balance
coefficient: EBV alpha / HDRF lambda), cv (vertex-balance: EBV beta),
inv_e, inv_v (the static normalizers), eps (the range normalizer's
epsilon) — they are traced values in the chunked driver (inv_e depends on
the real edge count), so they cannot be static kernel parameters. The
scorer's STRUCTURE (balance mode, degree weighting) is static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import default_interpret

LANES = 128
SUBLANES = 8
# A block's SMEM/VMEM slices must sit on the 1-D HBM tiling (1024 words).
BLOCK_ALIGN = 1024
VMEM_FLOOR = 32 * 2**20


def tiles_shape(num_parts: int, num_words: int) -> tuple[int, int, int]:
    """Shape of the kernel's bitset layout for a [num_parts, num_words] bitset."""
    return (-(-num_words // LANES), -(-num_parts // SUBLANES) * SUBLANES, LANES)


def to_tiles(keep_bits: jax.Array) -> jax.Array:
    """[p, Vw] uint32 bitset -> the kernel's [⌈Vw/128⌉, P8, 128] int32 layout."""
    p, vw = keep_bits.shape
    rows, p8, _ = tiles_shape(p, vw)
    kb = jax.lax.bitcast_convert_type(keep_bits, jnp.int32)
    kb = jnp.pad(kb, ((0, p8 - p), (0, rows * LANES - vw)))
    return kb.reshape(p8, rows, LANES).transpose(1, 0, 2)


def from_tiles(tiles: jax.Array, num_parts: int, num_words: int) -> jax.Array:
    """Inverse of `to_tiles`."""
    rows, p8, _ = tiles.shape
    kb = tiles.transpose(1, 0, 2).reshape(p8, rows * LANES)[:num_parts, :num_words]
    return jax.lax.bitcast_convert_type(kb, jnp.uint32)


def vmem_bytes(tiles: tuple[int, int, int], block: int) -> int:
    """VMEM the kernel asks for: the resident bitset, the double-buffered
    parts block, and headroom for the compiler's own scratch."""
    rows, p8, lanes = tiles
    return max(VMEM_FLOOR, rows * p8 * lanes * 4 + 2 * 4 * block + 4 * 2**20)


def _ebg_commit_kernel(
    coef_ref, u_ref, v_ref, ok_ref, wu_ref, wv_ref, e_in, v_in, keep_hbm,
    keep_out_hbm, e_out, v_out, parts_ref,
    keep, ec, vc, sem,
    *, num_parts: int, block: int, balance: str, weighted: bool, window: bool,
):
    step = pl.program_id(0)
    p8 = keep.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (p8, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (p8, LANES), 1)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    real = sub < num_parts

    @pl.when(step == 0)
    def _load_state():
        cp = pltpu.make_async_copy(keep_hbm, keep, sem.at[0])
        cp.start()
        cp.wait()
        ec[...] = e_in[...]
        vc[...] = v_in[...]

    ce, cv = coef_ref[0], coef_ref[1]
    inv_e, inv_v, eps = coef_ref[2], coef_ref[3], coef_ref[4]
    parts_ref[...] = jnp.full(parts_ref.shape, num_parts, jnp.int32)

    def miss(x):
        """(P8, 128) f32, every lane: 1 where vertex x is absent from part i."""
        t = keep[x >> 12]
        bit = (t >> (x & 31)) & 1
        present = jnp.max(jnp.where(lane == ((x >> 5) & 127), bit, 0), axis=1, keepdims=True)
        return jnp.broadcast_to((1 - present).astype(jnp.float32), (p8, LANES))

    def set_bit(x, win):
        """keep[i] |= {x} for the winner rows `win`."""
        r = x >> 12
        t = keep[r]
        keep[r] = jnp.where(win & (lane == ((x >> 5) & 127)), t | (1 << (x & 31)), t)

    def score_edge(j):
        """Score edge j against the current bitset, commit the counters and
        record the winner; returns the winner mask."""
        uj, vj = u_ref[j], v_ref[j]
        mu, mv = miss(uj), miss(vj)
        e_c, v_c = ec[...], vc[...]
        if balance == "static":
            norm = inv_e
        else:
            spread = (jnp.max(jnp.where(real, e_c, float("-inf")), axis=0, keepdims=True)
                      - jnp.min(jnp.where(real, e_c, float("inf")), axis=0, keepdims=True))
            norm = 1.0 / (eps + spread)
        gain = wu_ref[j] * mu + wv_ref[j] * mv if weighted else mu + mv
        score = gain + ce * e_c * norm + cv * v_c * inv_v
        score = jnp.where(real, score, float("inf"))
        best = jnp.min(score, axis=0, keepdims=True)
        i = jnp.min(jnp.where(score == best, sub, p8), axis=0, keepdims=True)  # ties -> lowest
        win = sub == i
        ec[...] = e_c + jnp.where(win, 1.0, 0.0)
        vc[...] = v_c + jnp.where(win, mu + mv, 0.0)
        rows = pl.ds(j >> 7, 1)
        prow = parts_ref[rows, :]
        parts_ref[rows, :] = jnp.where(lane1 == (j & 127), i, prow)
        return win

    def commit_loop(j, c):
        @pl.when(ok_ref[j] != 0)
        def _commit():
            win = score_edge(j)
            if window:  # live state: the next edge sees these bits
                set_bit(u_ref[j], win)
                set_bit(v_ref[j], win)  # after u's store: u and v may share a word

        return c

    jax.lax.fori_loop(0, block, commit_loop, 0)

    if not window:  # frozen: the block's bits land after its last score
        def bits_loop(j, c):
            @pl.when(ok_ref[j] != 0)
            def _bits():
                prow = parts_ref[pl.ds(j >> 7, 1), :]
                i = jnp.max(jnp.where(lane1 == (j & 127), prow, -1), axis=1, keepdims=True)
                win = sub == i
                set_bit(u_ref[j], win)
                set_bit(v_ref[j], win)

            return c

        jax.lax.fori_loop(0, block, bits_loop, 0)

    e_out[...] = ec[...]
    v_out[...] = vc[...]

    @pl.when(step == pl.num_programs(0) - 1)
    def _store_state():
        cp = pltpu.make_async_copy(keep, keep_out_hbm, sem.at[0])
        cp.start()
        cp.wait()


@functools.partial(
    jax.jit, static_argnames=("num_parts", "balance", "weighted", "window", "interpret"))
def ebg_commit_tiles_pallas(
    tiles: jax.Array,  # [⌈Vw/128⌉, P8, 128] int32: the bitset in the `to_tiles` layout
    e_count: jax.Array,  # [p] f32
    v_count: jax.Array,  # [p] f32
    u: jax.Array,  # [B] int32, or [nblk, B]: a sequence of blocks
    v: jax.Array,  # like u
    valid: jax.Array,  # like u, bool (pad edges False)
    wu: jax.Array,  # like u, f32 membership weights (ignored unless weighted)
    wv: jax.Array,  # like u
    coef: jax.Array,  # [5] f32: ce, cv, inv_e, inv_v, eps
    *,
    num_parts: int,
    balance: str = "static",
    weighted: bool = False,
    window: bool = False,
    interpret: bool | None = None,
):
    """Commit one block ([B] streams) or a block sequence ([nblk, B]) in one
    launch, on a bitset already in the kernel's layout — callers that carry
    the bitset across launches keep it tiled. Returns (tiles, e_count,
    v_count, parts) with parts shaped like `u`."""
    interpret = default_interpret(interpret)
    p = num_parts
    p8 = tiles.shape[1]
    blocks = u.reshape((-1, u.shape[-1]))
    nblk, B = blocks.shape
    bp = -(-B // BLOCK_ALIGN) * BLOCK_ALIGN

    def stream(x, dtype):
        x = x.reshape(nblk, B).astype(dtype)
        return jnp.pad(x, ((0, 0), (0, bp - B))).reshape(-1)

    def counters(x):
        return jnp.broadcast_to(jnp.pad(x.astype(jnp.float32), (0, p8 - p))[:, None], (p8, LANES))

    smem_block = pl.BlockSpec((bp,), lambda b: (b,), memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((p8, LANES), lambda b: (0, 0))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    keep_out, e_out, v_out, parts = pl.pallas_call(
        functools.partial(
            _ebg_commit_kernel, num_parts=p, block=B, balance=balance, weighted=weighted,
            window=window,
        ),
        grid=(nblk,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [smem_block] * 5
        + [whole, whole, any_space],
        out_specs=[any_space, whole, whole,
                   pl.BlockSpec((None, bp // LANES, LANES), lambda b: (b, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
            jax.ShapeDtypeStruct((p8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((p8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblk, bp // LANES, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM(tiles.shape, jnp.int32),  # resident bitset
            pltpu.VMEM((p8, LANES), jnp.float32),  # e_count
            pltpu.VMEM((p8, LANES), jnp.float32),  # v_count
            pltpu.SemaphoreType.DMA((1,)),
        ],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_bytes(tiles.shape, bp),
        ),
        interpret=interpret,
    )(
        coef.astype(jnp.float32), stream(u, jnp.int32), stream(v, jnp.int32),
        stream(valid, jnp.int32), stream(wu, jnp.float32), stream(wv, jnp.float32),
        counters(e_count), counters(v_count), tiles,
    )
    parts = parts.reshape(nblk, bp)[:, :B].reshape(u.shape)
    return keep_out, e_out[:p, 0], v_out[:p, 0], parts


@functools.partial(jax.jit, static_argnames=("balance", "weighted", "window", "interpret"))
def ebg_commit_block_pallas(
    keep_bits, e_count, v_count, u, v, valid, wu, wv, coef, *,
    balance: str = "static", weighted: bool = False, window: bool = False,
    interpret: bool | None = None,
):
    """`ebg_commit_tiles_pallas` on a [p, Vw] uint32 bitset: converts to the
    kernel's layout and back around one launch. Returns (keep_bits,
    e_count, v_count, parts)."""
    p, vw = keep_bits.shape
    tiles, e_count, v_count, parts = ebg_commit_tiles_pallas(
        to_tiles(keep_bits), e_count, v_count, u, v, valid, wu, wv, coef,
        num_parts=p, balance=balance, weighted=weighted, window=window, interpret=interpret,
    )
    return from_tiles(tiles, p, vw), e_count, v_count, parts
