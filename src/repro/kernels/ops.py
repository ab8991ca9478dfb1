"""Public jit'd wrappers for the Pallas kernels with CPU-oracle dispatch.

On the CPU container the kernels default to the pure-jnp oracle; on TPU the
production entry points default to the compiled Pallas path. Callers can
force either with `impl=`, and — independently — force interpret vs
compiled Pallas with `interpret=` (e.g. `impl="pallas", interpret=True`
runs the real kernel under the interpreter on any backend, which is how
the engine's `compute_backend="pallas"` stays testable off-TPU).

These wrappers also own the block-padding convention: edge streams are
padded to a multiple of `block_e` with identity-weight no-op edges, so
callers (the BSP engine pads to `pad_multiple`, not to `block_e`) never
have to know the kernels' grid granularity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api.config import COMPUTE_BACKENDS, check_compute_backend  # noqa: F401  (re-exported seam)
from repro.kernels import ref
from repro.kernels.bsp_superstep import bsp_superstep_pallas
from repro.kernels.decode_attn import decode_attention_pallas
from repro.kernels.dispatch import default_interpret, platform_is_tpu
from repro.kernels.ebg_commit import ebg_commit_block_pallas, ebg_commit_tiles_pallas
from repro.kernels.ebg_score import ebg_membership_pallas
from repro.kernels.segment_reduce import segment_reduce_pallas

IMPLS = ("ref", "pallas")


def _default_impl() -> str:
    return "pallas" if platform_is_tpu() else "ref"


def _resolve_impl(impl: str | None, interpret: bool | None) -> tuple[str, bool]:
    """The single place backend sniffing happens.

    impl=None  -> pallas on TPU, pure-jnp oracle elsewhere.
    interpret=None -> interpreter off-TPU, compiled kernel on TPU.
    An explicit `interpret` always wins over the sniff, so callers can
    force compiled Pallas off-TPU (or the interpreter on TPU).
    """
    impl = impl or _default_impl()
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS} or None, got {impl!r}")
    return impl, default_interpret(interpret)


def _pad_to_block(lsrc, ldst, weight, block_e: int, pad_dst: int, identity: float):
    """Pad an edge stream to a multiple of block_e with no-op edges.

    Pad edges point at `pad_dst` (callers pass num_out-1, the engine's dump
    slot, which also keeps dst-sortedness) and carry the reduction identity
    as weight, so they contribute nothing. Returns the (possibly smaller)
    block size actually used — a stream shorter than block_e becomes a
    single exact-size block instead of mostly padding.
    """
    E = lsrc.shape[0]
    block_e = max(min(block_e, E), 1)
    pad = (-E) % block_e
    if pad:
        lsrc = jnp.concatenate([lsrc, jnp.zeros((pad,), lsrc.dtype)])
        ldst = jnp.concatenate([ldst, jnp.full((pad,), pad_dst, ldst.dtype)])
        weight = jnp.concatenate([weight, jnp.full((pad,), identity, weight.dtype)])
    return lsrc, ldst, weight, block_e


def segment_min_plus(
    lsrc, ldst, weight, val, *, num_out: int,
    impl: str | None = None, block_e: int = 512, interpret: bool | None = None,
):
    """out[d] = min(val[d], min_{e: dst=d} val[src_e] + w_e); dst-sorted edges.

    Padded edges must carry weight=INF (min identity).
    """
    impl, interpret = _resolve_impl(impl, interpret)
    if impl == "ref":
        mask = weight < ref.INF
        return ref.segment_min_plus_ref(lsrc, ldst, weight, mask, val, num_out)
    lsrc, ldst, weight, block_e = _pad_to_block(
        lsrc, ldst, weight, block_e, num_out - 1, float(ref.INF)
    )
    return segment_reduce_pallas(
        lsrc, ldst, weight, val, num_out=num_out, block_e=block_e, op="min", interpret=interpret
    )


def segment_sum_scaled(
    lsrc, ldst, scale, val, *, num_out: int,
    impl: str | None = None, block_e: int = 512, interpret: bool | None = None,
):
    """out[d] = sum_{e: dst=d} val[src_e] * scale_e; padded edges scale=0."""
    impl, interpret = _resolve_impl(impl, interpret)
    if impl == "ref":
        mask = scale != 0.0
        return ref.segment_sum_ref(lsrc, ldst, scale, mask, val, num_out)
    lsrc, ldst, scale, block_e = _pad_to_block(lsrc, ldst, scale, block_e, num_out - 1, 0.0)
    return segment_reduce_pallas(
        lsrc, ldst, scale, val, num_out=num_out, block_e=block_e, op="sum", interpret=interpret
    )


def segment_max(
    lsrc, ldst, weight, val, *, num_out: int,
    impl: str | None = None, block_e: int = 512, interpret: bool | None = None,
):
    """out[d] = max(val[d], max_{e: dst=d} val[src_e]); dst-sorted edges.

    The max-combine entry point for max-semiring programs (e.g. the
    engine's reachability). It runs on the SAME min-plus kernels via
    negation — no separate Pallas kernel to maintain. `weight` is the pad
    carrier only: real edges must hold 0, padded edges the min identity
    INF (so they contribute nothing in the negated domain).
    """
    return -segment_min_plus(
        lsrc, ldst, weight, -val, num_out=num_out, impl=impl, block_e=block_e,
        interpret=interpret,
    )


def bsp_superstep(
    lsrc, ldst, weight, val, *, num_out: int, combine: str = "min",
    inner_cap: int = 1, out_degree=None,
    impl: str | None = None, block_e: int = 512, interpret: bool | None = None,
):
    """Whole-local-stage BSP superstep for a batch of workers (the engine's
    megakernel entry): lsrc/ldst/weight are [p, E] edge streams, val is the
    [p, num_out] f32 value state.

    combine="min" iterates the min-plus relaxation to local convergence
    (capped at `inner_cap`) — padded edges must carry weight=INF (the min
    identity); the stream may concatenate direction halves, each
    dst-sorted. combine="max" runs on the same machinery via negation
    (`weight` is the pad carrier only: real edges hold 0, pads INF).
    combine="sum" is one out-degree-normalized push-sum sweep
    (`out_degree`: [p, num_out] f32; the share division is fused) —
    padded edges carry weight=0 and the stream must be globally
    dst-sorted (float accumulation order).

    Returns (new_val [p, num_out] f32, per-worker inner iteration counts
    [p] int32) — bit-identical values and counts to the engine's batched
    XLA path across impls (the driver/backend/program parity suites pin
    this).
    """
    impl, interpret = _resolve_impl(impl, interpret)
    if combine not in ("min", "max", "sum"):
        raise ValueError(f"combine must be 'min', 'max' or 'sum', got {combine!r}")
    if combine == "max":
        out, iters = bsp_superstep(
            lsrc, ldst, weight, -val, num_out=num_out, combine="min",
            inner_cap=inner_cap, impl=impl, block_e=block_e, interpret=interpret,
        )
        return -out, iters
    if (combine == "sum") != (out_degree is not None):
        raise ValueError("out_degree is required for combine='sum' and only then")
    if impl == "ref":
        return ref.bsp_superstep_ref(
            lsrc, ldst, weight, val, num_out,
            combine=combine, inner_cap=inner_cap, out_degree=out_degree,
        )
    # The kernel owns the block padding: its compiled block size is
    # DMA-aligned, so only it knows the granularity.
    return bsp_superstep_pallas(
        lsrc, ldst, weight, val, out_degree,
        num_out=num_out, combine=combine, inner_cap=inner_cap,
        block_e=block_e, interpret=interpret,
    )


def ebg_membership(
    keep_bits, u, v, *, impl: str | None = None, block_e: int = 512, interpret: bool | None = None,
):
    """memb[i,b] = #endpoints of edge b absent from keep[i] (packed bitset)."""
    impl, interpret = _resolve_impl(impl, interpret)
    if impl == "ref":
        return ref.ebg_membership_ref(keep_bits, u, v)
    E = u.shape[0]
    block_e = max(min(block_e, E), 1)
    pad = (-E) % block_e
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)])
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    out = ebg_membership_pallas(keep_bits, u, v, block_e=block_e, interpret=interpret)
    return out[:, :E] if pad else out


def ebg_commit_block(
    keep_bits, e_count, v_count, u, v, valid, *,
    alpha, beta, inv_e, inv_v, eps=1.0, balance: str = "static",
    wu=None, wv=None, window: bool = False,
    impl: str | None = None, interpret: bool | None = None,
):
    """Fused streaming-scorer block commit: membership score + argmin +
    exact balance commit + bitset update for one edge block ([B] streams)
    or a sequence of blocks ([nblk, B], committed in order — the Pallas
    path runs a whole edge stream in one launch), with the (p,) counters
    and the (p, ⌈V/32⌉) bitset VMEM-resident on the Pallas path.

    The scorer rides in as its coefficient vector plus structure flags:
    alpha/beta are the generic edge/vertex balance coefficients (EBV's
    namesakes; HDRF's lambda is alpha with beta=0), `balance` selects the
    edge-balance normalizer ("static" inv_e = p/|E|, "range"
    1/(eps + max−min)), and wu/wv optionally weight the membership term
    per edge (HDRF's 2−θ degree streams). All coefficients may be traced
    scalars (inv_e depends on the real edge count). Pad edges carry
    valid=False: they are never committed, and their assignment is the
    out-of-bounds row p. `window=True` turns
    the frozen-membership commit into the speculative window commit:
    scores stay vectorized against block-start state, but each commit
    replays its membership consequences onto later conflicted columns —
    assignments bit-identical to the one-edge-at-a-time scan driver.
    Returns (keep_bits, e_count, v_count, parts) — assignments
    bit-identical across impls and to the dense-membership XLA path.
    """
    impl, interpret = _resolve_impl(impl, interpret)
    if balance not in ("static", "range"):
        raise ValueError(f"balance must be 'static' or 'range', got {balance!r}")
    if (wu is None) != (wv is None):
        raise ValueError("wu and wv must be given together")
    if impl == "ref":
        def one_block(state, blk):
            ub, vb, valb, wub, wvb = blk
            *state, parts = ref.ebg_commit_block_ref(
                *state, ub, vb, valb,
                alpha=alpha, beta=beta, inv_e=inv_e, inv_v=inv_v,
                eps=eps, balance=balance, wu=wub, wv=wvb, window=window,
            )
            return tuple(state), parts

        state, blocks = (keep_bits, e_count, v_count), (u, v, valid, wu, wv)
        if u.ndim == 1:
            state, parts = one_block(state, blocks)
        else:
            state, parts = jax.lax.scan(one_block, state, blocks)
        return (*state, parts)
    weighted = wu is not None
    coef, wu, wv = _ebg_commit_args(alpha, beta, inv_e, inv_v, eps, u, wu, wv)
    return ebg_commit_block_pallas(
        keep_bits, e_count, v_count, u, v, valid, wu, wv, coef,
        balance=balance, weighted=weighted, window=window, interpret=interpret,
    )


def _ebg_commit_args(alpha, beta, inv_e, inv_v, eps, u, wu, wv):
    """The commit kernel's coefficient vector and weight streams (zeros
    when unweighted)."""
    coef = jnp.stack([
        jnp.float32(alpha), jnp.float32(beta), jnp.float32(inv_e),
        jnp.float32(inv_v), jnp.float32(eps),
    ])
    if wu is None:
        return coef, jnp.zeros(u.shape, jnp.float32), jnp.zeros(u.shape, jnp.float32)
    return coef, wu, wv


def ebg_commit_tiles(
    tiles, e_count, v_count, u, v, valid, *, num_parts: int,
    alpha, beta, inv_e, inv_v, eps=1.0, balance: str = "static",
    wu=None, wv=None, window: bool = False, interpret: bool | None = None,
):
    """`ebg_commit_block` on the Pallas kernel, with the bitset in the
    kernel's own layout (`kernels.ebg_commit.to_tiles`) so a caller that
    launches once per block converts it once, not per launch. Returns
    (tiles, e_count, v_count, parts)."""
    weighted = wu is not None
    coef, wu, wv = _ebg_commit_args(alpha, beta, inv_e, inv_v, eps, u, wu, wv)
    return ebg_commit_tiles_pallas(
        tiles, e_count, v_count, u, v, valid, wu, wv, coef, num_parts=num_parts,
        balance=balance, weighted=weighted, window=window, interpret=interpret,
    )


def decode_attention(
    q, k, v, *, softcap: float = 0.0,
    impl: str | None = None, block_s: int = 512, interpret: bool | None = None,
):
    """Single-token GQA decode attention over a KV cache."""
    impl, interpret = _resolve_impl(impl, interpret)
    if impl == "ref":
        return ref.decode_attention_ref(q, k, v, softcap=softcap)
    return decode_attention_pallas(q, k, v, softcap=softcap, block_s=block_s, interpret=interpret)


def pack_keep_bits(keep_bool: jax.Array) -> jax.Array:
    """[p, V] bool -> [p, ceil(V/32)] uint32 packed bitset."""
    p, V = keep_bool.shape
    pad = (-V) % 32
    kb = jnp.pad(keep_bool, ((0, 0), (0, pad)))
    words = kb.reshape(p, -1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(words << shifts[None, None, :], axis=-1, dtype=jnp.uint32)
