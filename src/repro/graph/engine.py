"""Subgraph-centric bulk-synchronous-parallel engine (paper §IV-B).

One subgraph == one worker == one mesh device. A superstep is
  1. compute:   local work over the subgraph's own edges ("think like a
                graph") — either a fixpoint relaxation iterated to local
                convergence (min/max-semiring programs) or a single sweep
                (PageRank's push-sum),
  2. exchange:  mirror→master reduction then master→mirror broadcast over
                fixed padded buffers (dense all_to_all; the TPU-native
                替代 of MPI point-to-point sends),
  3. barrier:   implicit in SPMD — the collective is the synchronization.

Every algorithm is expressed as a `VertexProgram` — a frozen description of
what actually varies between them (value dtype, exchange combine, local
compute, apply step, message policy, convergence rule). ONE generic
superstep body, ONE fused driver, ONE host driver, and ONE distributed
stepper execute any program; CC, SSSP, PageRank, BFS, and max-label
reachability are stock instances in `PROGRAMS`.

Two execution modes sharing the same superstep body:
  - simulation:   all p workers live on one device as a leading batch axis;
                  exchange is a transpose. Used by tests/benchmarks.
  - distributed:  shard_map over a mesh axis; exchange is lax.all_to_all.
                  Used by the multi-pod dry-run and real clusters.

Messages are counted with delta semantics (a mirror/master "sends" only if
its value changed this superstep) for semiring programs — the paper's
platform-independent communication metric (Tables IV/V) — and every-step
semantics for PageRank (it pushes rank shares unconditionally).
`exchange_period > 1` enables bounded staleness (straggler mitigation):
workers run k local supersteps between global exchanges; monotone
(min/max-semiring) programs converge to the same fixpoint.

Max-combine programs run on the existing min-plus machinery (and hence the
min-plus Pallas kernels) via negation at the driver boundary: values are
negated on entry and on exit, so the superstep body only ever sees the
{min, sum} combines.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.api.config import check_compute_backend
from repro.core.metrics import max_mean_ratio
from repro.graph.build import SubgraphSet, check_addressing
from repro.kernels import ops

INF_F32 = jnp.float32(3.0e38)
INF_I32 = jnp.int32(2**31 - 1)

# Simulation-mode driver implementations. "fused" runs the whole BSP loop as
# one jitted lax.while_loop program (one dispatch, one host sync per run);
# "host" runs one jitted superstep per Python iteration (kept for A/B and as
# the readable reference of the loop semantics).
DRIVERS = ("fused", "host")

def check_driver(driver) -> str:
    if driver not in DRIVERS:
        raise ValueError(f"driver must be one of {DRIVERS}, got {driver!r}")
    return driver


@dataclasses.dataclass
class BSPStats:
    supersteps: int
    messages_per_worker: np.ndarray  # [p] total messages sent by each worker
    messages_per_step: np.ndarray  # [steps]
    comp_work_per_worker: np.ndarray  # [p] edge-relaxation work proxy
    inner_iters_per_step: np.ndarray  # [steps, p]
    # Full per-step per-worker message matrix [steps, p] — what the BSP cost
    # model in benchmarks/runtime.py consumes. messages_per_worker and
    # messages_per_step above are its marginals, kept for existing call
    # sites; every driver populates all three.
    messages_per_step_worker: np.ndarray
    # Local relaxation passes of the whole run: how many times the local
    # stage swept the edge slots (`relax_passes`, below).
    relax_passes: int = 0

    @property
    def total_messages(self) -> int:
        return int(self.messages_per_worker.sum())

    @property
    def max_mean(self) -> float:
        """Paper Table-V max/mean message balance (single definition in
        repro.core.metrics)."""
        return max_mean_ratio(self.messages_per_worker)


# ----------------------------------------------------------- VertexProgram


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """Everything that varies between BSP algorithms, in one hashable value.

    A program is a static argument to the jitted drivers, so every field
    must be hashable; semantics are strings/bools/floats, and `init_fn` is
    a module-level function (compared by identity, which keeps the jit
    cache stable across calls).

    | field | meaning |
    |---|---|
    | dtype       | value dtype: "int32" or "float32" |
    | combine     | exchange reduction & local semiring: "min" | "max" | "sum" |
    | local       | "fixpoint" (relax to local convergence) or "sweep" (one out-degree-normalized push-sum pass — PageRank's compute) |
    | weight      | what the semiring adds along an edge: "none", "edge" (the f32 edge weight), or "unit" (+1, BFS hop counts) |
    | bidirectional | relax both edge directions (undirected algorithms) |
    | apply       | master-side post-combine step: "none" or "pagerank" (damping + renormalize) |
    | message_policy | "delta" (count only changed values — paper Tables IV/V) or "always" |
    | convergence | "no_change" (fixpoint reached) or "tol" (L1 step delta below `tol`) |
    | damping     | apply="pagerank" damping factor |
    | init_fn     | (sub, *, num_vertices, source) -> [p, max_v+1] initial values |
    | needs_source | facade resolves a default source vertex (SSSP/BFS) |
    | default_steps | driver step budget when the caller passes none (PR's classic 20 power iterations) |
    """

    name: str
    dtype: str
    combine: str = "min"
    local: str = "fixpoint"
    weight: str = "none"
    bidirectional: bool = False
    apply: str = "none"
    message_policy: str = "delta"
    convergence: str = "no_change"
    damping: float = 0.85
    init_fn: Optional[Callable] = None
    needs_source: bool = False
    default_steps: Optional[int] = None
    aliases: tuple = ()

    def __post_init__(self):
        checks = (
            ("dtype", self.dtype, ("int32", "float32")),
            ("combine", self.combine, ("min", "max", "sum")),
            ("local", self.local, ("fixpoint", "sweep")),
            ("weight", self.weight, ("none", "edge", "unit")),
            ("apply", self.apply, ("none", "pagerank")),
            ("message_policy", self.message_policy, ("delta", "always")),
            ("convergence", self.convergence, ("no_change", "tol")),
        )
        for field, got, allowed in checks:
            if got not in allowed:
                raise ValueError(f"VertexProgram.{field} must be one of {allowed}, got {got!r}")
        if self.combine == "sum" and self.local != "sweep":
            raise ValueError("combine='sum' has no fixpoint semantics; use local='sweep'")
        if self.apply == "pagerank" and self.combine != "sum":
            raise ValueError("apply='pagerank' renormalizes summed partials; use combine='sum'")

    @property
    def inf(self):
        """Largest representable "unreached" value of the program's dtype."""
        return INF_I32 if self.dtype == "int32" else INF_F32

    @property
    def identity(self):
        """Identity of the exchange combine (fills masked recv slots)."""
        if self.combine == "sum":
            return jnp.float32(0.0)
        return -self.inf if self.combine == "max" else self.inf

    def init(self, sub: SubgraphSet, *, num_vertices: int = 0, source=None) -> jax.Array:
        if self.init_fn is None:
            raise ValueError(
                f"program {self.name!r} has no init_fn — pass init_val explicitly to run_bsp"
            )
        if self.needs_source and source is None:
            raise ValueError(
                f"program {self.name!r} is source-rooted: pass source= "
                "(GraphPipeline defaults it to the highest-degree covered vertex)"
            )
        return self.init_fn(sub, num_vertices=num_vertices, source=source)


def _exec_view(prog: VertexProgram) -> tuple[VertexProgram, bool]:
    """The semiring actually executed: max-combine programs run as min over
    negated values (reusing the min-plus kernels); everything else runs
    as-is. Returns (program-for-the-superstep, negate-values?)."""
    if prog.combine != "max":
        return prog, False
    return dataclasses.replace(prog, combine="min"), True


# --------------------------------------------------------- program registry

PROGRAMS: dict[str, VertexProgram] = {}


def register_program(prog: VertexProgram) -> VertexProgram:
    """Register a program under its name and aliases for string lookup
    (`GraphPipeline.run("bfs")`, `run_bsp(sub, "cc")`, benchmarks)."""
    # Keys are stored lowercased to match get_program's case-insensitive
    # lookup, and all validated before inserting any, so a rejected
    # registration leaves the registry untouched.
    keys = tuple(k.lower() for k in (prog.name, *prog.aliases))
    for key in keys:
        if key in PROGRAMS:
            raise ValueError(f"program name {key!r} already registered")
    for key in keys:
        PROGRAMS[key] = prog
    return prog


def get_program(program) -> VertexProgram:
    """Resolve a program handle (VertexProgram instance or registered name)."""
    if isinstance(program, VertexProgram):
        return program
    key = str(program).lower()
    if key not in PROGRAMS:
        names = sorted({p.name for p in PROGRAMS.values()})
        raise ValueError(f"unknown program {program!r}; registered programs: {names}")
    return PROGRAMS[key]


def program_names() -> tuple:
    """Primary (alias-free) names of all registered programs."""
    return tuple(sorted({p.name for p in PROGRAMS.values()}))


# ------------------------------------------------------------- init values


def check_source(sub: SubgraphSet, source, num_vertices: int = 0) -> int:
    """Validate a query source vertex id and return it as a Python int.

    Source-rooted inits (SSSP/BFS) must fail fast on an out-of-range
    source — silently accepting one returns an all-INF "answer" that looks
    like an unreachable graph. The valid range is [0, num_vertices) when
    the caller knows the global vertex count, else [0, max covered gid]
    (the tightest bound the subgraph tensors themselves carry). The serving
    tier validates at admission time so one bad source rejects that query
    alone instead of poisoning a whole micro-batch.
    """
    if source is None:
        raise ValueError("source must be a vertex id, got None")
    s = int(source)
    hi = int(num_vertices) if num_vertices > 0 else int(jnp.max(sub.gid)) + 1
    if not 0 <= s < hi:
        raise ValueError(f"source={s} is out of range: valid vertex ids are [0, {hi})")
    return s


def init_cc(sub: SubgraphSet, *, num_vertices: int = 0, source=None) -> jax.Array:
    p = sub.gid.shape[0]
    val = jnp.where(sub.vmask, sub.gid, INF_I32)
    return jnp.concatenate([val, jnp.full((p, 1), INF_I32, jnp.int32)], axis=1)


def init_sssp(sub: SubgraphSet, source: int, *, num_vertices: int = 0) -> jax.Array:
    source = check_source(sub, source, num_vertices)
    p = sub.gid.shape[0]
    val = jnp.where(sub.gid == source, 0.0, INF_F32).astype(jnp.float32)
    return jnp.concatenate([val, jnp.full((p, 1), INF_F32, jnp.float32)], axis=1)


def init_pr(sub: SubgraphSet, num_vertices: int, *, source=None) -> jax.Array:
    p = sub.gid.shape[0]
    # Mirrors start with the same 1/N as masters (broadcast of the init) —
    # every present vertex replica holds the global initial rank.
    val = jnp.where(sub.vmask, 1.0 / num_vertices, 0.0).astype(jnp.float32)
    return jnp.concatenate([val, jnp.zeros((p, 1), jnp.float32)], axis=1)


def init_bfs(sub: SubgraphSet, source: int, *, num_vertices: int = 0) -> jax.Array:
    source = check_source(sub, source, num_vertices)
    p = sub.gid.shape[0]
    val = jnp.where(sub.gid == source, 0, INF_I32).astype(jnp.int32)
    return jnp.concatenate([val, jnp.full((p, 1), INF_I32, jnp.int32)], axis=1)


def init_reach(sub: SubgraphSet, *, num_vertices: int = 0, source=None) -> jax.Array:
    # Max-label propagation: absent slots hold the max identity (-INF).
    p = sub.gid.shape[0]
    val = jnp.where(sub.vmask, sub.gid, -INF_I32)
    return jnp.concatenate([val, jnp.full((p, 1), -INF_I32, jnp.int32)], axis=1)


# ---------------------------------------------------------- stock programs

CC = register_program(VertexProgram(
    name="cc", dtype="int32", combine="min", bidirectional=True,
    init_fn=lambda sub, *, num_vertices=0, source=None: init_cc(sub),
    aliases=("components", "connected_components"),
))

SSSP = register_program(VertexProgram(
    name="sssp", dtype="float32", combine="min", weight="edge",
    init_fn=lambda sub, *, num_vertices=0, source=None: init_sssp(
        sub, source, num_vertices=num_vertices
    ),
    needs_source=True,
))

PR = register_program(VertexProgram(
    name="pr", dtype="float32", combine="sum", local="sweep", apply="pagerank",
    message_policy="always", convergence="tol",
    init_fn=lambda sub, *, num_vertices=0, source=None: init_pr(sub, num_vertices),
    default_steps=20,  # the classic fixed-iteration power-method budget
    aliases=("pagerank",),
))

BFS = register_program(VertexProgram(
    name="bfs", dtype="int32", combine="min", weight="unit",
    init_fn=lambda sub, *, num_vertices=0, source=None: init_bfs(
        sub, source, num_vertices=num_vertices
    ),
    needs_source=True,
))

REACH = register_program(VertexProgram(
    name="reach", dtype="int32", combine="max", bidirectional=True,
    init_fn=lambda sub, *, num_vertices=0, source=None: init_reach(sub),
    aliases=("reachability",),
))


# ---------------------------------------------------------------- helpers


def _gather_rows(val: jax.Array, idx: jax.Array) -> jax.Array:
    """val: [p, max_v+1]; idx: [p, p, m] → out[i, j, m] = val[i, idx[i,j,m]]."""
    p = val.shape[0]
    return jnp.take_along_axis(val, idx.reshape(p, -1), axis=1).reshape(idx.shape)


def _scatter_min(val: jax.Array, idx: jax.Array, upd: jax.Array) -> jax.Array:
    p = val.shape[0]
    rows = jnp.arange(p)[:, None]
    return val.at[rows, idx.reshape(p, -1)].min(upd.reshape(p, -1))


def _scatter_add(val: jax.Array, idx: jax.Array, upd: jax.Array) -> jax.Array:
    p = val.shape[0]
    rows = jnp.arange(p)[:, None]
    return val.at[rows, idx.reshape(p, -1)].add(upd.reshape(p, -1))


def _scatter_set(val: jax.Array, idx: jax.Array, upd: jax.Array) -> jax.Array:
    p = val.shape[0]
    rows = jnp.arange(p)[:, None]
    return val.at[rows, idx.reshape(p, -1)].set(upd.reshape(p, -1))


def _sorted_segments(key: jax.Array, num_segments: int) -> tuple[jax.Array, jax.Array]:
    """The graph-only half of `_sorted_segment_min` for one sorted key row
    [E]: each slot's segment-start flag [E] (`key[e] != key[e-1]`, true at
    e = 0) and each segment's last slot [num_segments], -1 where it is
    empty — `searchsorted(key, u, side="right") - 1` where u has slots.

    Only a segment's last slot writes its position; every other slot gets
    an index of its own past the end, which the scatter drops, so the
    indices are unique."""
    last = jnp.concatenate([key[1:] != key[:-1], jnp.ones((1,), bool)])
    slots = jnp.arange(key.shape[0], dtype=jnp.int32)
    idx = jnp.where(last, key, num_segments + slots)
    end = jnp.full((num_segments,), -1, jnp.int32).at[idx].set(
        slots, mode="drop", unique_indices=True
    )
    return jnp.concatenate([jnp.ones((1,), bool), last[:-1]]), end


def _sorted_segment_min(data: jax.Array, start: jax.Array, end: jax.Array) -> jax.Array:
    """Per-segment minimum of one row [E] whose segment keys are sorted, by
    a segmented min-scan along the slots read at each segment's last slot
    (`start`, `end` from `_sorted_segments`). Min is exact in any order, so
    this equals `jax.ops.segment_min` bit for bit, an empty segment reading
    the dtype's largest value as it does there; unlike it, no pass
    scatters into the [num_segments] output.

    The scan combines (start, value) pairs by (fa | fb, vb if fb else
    min(va, vb)), in log2(E) doubling steps (Hillis-Steele): after the step
    of shift k each slot holds the combine of the 2k slots ending at it,
    slots before the row's start reading as the identity. The TPU compiler
    takes minutes over `jax.lax.associative_scan` at millions of slots and
    seconds over this form."""
    if jnp.issubdtype(data.dtype, jnp.integer):
        identity = jnp.asarray(jnp.iinfo(data.dtype).max, data.dtype)
    else:
        identity = jnp.asarray(jnp.inf, data.dtype)
    n = data.shape[0]
    flag, run = start, data
    k = 1
    while k < n:
        prev = jnp.concatenate([jnp.full((k,), identity), run[:-k]])
        run = jnp.where(flag, run, jnp.minimum(prev, run))
        flag = flag | jnp.concatenate([jnp.ones((k,), bool), flag[:-k]])
        k *= 2
    return jnp.where(end >= 0, run[jnp.maximum(end, 0)], identity)


# -------------------------------------------------- local compute (stage 1)


def _edge_addend(prog: VertexProgram, weight: jax.Array, dtype) -> Optional[jax.Array]:
    """What the semiring adds along an edge, or None for weight='none'."""
    if prog.weight == "edge":
        return weight.astype(dtype)
    if prog.weight == "unit":
        return jnp.ones_like(weight, dtype=dtype)
    return None


def _add_saturating(prog: VertexProgram, data: jax.Array, w: jax.Array) -> jax.Array:
    """data + w with the INF identity absorbing: int32 INF + 1 must stay INF
    (not wrap to INT32_MIN and win every min — BFS over unreached sources).
    f32 INF absorbs additions natively."""
    if prog.dtype == "int32":
        return jnp.where(data >= prog.inf, prog.inf, data + w)
    return data + w


def _local_segments(prog: VertexProgram, sub: SubgraphSet, backend: str):
    """`_relax_xla`'s graph-only input: the `_sorted_segments` of every
    worker's reduce keys — `ldst`, and `lsrc_s` for bidirectional programs
    (else None) — or None where the local stage does not run `_relax_xla`.
    The drivers compute it once per run, before their loops; in batched
    runs it is shared by every query."""
    if backend != "xla" or prog.local != "fixpoint":
        return None
    segments = jax.vmap(functools.partial(_sorted_segments, num_segments=sub.max_v + 1))
    return segments(sub.ldst), (segments(sub.lsrc_s) if prog.bidirectional else None)


def _relax_xla(prog: VertexProgram, sub: SubgraphSet, v: jax.Array, segs=None) -> jax.Array:
    """One local relaxation pass: each edge slot reads its source's value,
    and each vertex takes the minimum over its (sorted) slots by
    `_sorted_segment_min`. `segs` is `_local_segments(prog, sub, "xla")`;
    None computes it here, in the pass."""
    if segs is None:
        segs = _local_segments(prog, sub, "xla")
    fwd, rev = segs
    inf = prog.inf
    data = jnp.take_along_axis(v, sub.lsrc, axis=1)
    w = _edge_addend(prog, sub.weight, v.dtype)
    if w is not None:
        data = _add_saturating(prog, data, w)
    data = jnp.where(sub.edge_mask, data, inf)
    new = jnp.minimum(v, jax.vmap(_sorted_segment_min)(data, *fwd))
    if prog.bidirectional:
        data2 = jnp.take_along_axis(v, sub.ldst_s, axis=1)
        w2 = _edge_addend(prog, sub.weight_s, v.dtype)
        if w2 is not None:
            data2 = _add_saturating(prog, data2, w2)
        data2 = jnp.where(sub.edge_mask_s, data2, inf)
        new = jnp.minimum(new, jax.vmap(_sorted_segment_min)(data2, *rev))
    return new


def _relax_stream(prog: VertexProgram, sub: SubgraphSet):
    """[p, E(+E)] (lsrc, ldst, weight) edge stream for `ops.bsp_superstep`:
    the forward CSR half and, for bidirectional programs, the reversed
    (src-sorted) half concatenated behind it. Weights are the semiring
    addend in f32 with padded edges carrying the INF identity; each half is
    dst-sorted, which is all the megakernel's rank compression needs."""

    def edge_w(weight, mask):
        w = _edge_addend(prog, weight, jnp.float32)
        if w is None:
            w = jnp.zeros_like(weight)
        return jnp.where(mask, w, INF_F32)

    lsrc, ldst, w = sub.lsrc, sub.ldst, edge_w(sub.weight, sub.edge_mask)
    if prog.bidirectional:
        # Reverse direction: reduce into sources using the src-sorted edge
        # copy (lsrc_s is the sorted/destination role here).
        lsrc = jnp.concatenate([lsrc, sub.ldst_s], axis=1)
        ldst = jnp.concatenate([ldst, sub.lsrc_s], axis=1)
        w = jnp.concatenate([w, edge_w(sub.weight_s, sub.edge_mask_s)], axis=1)
    return lsrc, ldst, w


def _local_fixpoint(
    prog: VertexProgram,
    sub: SubgraphSet,
    val: jax.Array,
    inner_cap: int,
    backend: str = "xla",
    interpret: bool | None = None,
    block_e: int = 512,
    segs=None,
):
    """Batched local fixpoint. val: [p, max_v+1] (last slot = dump).

    backend "xla" loops `_relax_xla` passes (`segs`, its graph-only input,
    is computed here, before the loop, where the driver passes none);
    "ref"/"pallas" route the WHOLE
    local stage (every relaxation pass + the per-worker convergence flag)
    through the `ops.bsp_superstep` megakernel in one launch. For int32
    programs (CC/BFS/REACH) the kernel path remaps INF_I32 <-> INF_F32 and
    runs in f32 — exact only for values below 2^24 (`run_bsp` enforces
    this; graphs beyond it must use backend "xla"). The fused drivers hoist
    that remap to the run boundary by passing an f32 exec view of the
    program; this in-place branch only pays per call for the host driver.
    """
    to_f32 = backend != "xla" and prog.dtype == "int32"
    v0 = jnp.where(val == INF_I32, INF_F32, val.astype(jnp.float32)) if to_f32 else val

    if backend != "xla":
        lsrc, ldst, w = _relax_stream(prog, sub)
        new_val, iters = ops.bsp_superstep(
            lsrc, ldst, w, v0, num_out=sub.max_v + 1, combine="min",
            inner_cap=inner_cap, impl=backend, block_e=block_e, interpret=interpret,
        )
        if to_f32:
            new_val = jnp.where(new_val >= INF_F32, INF_I32, new_val.astype(jnp.int32))
        return new_val, iters

    if segs is None:
        segs = _local_segments(prog, sub, backend)
    relax = functools.partial(_relax_xla, prog, sub, segs=segs)

    def body_count(carry):
        v, ch, it, iters = carry
        new = relax(v)
        ch = jnp.any(new != v, axis=1)  # per worker
        return new, ch, it + 1, iters + ch.astype(jnp.int32)

    p = val.shape[0]
    carry = (v0, jnp.ones((p,), bool), jnp.int32(0), jnp.zeros((p,), jnp.int32))
    carry = jax.lax.while_loop(lambda c: jnp.any(c[1]) & (c[2] < inner_cap), body_count, carry)
    new_val, _, _, iters = carry
    return new_val, iters


def _local_sweep(
    prog: VertexProgram,
    sub: SubgraphSet,
    val: jax.Array,
    backend: str = "xla",
    interpret: bool | None = None,
    block_e: int = 512,
) -> jax.Array:
    """One out-degree-normalized push-sum pass (PageRank's local compute):
    each vertex pushes val/outdeg along its out-edges, summed at dst."""
    p = val.shape[0]
    nseg = sub.max_v + 1
    outdeg = jnp.concatenate([sub.out_degree, jnp.ones((p, 1), jnp.float32)], axis=1)
    if backend != "xla":
        # Megakernel path: the share division is fused at the gather, padded
        # edges carry weight 0 (the sum identity and the kernel's pad mask).
        scale = sub.edge_mask.astype(jnp.float32)
        new, _ = ops.bsp_superstep(
            sub.lsrc, sub.ldst, scale, val, num_out=nseg, combine="sum",
            out_degree=outdeg, impl=backend, block_e=block_e, interpret=interpret,
        )
        return new
    share = jnp.where(outdeg > 0, val / outdeg, 0.0)
    data = jnp.take_along_axis(share, sub.lsrc, axis=1)
    data = jnp.where(sub.edge_mask, data, 0.0)
    return jax.vmap(
        lambda d, s: jax.ops.segment_sum(d, s, num_segments=nseg, indices_are_sorted=True)
    )(data, sub.ldst)


# --------------------------------------------------- THE generic superstep


def _apply_step(prog: VertexProgram, sub: SubgraphSet, combined: jax.Array, num_vertices: int):
    """Master-side post-combine step. "none" passes the combined value
    through; "pagerank" turns summed partials into damped, renormalized
    ranks at masters (mirrors zeroed until the broadcast)."""
    if prog.apply == "none":
        return combined
    p = combined.shape[0]
    base = (1.0 - prog.damping) / num_vertices
    new = jnp.where(sub.is_master, base + prog.damping * combined[:, : sub.max_v], 0.0)
    return jnp.concatenate([new, jnp.zeros((p, 1), jnp.float32)], axis=1)


def _superstep(
    prog: VertexProgram,
    sub: SubgraphSet,
    val,
    exchange,
    inner_cap: int,
    do_exchange: bool = True,
    count_ref=None,
    num_vertices: int = 0,
    backend: str = "xla",
    interpret: bool | None = None,
    block_e: int = 512,
    segs=None,
):
    """ONE BSP superstep for ANY program. Returns
    (new_val, per-worker msg count, per-worker inner iters, L1 delta).

    Stages: local compute → mirror→master exchange + combine → apply →
    master→mirror broadcast, under the named scopes `bsp.local`,
    `bsp.exchange` (both exchanges) and `bsp.apply`, so a profile can tell
    them apart. `count_ref` is the value snapshot of the LAST
    exchange — delta messages are counted against it (matters under bounded
    staleness). The L1 delta is only materialized for convergence='tol'
    programs (a zero scalar otherwise). `segs` is the run's
    `_local_segments`, handed to the local stage.
    """
    p = val.shape[0]
    start = val if count_ref is None else count_ref

    # 1. local compute. Fixpoint programs carry the value itself; sweep
    # programs carry the per-vertex partial aggregate (one sweep = one
    # inner iteration of comp work per worker).
    with jax.named_scope("bsp.local"):
        if prog.local == "fixpoint":
            state, iters = _local_fixpoint(
                prog, sub, val, inner_cap, backend, interpret, block_e, segs
            )
        else:
            state = _local_sweep(prog, sub, val, backend, interpret, block_e)
            iters = jnp.ones((p,), jnp.int32)
    if not do_exchange:  # bounded-staleness local step (straggler mitigation)
        return state, jnp.zeros((p,), jnp.int32), iters, jnp.float32(0.0)

    # 2. mirror → master (forward): send current state of mirror slots.
    with jax.named_scope("bsp.exchange"):
        S = _gather_rows(state, sub.send_idx)  # [i, j, m]
        if prog.message_policy == "delta":
            changed = state != start
            ch_send = jnp.take_along_axis(
                changed, sub.send_idx.reshape(p, -1), axis=1
            ).reshape(sub.send_idx.shape)
            msgs_fwd = jnp.sum(ch_send & sub.msg_mask, axis=(1, 2))
        else:
            msgs_fwd = jnp.sum(sub.msg_mask, axis=(1, 2))
        R = exchange(S)  # receiver-rowed [j, i, m]
        upd = jnp.where(sub.recv_mask, R, prog.identity)
        if prog.combine == "sum":
            combined = _scatter_add(state, sub.recv_idx, upd)
        else:
            combined = _scatter_min(state, sub.recv_idx, upd)

    # 3. apply at masters, then master → mirror (broadcast).
    with jax.named_scope("bsp.apply"):
        new_val = _apply_step(prog, sub, combined, num_vertices)
    with jax.named_scope("bsp.exchange"):
        B = _gather_rows(new_val, sub.recv_idx)  # [j, i, m] master values
        if prog.message_policy == "delta":
            ch_master = new_val != start
            ch_b = jnp.take_along_axis(
                ch_master, sub.recv_idx.reshape(p, -1), axis=1
            ).reshape(sub.recv_idx.shape)
            msgs_bwd = jnp.sum(ch_b & sub.recv_mask, axis=(1, 2))
        else:
            msgs_bwd = jnp.sum(sub.recv_mask, axis=(1, 2))
        Rb = exchange(B)  # sender-rowed view at mirrors: [i, j, m]
        idx_masked = jnp.where(sub.msg_mask, sub.send_idx, sub.max_v)
        out = _scatter_set(new_val, idx_masked, Rb)

    if prog.convergence == "tol":
        delta = jnp.abs(out[:, : sub.max_v] - val[:, : sub.max_v]).sum()
    else:
        delta = jnp.float32(0.0)
    return out, msgs_fwd + msgs_bwd, iters, delta


def check_int32_kernel_gid(prog: VertexProgram, gid: jax.Array, compute_backend: str) -> None:
    """FLAT-addressing guard: refuse kernel backends for int32 programs
    whose global-id space reaches 2^24.

    The kernel path runs the int32 semiring in f32, which is only exact for
    magnitudes below 2^24 — larger values would merge distinct CC/REACH
    labels (or BFS hop counts) silently. Under flat addressing the kernel
    label domain IS the global id space, so `max(gid)` bounds every int32
    program's finite values: CC/REACH propagate the labels themselves, and
    BFS hop counts are below the covered-vertex count <= max(gid)+1.
    Two-level runs enforce at the VALUE boundary instead
    (`check_int32_kernel_values` via `_kernel_value_boundary`), which is
    what lets 2^24+-vertex graphs stay exact on ref/pallas.
    """
    check_compute_backend(compute_backend)
    if compute_backend != "xla" and prog.dtype == "int32":
        max_label = int(jnp.max(gid))
        if max_label >= 1 << 24:
            raise ValueError(
                f"compute_backend={compute_backend!r} runs int32 {prog.name} in f32, "
                f"exact only for vertex ids < 2^24; graph has id {max_label} — "
                "use compute_backend='xla'"
            )


def check_int32_kernel_values(prog: VertexProgram, bound, compute_backend: str) -> None:
    """TWO-LEVEL-addressing guard at the kernel VALUE boundary.

    `bound` is the run's proven ceiling on every finite kernel value's
    magnitude — the max over workers of per-worker local value maxima
    (label-domain programs: the rank-codec size; unit-weight programs:
    the covered-vertex count bounding hop growth). Same exactness rule
    as `check_int32_kernel_gid`, applied to what the kernels actually
    see instead of the global id space.
    """
    check_compute_backend(compute_backend)
    if compute_backend != "xla" and prog.dtype == "int32":
        bound = int(bound)
        if bound >= 1 << 24:
            raise ValueError(
                f"compute_backend={compute_backend!r} runs int32 {prog.name} in f32, "
                f"exact only for kernel values < 2^24; this run's per-worker value "
                f"bound is {bound} — use compute_backend='xla'"
            )


def check_int32_kernel_labels(prog: VertexProgram, sub: SubgraphSet, compute_backend: str) -> None:
    """Addressing-aware kernel-boundary guard over a SubgraphSet.

    Flat addressing keeps the legacy global-id guard. Two-level addressing
    defers to the value boundary (`_kernel_value_boundary` in the drivers):
    label-domain programs are rank-compressed below 2^24 there and the
    guard checks per-worker value maxima, so a >= 2^24-vertex graph passes
    clean where flat addressing must raise.
    """
    check_addressing(sub.addressing)
    if sub.addressing == "flat":
        check_int32_kernel_gid(prog, sub.gid, compute_backend)


def _label_domain(prog: VertexProgram) -> bool:
    """True for programs whose finite values form a CLOSED label set: the
    semiring only ever min/max-combines values already present at init
    (CC/REACH label propagation), never synthesizes new finite values.
    Exactly these programs admit lossless rank compression."""
    return (
        prog.dtype == "int32"
        and prog.weight == "none"
        and prog.apply == "none"
        and prog.local == "fixpoint"
        and prog.combine in ("min", "max")
    )


@dataclasses.dataclass(frozen=True)
class _ValueCodec:
    """Order-preserving bijection between a closed finite label set and
    dense int32 ranks [0, size), with the exec-domain INF_I32 sentinel
    fixed. min/max, delta message counts, and no-change convergence
    commute with any strictly monotone map, so a BSP run over encoded
    values is step-for-step identical to the raw run — while the kernels
    only ever see ranks < size <= covered vertices, far below 2^24 even
    when the labels themselves are 2^24+ global ids."""

    uniq: tuple  # sorted distinct finite exec-domain values (hashable)

    @classmethod
    def from_values(cls, values: np.ndarray) -> "_ValueCodec":
        v = np.asarray(values)
        finite = np.abs(v.astype(np.int64)) != int(INF_I32)
        return cls(uniq=tuple(np.unique(v[finite]).tolist()))

    @property
    def size(self) -> int:
        return len(self.uniq)

    def _table(self) -> jax.Array:
        return jnp.asarray(np.asarray(self.uniq, np.int32))

    def encode(self, val: jax.Array) -> jax.Array:
        finite = jnp.abs(val) != INF_I32
        ranks = jnp.searchsorted(self._table(), val).astype(jnp.int32)
        return jnp.where(finite, ranks, val)

    def decode(self, val: jax.Array) -> jax.Array:
        finite = jnp.abs(val) != INF_I32
        idx = jnp.clip(val, 0, max(self.size - 1, 0))
        return jnp.where(finite, self._table()[idx], val)


def _kernel_value_boundary(
    prog: VertexProgram, sub: SubgraphSet, val: jax.Array, compute_backend: str
) -> tuple[jax.Array, Optional[_ValueCodec]]:
    """Two-level enforcement where values cross into the kernels (exec
    domain, i.e. after any max→min negation). Returns (kernel-ready
    values, codec-or-None); callers decode driver output with the codec.

    label-domain programs → rank-compress (bound = codec size); unit-weight
    programs (BFS hops) → bound = current max + covered vertices; any other
    int32 program falls back to the conservative global-id guard (its value
    growth is unknown — use flat addressing if that guard is too strict).
    """
    if compute_backend == "xla" or prog.dtype != "int32" or sub.addressing == "flat":
        return val, None
    if _label_domain(prog):
        codec = _ValueCodec.from_values(np.asarray(val))
        check_int32_kernel_values(prog, max(codec.size - 1, 0), compute_backend)
        return codec.encode(val), codec
    if prog.weight == "unit":
        covered = int(np.asarray(sub.is_master).sum())
        vnp = np.abs(np.asarray(val).astype(np.int64))
        finite = vnp != int(INF_I32)
        base = int(vnp[finite].max()) if finite.any() else 0
        check_int32_kernel_values(prog, base + covered, compute_backend)
        return val, None
    check_int32_kernel_gid(prog, sub.gid, compute_backend)
    return val, None


# ------------------------------------------------------------ entry points


def _sim_exchange(S: jax.Array) -> jax.Array:
    return jnp.swapaxes(S, 0, 1)


@functools.partial(
    jax.jit,
    static_argnames=("prog", "inner_cap", "do_exchange", "num_vertices", "backend", "block_e"),
)
def _jit_superstep_sim(prog, sub, val, inner_cap, do_exchange, count_ref, num_vertices=0,
                       backend="xla", block_e=512):
    obs.count("engine.trace")  # runs only while JAX traces
    return _superstep(
        prog, sub, val, _sim_exchange, inner_cap, do_exchange, count_ref, num_vertices, backend,
        block_e=block_e,
    )


# ------------------------------------------------------- fused sim driver
#
# The host loop in `run_bsp` dispatches one device program per superstep and
# syncs after each one (np.asarray of the message counts, the convergence
# check). The fused driver runs the WHOLE BSP loop inside one jitted
# lax.while_loop: per-step stats land in preallocated [max_supersteps, p]
# on-device buffers, convergence exits the loop inside the trace, the value
# carry is donated, and the host syncs exactly once per run to fetch
# (steps, stats).


@functools.partial(
    jax.jit,
    static_argnames=("prog", "max_supersteps", "inner_cap", "exchange_period", "tol",
                     "num_vertices", "backend", "block_e"),
    donate_argnums=(1,),
)
def _fused_bsp(sub, val, *, prog, max_supersteps, inner_cap, exchange_period, tol,
               num_vertices, backend, block_e=512):
    # Kernel backends run int32 programs in f32. Hoist the INF_I32 <->
    # INF_F32 remap OUT of the superstep loop: remap once here, run the
    # whole loop on an f32 exec view of the program, remap once on exit.
    # The remap is a bijection on every occurring value, so values, message
    # counts, and convergence are bit-identical to the in-loop remap (and
    # the host driver, which still pays it per superstep in
    # `_local_fixpoint`). Pinned by test_fused_no_inloop_remap.
    obs.count("engine.trace")  # runs only while JAX traces
    to_f32 = backend != "xla" and prog.dtype == "int32"
    if to_f32:
        val = jnp.where(val == INF_I32, INF_F32, val.astype(jnp.float32))
        prog = dataclasses.replace(prog, dtype="float32")
    p = val.shape[0]
    msgs_buf = jnp.zeros((max_supersteps, p), jnp.int32)
    iters_buf = jnp.zeros((max_supersteps, p), jnp.int32)
    segs = _local_segments(prog, sub, backend)  # graph-only: once per run

    def converged_flag(v, v2, do_ex, delta):
        if prog.convergence == "tol":
            return (delta < tol) if tol else jnp.bool_(False)
        # Converged only when an exchange round produced no change anywhere
        # (identical to the host driver's break condition).
        return do_ex & ~jnp.any(v2 != v)

    def cond(carry):
        _, _, k, done, _, _ = carry
        return ~done & (k < max_supersteps)

    def body(carry):
        v, last_ex, k, _, msgs_buf, iters_buf = carry
        if exchange_period == 1:
            # Static specialization of the common case: every step exchanges,
            # so the trace needs no branch or last-exchange select.
            v2, msgs, iters, delta = _superstep(
                prog, sub, v, _sim_exchange, inner_cap, True, last_ex, num_vertices, backend,
                block_e=block_e, segs=segs,
            )
            converged = converged_flag(v, v2, jnp.bool_(True), delta)
            last_ex = v2
        else:
            do_ex = (k % exchange_period) == (exchange_period - 1)
            v2, msgs, iters, delta = jax.lax.cond(
                do_ex,
                lambda v_, le: _superstep(
                    prog, sub, v_, _sim_exchange, inner_cap, True, le, num_vertices, backend,
                    block_e=block_e, segs=segs,
                ),
                lambda v_, le: _superstep(
                    prog, sub, v_, _sim_exchange, inner_cap, False, le, num_vertices, backend,
                    block_e=block_e, segs=segs,
                ),
                v, last_ex,
            )
            converged = converged_flag(v, v2, do_ex, delta)
            last_ex = jnp.where(do_ex, v2, last_ex)
        return (v2, last_ex, k + 1, converged, msgs_buf.at[k].set(msgs), iters_buf.at[k].set(iters))

    carry = (val, val, jnp.int32(0), jnp.bool_(False), msgs_buf, iters_buf)
    val, _, steps, converged, msgs_buf, iters_buf = jax.lax.while_loop(cond, body, carry)
    if to_f32:
        val = jnp.where(val >= INF_F32, INF_I32, val.astype(jnp.int32))
    # Edge counts ride along so the stats assembly needs no extra dispatch.
    # The converged flag disambiguates "fixpoint reached on the last step"
    # from "step budget exhausted" — the checkpointed segment driver in
    # repro.resilience.bsp needs it to stop instead of launching a phantom
    # extra segment (which would append a superstep the uninterrupted run
    # never paid, breaking bit-parity of the stats).
    edges = jnp.sum(sub.edge_mask, axis=1, dtype=jnp.int32)
    return val, steps, converged, msgs_buf, iters_buf, edges


def relax_passes(prog: VertexProgram, iters_sw: np.ndarray, inner_cap: int) -> int:
    """Local relaxation passes of a run, from its [steps, p] inner-iteration
    counts: how many times the local stage swept the edge slots.

    A sweep program makes one pass per superstep. A fixpoint program's
    workers relax independently inside the local loop (a worker's pass
    reads only its own values), so a worker that stops changing stays
    unchanged, and `iters[s, w]` — the passes in which w changed — are the
    first passes of superstep s. The batched `while_loop` stops after the
    first pass in which no worker changed, or at `inner_cap`: it runs
    `min(max_w iters[s, w] + 1, inner_cap)` passes. Counted on the host at
    stats assembly, the same way for the sim, dist and batched drivers (a
    dist device loops over its own workers only, so the count is the
    busiest device's). The kernel backends loop each worker to its own
    count; the number is the XLA loop's all the same.
    """
    steps = iters_sw.shape[0]
    if prog.local != "fixpoint":
        return int(steps)
    if steps == 0:
        return 0
    return int(np.minimum(iters_sw.max(axis=1) + 1, inner_cap).sum())


def _assemble_stats(steps: int, msgs_sw: np.ndarray, iters_sw: np.ndarray,
                    edges: np.ndarray, prog: VertexProgram, inner_cap: int) -> BSPStats:
    return BSPStats(
        supersteps=steps,
        messages_per_worker=msgs_sw.sum(axis=0),
        messages_per_step=msgs_sw.sum(axis=1),
        comp_work_per_worker=(iters_sw * edges[None, :]).sum(axis=0),
        inner_iters_per_step=iters_sw,
        messages_per_step_worker=msgs_sw,
        relax_passes=relax_passes(prog, iters_sw, inner_cap),
    )


def check_pagerank_num_vertices(prog: VertexProgram, num_vertices: int) -> None:
    """pagerank-apply programs renormalize by the GLOBAL vertex count at
    trace time — fail with a named argument, not a ZeroDivisionError."""
    if prog.apply == "pagerank" and num_vertices <= 0:
        raise ValueError(
            f"program {prog.name!r} renormalizes by the global vertex count: "
            "pass num_vertices= (GraphPipeline supplies graph.num_vertices)"
        )


def run_bsp(
    sub: SubgraphSet,
    program,
    init_val: Optional[jax.Array] = None,
    *,
    max_supersteps: Optional[int] = None,
    inner_cap: int = 10_000,
    exchange_period: int = 1,
    tol: float = 0.0,
    num_vertices: int = 0,
    source=None,
    compute_backend: str = "xla",
    driver: str = "fused",
    block_e: int = 512,
    checkpoint_every: Optional[int] = None,
    ckpt_dir=None,
    fault_plan=None,
) -> tuple[jax.Array, BSPStats]:
    """THE simulation-mode driver: runs any `VertexProgram` (instance or
    registered name). exchange_period>1 = bounded staleness (fixpoint
    programs only).

    Fault tolerance (docs/api.md "Fault tolerance"): `checkpoint_every=k`
    with `ckpt_dir=` snapshots the value carry + per-step stats buffers
    every k supersteps through `repro.checkpoint.ckpt`, and `fault_plan=`
    (a `repro.resilience.FaultPlan`) injects a deterministic worker crash;
    `repro.resilience.resume_bsp` restores the last checkpoint and
    continues to a final state bit-identical to an uninterrupted run. Any
    of the three kwargs routes the run through the segmented driver in
    `repro.resilience.bsp` (same values and stats, pinned by
    tests/test_resilience.py).

    init_val defaults to the program's own `init_fn` (pass `source=` /
    `num_vertices=` as the program needs). max_supersteps=None takes the
    program's `default_steps` budget (PR: 20), else 200. compute_backend
    selects the
    local-compute implementation (see repro.api.config.COMPUTE_BACKENDS);
    all backends converge to the same fixpoint. driver="fused" runs the
    whole loop as one device program; driver="host" dispatches one
    superstep per Python iteration (identical values and stats —
    tests/test_drivers.py pins the equivalence). `tol` is the L1 step-delta
    convergence threshold for convergence='tol' programs (0 = run all
    max_supersteps, PageRank's fixed-iteration mode). `block_e` is the
    megakernel's edge-block size for kernel backends (VMEM streaming
    granularity — see docs/api.md "Performance guide"; ignored by "xla";
    values are bit-identical across block_e choices).

    driver="fused" DONATES the initial value buffer to the device program
    (that is where the fused loop's zero-copy value carry starts): on
    accelerators the caller's buffer is consumed, so build a fresh init per
    run (as repro.graph.algorithms does) rather than reusing one across
    calls.
    """
    if checkpoint_every is not None or ckpt_dir is not None or fault_plan is not None:
        # Deferred import: resilience builds on this module.
        from repro.resilience.bsp import run_bsp_resilient

        return run_bsp_resilient(
            sub, program, init_val,
            max_supersteps=max_supersteps, inner_cap=inner_cap,
            exchange_period=exchange_period, tol=tol, num_vertices=num_vertices,
            source=source, compute_backend=compute_backend, driver=driver,
            block_e=block_e,
            checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir, fault_plan=fault_plan,
        )
    prog = get_program(program)
    check_int32_kernel_labels(prog, sub, compute_backend)
    check_pagerank_num_vertices(prog, num_vertices)
    check_driver(driver)
    if max_supersteps is None:
        max_supersteps = prog.default_steps or 200
    if exchange_period > 1 and (prog.local != "fixpoint" or prog.convergence != "no_change"):
        raise ValueError(
            f"exchange_period>1 (bounded staleness) needs a fixpoint/no-change program; "
            f"{prog.name!r} is local={prog.local!r}, convergence={prog.convergence!r}"
        )
    with obs.span("engine.prepare"):
        if init_val is None:
            init_val = prog.init(sub, num_vertices=num_vertices, source=source)
        # Max-combine runs as min over negated values (kernel reuse); delta
        # message counts and no-change convergence are negation-invariant.
        exec_prog, negate = _exec_view(prog)
        val = -init_val if negate else init_val
        # Two-level runs rank-compress label-domain values here so the kernels
        # only ever see ranks < 2^24; codec=None means values pass raw.
        val, codec = _kernel_value_boundary(prog, sub, val, compute_backend)
    p = val.shape[0]

    if driver == "fused":
        with obs.span("engine.dispatch"):
            val, steps, _, msgs_buf, iters_buf, edges = _fused_bsp(
                sub,
                val,
                prog=exec_prog,
                max_supersteps=max_supersteps,
                inner_cap=inner_cap,
                exchange_period=exchange_period,
                tol=tol,
                num_vertices=num_vertices,
                backend=compute_backend,
                block_e=block_e,
            )
        obs.count("engine.dispatch.fused")
        # The run's single host sync: one device_get for every stat buffer.
        with obs.span("engine.fetch"):
            steps, msgs_sw, iters_sw, edges = jax.device_get((steps, msgs_buf, iters_buf, edges))
        steps = int(steps)
        if codec is not None:
            val = codec.decode(val)
        return (-val if negate else val), _assemble_stats(
            steps,
            msgs_sw[:steps].astype(np.int64),
            iters_sw[:steps].astype(np.int64),
            edges.astype(np.int64),
            exec_prog,
            inner_cap,
        )

    msg_steps = []
    iters_steps = []
    edges = np.asarray(sub.edge_mask.sum(axis=1), np.int64)
    steps = 0
    last_exchanged = val
    for k in range(max_supersteps):
        do_exchange = (k % exchange_period) == exchange_period - 1
        before = val
        val, msgs, iters, delta = _jit_superstep_sim(
            exec_prog, sub, val, inner_cap, do_exchange, last_exchanged,
            num_vertices, compute_backend, block_e,
        )
        obs.count("engine.dispatch.host")
        if do_exchange:
            last_exchanged = val
        steps += 1
        msg_steps.append(np.asarray(msgs, np.int64))
        iters_steps.append(np.asarray(iters, np.int64))
        if prog.convergence == "tol":
            if tol and float(delta) < tol:
                break
        # Converged only when an exchange round produced no change anywhere.
        elif do_exchange and not bool(jnp.any(val != before)):
            break
    msgs_sw = np.asarray(msg_steps).reshape(steps, p)
    iters_sw = np.asarray(iters_steps).reshape(steps, p)
    if codec is not None:
        val = codec.decode(val)
    return (-val if negate else val), _assemble_stats(
        steps, msgs_sw, iters_sw, edges, exec_prog, inner_cap
    )


# ----------------------------------------------- batched fused sim driver
#
# The serving tier runs a [B] batch of point queries over SHARED subgraph
# structure in one fused dispatch: the generic superstep is vmapped over a
# leading batch axis and the whole loop is one jitted lax.while_loop. A
# per-query convergence mask freezes finished queries — their values stop
# evolving and they stop contributing messages/inner iterations — while
# stragglers run to their own fixpoint, so per-query BSPStats report the
# supersteps each query actually paid (not the batch max) and are
# bit-identical to B separate single-source `run_bsp` runs.


@functools.partial(
    jax.jit,
    static_argnames=("prog", "max_supersteps", "inner_cap", "tol", "num_vertices", "backend",
                     "block_e"),
    donate_argnums=(1,),
)
def _fused_bsp_batch(sub, vals, *, prog, max_supersteps, inner_cap, tol, num_vertices, backend,
                     block_e=512):
    # Same run-boundary hoist of the kernel path's int32<->f32 remap as
    # `_fused_bsp` (bijective, so per-query values/stats are unchanged).
    obs.count("engine.trace")  # runs only while JAX traces
    to_f32 = backend != "xla" and prog.dtype == "int32"
    if to_f32:
        vals = jnp.where(vals == INF_I32, INF_F32, vals.astype(jnp.float32))
        prog = dataclasses.replace(prog, dtype="float32")
    B = vals.shape[0]
    p = vals.shape[1]
    msgs_buf = jnp.zeros((max_supersteps, B, p), jnp.int32)
    iters_buf = jnp.zeros((max_supersteps, B, p), jnp.int32)
    # Every step exchanges (exchange_period=1), so the delta-message
    # reference is the entry value itself — count_ref=None, as in the
    # specialized period-1 branch of `_fused_bsp`. The graph-only segments
    # are computed once, outside the vmap, and shared by every query.
    segs = _local_segments(prog, sub, backend)
    vstep = jax.vmap(
        lambda v: _superstep(
            prog, sub, v, _sim_exchange, inner_cap, True, None, num_vertices, backend,
            block_e=block_e, segs=segs,
        )
    )

    def cond(carry):
        _, k, done, _, _, _ = carry
        return ~jnp.all(done) & (k < max_supersteps)

    def body(carry):
        v, k, done, steps_q, msgs_buf, iters_buf = carry
        v2, msgs, iters, delta = vstep(v)
        if prog.convergence == "tol":
            newly = (delta < tol) if tol else jnp.zeros((B,), bool)
        else:
            newly = ~jnp.any(v2 != v, axis=(1, 2))
        # Convergence masking: finished queries keep their values and send
        # nothing while stragglers run.
        v2 = jnp.where(done[:, None, None], v, v2)
        msgs = jnp.where(done[:, None], 0, msgs)
        iters = jnp.where(done[:, None], 0, iters)
        steps_q = steps_q + (~done).astype(jnp.int32)
        done = done | newly
        return v2, k + 1, done, steps_q, msgs_buf.at[k].set(msgs), iters_buf.at[k].set(iters)

    carry = (vals, jnp.int32(0), jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
             msgs_buf, iters_buf)
    vals, _, _, steps_q, msgs_buf, iters_buf = jax.lax.while_loop(cond, body, carry)
    if to_f32:
        vals = jnp.where(vals >= INF_F32, INF_I32, vals.astype(jnp.int32))
    edges = jnp.sum(sub.edge_mask, axis=1, dtype=jnp.int32)
    return vals, steps_q, msgs_buf, iters_buf, edges


def batch_init(prog, sub: SubgraphSet, sources=None, *, batch: Optional[int] = None,
               num_vertices: int = 0) -> jax.Array:
    """[B, p, max_v+1] initial values for a batch of point queries.

    Source-rooted programs take `sources` (a [B] sequence of vertex ids),
    each validated BEFORE any init is built — one bad source fails fast
    with the offending id named instead of poisoning the whole batch.
    Source-free programs (CC/PR/reach: whole-graph queries) take `batch`
    (or infer it from len(sources)) and tile one init B times.
    """
    prog = get_program(prog)
    if prog.needs_source:
        if sources is None:
            raise ValueError(
                f"program {prog.name!r} is source-rooted: pass sources= (a [B] "
                "sequence of vertex ids)"
            )
        for s in sources:
            check_source(sub, s, num_vertices)
        return jnp.stack(
            [prog.init(sub, num_vertices=num_vertices, source=s) for s in sources]
        )
    if batch is None:
        batch = len(sources) if sources is not None else 0
    if batch < 1:
        raise ValueError(
            f"program {prog.name!r} is source-free: pass batch= (or sources= "
            "to size the batch)"
        )
    one = prog.init(sub, num_vertices=num_vertices)
    return jnp.tile(one[None], (int(batch), 1, 1))


def _assemble_batch_stats(steps_q, msgs_sbw, iters_sbw, edges, prog, inner_cap) -> list:
    """Per-query BSPStats from the batched [S, B, p] buffers: query b's
    series is truncated to the supersteps IT paid under masking."""
    edges = edges.astype(np.int64)
    return [
        _assemble_stats(
            int(steps_q[b]),
            msgs_sbw[: int(steps_q[b]), b].astype(np.int64),
            iters_sbw[: int(steps_q[b]), b].astype(np.int64),
            edges,
            prog,
            inner_cap,
        )
        for b in range(msgs_sbw.shape[1])
    ]


def _resolve_batch_args(sub, program, *, max_supersteps, num_vertices, compute_backend,
                        exchange_period=1):
    prog = get_program(program)
    check_int32_kernel_labels(prog, sub, compute_backend)
    check_pagerank_num_vertices(prog, num_vertices)
    if exchange_period != 1:
        raise ValueError(
            "the batched driver always exchanges every superstep; "
            f"exchange_period={exchange_period} is not supported — run staleness "
            "experiments through single-query run_bsp"
        )
    if max_supersteps is None:
        max_supersteps = prog.default_steps or 200
    return prog, max_supersteps


def run_bsp_batch(
    sub: SubgraphSet,
    program,
    sources=None,
    init_vals: Optional[jax.Array] = None,
    *,
    batch: Optional[int] = None,
    max_supersteps: Optional[int] = None,
    inner_cap: int = 10_000,
    exchange_period: int = 1,
    tol: float = 0.0,
    num_vertices: int = 0,
    compute_backend: str = "xla",
    block_e: int = 512,
) -> tuple[jax.Array, list]:
    """Batched multi-source BSP: B queries of one program in ONE fused
    dispatch over shared subgraph structure.

    Returns (values [B, p, max_v+1], per-query BSPStats list) — each query's
    values AND stats are bit-identical to a single-source `run_bsp` call
    (tests/test_serve.py pins this across programs × backends). Like the
    single-query fused driver, the initial value buffer is DONATED.
    """
    prog, max_supersteps = _resolve_batch_args(
        sub, program, max_supersteps=max_supersteps, num_vertices=num_vertices,
        compute_backend=compute_backend, exchange_period=exchange_period,
    )
    if init_vals is None:
        init_vals = batch_init(prog, sub, sources, batch=batch, num_vertices=num_vertices)
    exec_prog, negate = _exec_view(prog)
    vals = -init_vals if negate else init_vals
    # One codec across the batch: the union of every query's finite values
    # (source-free programs tile one init, so this matches the per-query
    # codec exactly; ranks stay < covered either way).
    vals, codec = _kernel_value_boundary(prog, sub, vals, compute_backend)
    vals, steps_q, msgs_buf, iters_buf, edges = _fused_bsp_batch(
        sub, vals, prog=exec_prog, max_supersteps=max_supersteps, inner_cap=inner_cap,
        tol=tol, num_vertices=num_vertices, backend=compute_backend, block_e=block_e,
    )
    obs.count("engine.dispatch.batch")
    steps_q, msgs_sbw, iters_sbw, edges = jax.device_get((steps_q, msgs_buf, iters_buf, edges))
    if codec is not None:
        vals = codec.decode(vals)
    return (-vals if negate else vals), _assemble_batch_stats(
        steps_q, msgs_sbw, iters_sbw, edges, exec_prog, inner_cap
    )


@dataclasses.dataclass
class BatchExecutable:
    """AOT-compiled batched BSP loop for one (program, padded batch size).

    The serving tier's executable-cache value: `compile_batch_executable`
    lowers `_fused_bsp_batch` once for a fixed [B, p, max_v+1] value shape,
    and `run` replays it with zero retracing — steady-state queries never
    recompile. Negation (max-combine programs) and per-query stats assembly
    live in the wrapper, outside the compiled program.
    """

    program: VertexProgram
    sub: SubgraphSet
    batch: int
    negate: bool
    compiled: object
    compile_s: float
    compute_backend: str = "xla"
    inner_cap: int = 10_000

    def run(self, init_vals: jax.Array) -> tuple[jax.Array, list]:
        """Same contract as `run_bsp_batch` (init_vals is donated)."""
        if init_vals.shape[0] != self.batch:
            raise ValueError(
                f"executable compiled for batch {self.batch}, got {init_vals.shape[0]} "
                "— pad the batch to its bucket first"
            )
        vals = -init_vals if self.negate else init_vals
        # Per-call value boundary: the compiled program is shape-keyed, not
        # value-keyed, so each batch brings its own codec (a host-side
        # unique + searchsorted — no retrace, the dtype stays int32).
        vals, codec = _kernel_value_boundary(
            self.program, self.sub, vals, self.compute_backend
        )
        vals, steps_q, msgs_buf, iters_buf, edges = self.compiled(self.sub, vals)
        obs.count("engine.dispatch.batch")
        steps_q, msgs_sbw, iters_sbw, edges = jax.device_get(
            (steps_q, msgs_buf, iters_buf, edges)
        )
        if codec is not None:
            vals = codec.decode(vals)
        return (
            -vals if self.negate else vals
        ), _assemble_batch_stats(
            steps_q, msgs_sbw, iters_sbw, edges, self.program, self.inner_cap
        )


def compile_batch_executable(
    sub: SubgraphSet,
    program,
    batch: int,
    *,
    max_supersteps: Optional[int] = None,
    inner_cap: int = 10_000,
    tol: float = 0.0,
    num_vertices: int = 0,
    compute_backend: str = "xla",
    block_e: int = 512,
) -> BatchExecutable:
    """AOT-lower + compile the batched fused BSP loop for a fixed padded
    batch size (the warm path behind `repro.serve`'s executable cache)."""
    prog, max_supersteps = _resolve_batch_args(
        sub, program, max_supersteps=max_supersteps, num_vertices=num_vertices,
        compute_backend=compute_backend,
    )
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    exec_prog, negate = _exec_view(prog)
    dt = jnp.int32 if prog.dtype == "int32" else jnp.float32
    spec = jax.ShapeDtypeStruct((int(batch), sub.num_parts, sub.max_v + 1), dt)
    t0 = time.perf_counter()
    compiled = _fused_bsp_batch.lower(
        sub, spec, prog=exec_prog, max_supersteps=max_supersteps, inner_cap=inner_cap,
        tol=tol, num_vertices=num_vertices, backend=compute_backend, block_e=block_e,
    ).compile()
    return BatchExecutable(
        program=prog, sub=sub, batch=int(batch), negate=negate, compiled=compiled,
        compile_s=time.perf_counter() - t0, compute_backend=compute_backend,
        inner_cap=inner_cap,
    )


# ------------------------------------------------- distributed (shard_map)


_ARRAY_FIELDS = [
    "lsrc", "ldst", "weight", "edge_mask",
    "lsrc_s", "ldst_s", "weight_s", "edge_mask_s",
    "gid", "vmask", "is_master", "out_degree",
    "send_idx", "recv_idx", "msg_mask", "recv_mask",
]
_STATIC_FIELDS = ["num_parts", "max_v", "max_e", "max_msg", "addressing"]


def subgraphs_to_arrays(sub: SubgraphSet) -> tuple[dict, dict]:
    arrays = {k: getattr(sub, k) for k in _ARRAY_FIELDS}
    statics = {k: getattr(sub, k) for k in _STATIC_FIELDS}
    return arrays, statics


def make_distributed_stepper(
    mesh,
    axes,
    prog,
    statics: dict,
    *,
    num_supersteps: int,
    inner_cap: int,
    tol: float = 0.0,
    num_vertices: int = 0,
    compute_backend: str = "xla",
    block_e: int = 512,
    fault_plan=None,
):
    """Builds a shard_map'd BSP runner for ANY `VertexProgram`: subgraphs
    sharded 1:1 over `axes`.

    `fault_plan=` (a `repro.resilience.FaultPlan` with
    `crash_at_superstep=s`) injects a deterministic worker crash: the
    step loop is capped at s supersteps and the runner raises
    `WorkerCrashError` if the loop was still running when the cap hit
    (a run that converges in fewer than s supersteps completes — there
    is no superstep s to die in).

    `axes` may be a single mesh axis name or a tuple (e.g. ("pod","data",
    "model")) whose sizes multiply to the number of subgraphs — this is what
    the multi-pod dry-run lowers: p=512 subgraphs over (pod, data, model).
    Takes the subgraph tensors as a dict (see `subgraphs_to_arrays`) so the
    sharding specs form a clean pytree.

    Like the fused sim driver, the step loop is a lax.while_loop that exits
    on GLOBAL convergence — for no-change programs a psum'd change flag, for
    tol programs the psum'd L1 step delta against `tol` — and records
    per-step message/inner-iteration stats in [num_supersteps, local] device
    buffers. Callers always work in the program's true value domain:
    max-combine programs are negated in and out here. Returns
    (val, msgs_total, steps, msgs_per_step, iters_per_step).
    """
    prog = get_program(prog)
    check_compute_backend(compute_backend)
    check_pagerank_num_vertices(prog, num_vertices)
    crash_at = None
    if fault_plan is not None and fault_plan.crash_at_superstep is not None:
        crash_at = int(fault_plan.crash_at_superstep)
        if crash_at < num_supersteps:
            num_supersteps = crash_at  # the doomed superstep never completes
    # Pallas interpret vs compiled is keyed off the MESH platform, not the
    # host process backend: AOT-lowering for a TPU mesh from a CPU host must
    # bake in the compiled kernel, not the interpreter.
    try:
        mesh_platform = mesh.devices.reshape(-1)[0].platform
    except AttributeError:  # abstract/mock meshes: fall back to the host sniff
        mesh_platform = None
    interpret = None if mesh_platform is None else mesh_platform != "tpu"
    exec_prog, negate = _exec_view(prog)
    # Same run-boundary hoist as the fused sim drivers: kernel backends run
    # int32 programs in f32, remapped once per run inside the shard_map'd
    # loop (per shard), not once per superstep.
    to_f32 = compute_backend != "xla" and prog.dtype == "int32"
    if to_f32:
        exec_prog = dataclasses.replace(exec_prog, dtype="float32")
    axis_tuple = axes if isinstance(axes, tuple) else (axes,)
    spec3 = P(axis_tuple, None, None)
    spec2 = P(axis_tuple, None)
    in_specs = ({k: (spec3 if k in ("send_idx", "recv_idx", "msg_mask", "recv_mask") else spec2) for k in _ARRAY_FIELDS}, spec2)

    def a2a_exchange(S):  # S: [1, p, m] per device
        out = jax.lax.all_to_all(S, axis_tuple, split_axis=1, concat_axis=0, tiled=False)
        # out: [p, 1, m] → receiver-rowed [1, p, m]
        return jnp.swapaxes(out, 0, 1)

    def stepper(arrays: dict, val: jax.Array):
        obs.count("engine.trace")  # runs only while JAX traces
        sub = SubgraphSet(**arrays, **statics)
        if to_f32:
            val = jnp.where(val == INF_I32, INF_F32, val.astype(jnp.float32))
        nloc = val.shape[0]  # subgraphs per device (1 on a fully sharded mesh)
        msgs_buf = jnp.zeros((num_supersteps, nloc), jnp.int32)
        iters_buf = jnp.zeros((num_supersteps, nloc), jnp.int32)
        segs = _local_segments(exec_prog, sub, compute_backend)  # once per run

        def cond(carry):
            _, k, done, _, _ = carry
            return ~done & (k < num_supersteps)

        def body(carry):
            v, k, _, msgs_buf, iters_buf = carry
            v2, m, it, delta = _superstep(
                exec_prog, sub, v, a2a_exchange, inner_cap,
                num_vertices=num_vertices, backend=compute_backend, interpret=interpret,
                block_e=block_e, segs=segs,
            )
            # Convergence is global: psum the per-device signal so every
            # device takes the same trip count (collectives stay uniform).
            if prog.convergence == "tol":
                gdelta = jax.lax.psum(delta, axis_tuple)
                done = (gdelta < tol) if tol else jnp.bool_(False)
            else:
                changed = jax.lax.psum(jnp.any(v2 != v).astype(jnp.int32), axis_tuple)
                done = changed == 0
            return v2, k + 1, done, msgs_buf.at[k].set(m), iters_buf.at[k].set(it)

        val_out, steps, _, msgs_buf, iters_buf = jax.lax.while_loop(
            cond, body, (val, jnp.int32(0), jnp.bool_(False), msgs_buf, iters_buf)
        )
        if to_f32:
            val_out = jnp.where(val_out >= INF_F32, INF_I32, val_out.astype(jnp.int32))
        return val_out, msgs_buf.sum(axis=0), steps, msgs_buf, iters_buf

    sharded = jax.shard_map(
        stepper,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(spec2, P(axis_tuple), P(), P(None, axis_tuple), P(None, axis_tuple)),
        check_vma=False,
    )

    addressing = statics.get("addressing", "two_level")

    def runner(arrays: dict, val: jax.Array):
        # Same 2^24 exactness guard as run_bsp/_resolve_batch_args: an
        # inexact run must raise BEFORE any int->f32 remap. Flat addressing
        # bounds values by the global-id space; two-level checks the
        # per-worker VALUE maxima of the incoming carry (label-domain
        # callers encode to ranks first — GraphPipeline._run_distributed
        # does — so big global labels pass as small ranks, and a raw
        # unencoded 2^24+ label still raises). Under jit/AOT tracing the
        # arrays are abstract and the guard cannot run here — those paths
        # pre-check the concrete SubgraphSet before tracing.
        try:
            if addressing == "flat":
                check_int32_kernel_gid(prog, arrays["gid"], compute_backend)
            elif compute_backend != "xla" and prog.dtype == "int32":
                mag = jnp.abs(val)
                finite = mag != INF_I32
                bound = int(jnp.max(jnp.where(finite, mag, 0)))
                if prog.weight == "unit":
                    bound += int(jnp.sum(arrays["is_master"]))
                check_int32_kernel_values(prog, bound, compute_backend)
        except jax.errors.JAXTypeError:
            pass
        out, msgs, steps, msgs_b, iters_b = sharded(arrays, -val if negate else val)
        if negate:
            out = -out
        if crash_at is not None and int(steps) >= crash_at:
            # The loop was still running when the doomed superstep came due.
            from repro.resilience.faults import WorkerCrashError

            raise WorkerCrashError(superstep=crash_at)
        return out, msgs, steps, msgs_b, iters_b

    return runner
