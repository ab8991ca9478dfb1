"""Streaming vertex-cut partitioner core: a pluggable `EdgeScorer` over
ONE `lax.scan` driver and ONE chunked block-commit driver.

The paper's EBV algorithm (our `ebg`) is one member of a family of
streaming greedy edge partitioners — HDRF [Petroni et al., CIKM'15] and
PowerGraph Greedy [Gonzalez et al., OSDI'12] are the baselines its
headline table compares against — that share one sequential state machine
and differ ONLY in the per-edge score they minimize. The shared machine:

    state: keep[i] ⊆ V  (endpoint membership per subgraph, a p×V bitset)
           e_count[i], v_count[i]  (running balance counters)
    per edge (u, v):
        i* = argmin_i score(u, v, i, state)   (ties -> lowest subgraph id)
        e_count[i*] += 1; v_count[i*] += #endpoints new to keep[i*]
        keep[i*] |= {u, v}

`EdgeScorer` is the frozen description of the score:

    score(u,v,i) = wu·1[u∉keep[i]] + wv·1[v∉keep[i]]          (replication)
                 + ce · e_count[i] · norm_e                   (edge balance)
                 + cv · v_count[i] · (p/|V|)                  (vertex balance)

where (wu, wv) are per-edge degree weights (1 unless the scorer has a
degree term), and norm_e is either the static p/|E| (EBV) or the dynamic
HDRF range normalizer 1/(eps + max(e_count) − min(e_count)). Stock
instances:

| scorer   | wu, wv            | norm_e            | ce, cv        |
|----------|-------------------|-------------------|---------------|
| `ebv`    | 1, 1              | p/|E| (static)    | alpha, beta   |
| `hdrf`   | 2−θ(u), 2−θ(v)    | 1/(eps+max−min)   | lambda, 0     |
| `greedy` | 1, 1              | 1/(eps+max−min)   | 1, 0          |

θ(u) = d(u)/(d(u)+d(v)) is HDRF's normalized degree; we use exact total
degrees (the offline variant — the graph is in memory), so the weights
are a precomputed per-edge stream and the state machine stays identical
across scorers. HDRF's published argmax of g(u,i)+g(v,i)+bal(i) with
g(u,i) = (2−θ(u))·1[u∈A(i)] is equivalent, term by constant term, to the
argmin above; Greedy is HDRF with the degree term dropped.

Both drivers are scorer-generic: the faithful `lax.scan` (one edge per
step) and the blocked commit loop (scores for B edges evaluated against
block-start membership, balance committed exactly and sequentially inside
the block — block=1 is exactly the faithful algorithm). The chunked
driver's "ref"/"pallas" backends route whole blocks through the fused
`repro.kernels.ops.ebg_commit_block` kernel, which takes the scorer's
coefficient vector and weight streams. `repro.core.streaming_np` runs the
same machine in pure numpy (the test oracle, bit-identical).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.config import EBGConfig, GreedyConfig, HDRFConfig, check_compute_backend
from repro.api.registry import register_partitioner
from repro.core.order import degree_sum_order
from repro.core.types import Graph, PartitionResult
from repro.kernels import ops

MEMBERSHIP_TERMS = ("miss",)  # penalize endpoints absent from keep[i]
DEGREE_TERMS = ("none", "hdrf_theta")  # per-edge miss weights: 1 | 2−θ
BALANCE_MODES = ("static", "range")  # norm_e: p/|E| | 1/(eps+max−min)
TIE_POLICIES = ("lowest",)  # argmin ties -> lowest subgraph id
UPDATE_RULES = ("standard",)  # commit counters + endpoint membership


def _check(value, valid, field: str) -> None:
    if value not in valid:
        raise ValueError(f"EdgeScorer.{field} must be one of {valid}, got {value!r}")


@dataclasses.dataclass(frozen=True)
class EdgeScorer:
    """Frozen description of a streaming greedy edge-partitioner score.

    Default coefficients (`ce`/`cv`/`eps`) are overridable per call —
    e.g. `ebg`'s alpha/beta knobs are the EBV scorer's ce/cv.
    """

    name: str
    membership: str = "miss"  # replication term (see MEMBERSHIP_TERMS)
    degree_term: str = "none"  # per-edge miss weighting (DEGREE_TERMS)
    balance: str = "static"  # edge-balance normalizer (BALANCE_MODES)
    ce: float = 1.0  # edge-balance coefficient (EBV alpha, HDRF lambda)
    cv: float = 0.0  # vertex-balance coefficient (EBV beta)
    eps: float = 1.0  # range-normalizer epsilon
    tie: str = "lowest"  # argmin tie policy (TIE_POLICIES)
    update: str = "standard"  # state-update rule (UPDATE_RULES)
    sort_edges: bool = True  # default §IV-C degree-sum edge ordering
    description: str = ""

    def __post_init__(self) -> None:
        _check(self.membership, MEMBERSHIP_TERMS, "membership")
        _check(self.degree_term, DEGREE_TERMS, "degree_term")
        _check(self.balance, BALANCE_MODES, "balance")
        _check(self.tie, TIE_POLICIES, "tie")
        _check(self.update, UPDATE_RULES, "update")
        for field in ("ce", "cv", "eps"):
            v = getattr(self, field)
            if not isinstance(v, (int, float)) or not np.isfinite(v) or v < 0:
                raise ValueError(f"EdgeScorer.{field} must be finite and >= 0, got {v!r}")

    @property
    def weighted(self) -> bool:
        """Whether the replication term carries per-edge degree weights."""
        return self.degree_term != "none"

    def coefficients(self, ce=None, cv=None, eps=None) -> tuple[float, float, float]:
        """Resolve per-call coefficient overrides against the defaults."""
        return (
            float(self.ce if ce is None else ce),
            float(self.cv if cv is None else cv),
            float(self.eps if eps is None else eps),
        )


_SCORERS: dict[str, EdgeScorer] = {}


def register_scorer(scorer: EdgeScorer) -> EdgeScorer:
    """Register a scorer instance; returns it unchanged (decorator-style)."""
    if scorer.name in _SCORERS:
        raise ValueError(f"scorer {scorer.name!r} already registered")
    _SCORERS[scorer.name] = scorer
    return scorer


def get_scorer(scorer: Union[str, EdgeScorer]) -> EdgeScorer:
    if isinstance(scorer, EdgeScorer):
        return scorer
    try:
        return _SCORERS[scorer]
    except KeyError:
        raise KeyError(f"unknown scorer {scorer!r}; registered: {sorted(_SCORERS)}") from None


def scorer_names() -> tuple[str, ...]:
    return tuple(_SCORERS)


def list_scorers() -> tuple[EdgeScorer, ...]:
    return tuple(_SCORERS.values())


EBV = register_scorer(EdgeScorer(
    name="ebv",
    ce=1.0,
    cv=1.0,
    description="Paper Algorithm 1: unit membership + static p/|E|, p/|V| balance",
))
HDRF = register_scorer(EdgeScorer(
    name="hdrf",
    degree_term="hdrf_theta",
    balance="range",
    ce=1.0,
    cv=0.0,
    sort_edges=False,
    description="HDRF [Petroni'15]: 2−θ degree-weighted membership + lambda range balance",
))
GREEDY = register_scorer(EdgeScorer(
    name="greedy",
    balance="range",
    ce=1.0,
    cv=0.0,
    sort_edges=False,
    description="PowerGraph Greedy [Gonzalez'12]: A(u)∩A(v) membership + range balance",
))


def validate_edge_stream(
    src: np.ndarray,
    dst: np.ndarray,
    *,
    num_vertices: int,
    weights: Optional[np.ndarray] = None,
) -> None:
    """Validate an edge stream at partitioner intake, following the
    `Graph.validate` convention: raise ValueError naming the offending
    FIELD and the first offending ROW (stream position, pre-reorder).

    Checks: matching 1-D shapes, vertex ids in [0, num_vertices),
    no self-loops (a self-loop contributes a spurious replication miss
    to every score and the generators strip them — one arriving here is
    corrupt input, not data), and finite non-negative per-edge weights.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.ndim != 1 or src.shape != dst.shape:
        raise ValueError(
            f"src/dst must be 1-D and the same shape; got src {src.shape}, dst {dst.shape}"
        )
    for name, arr in (("src", src), ("dst", dst)):
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(f"{name} must be an integer array, got dtype {arr.dtype}")
        bad = np.flatnonzero((arr < 0) | (arr >= num_vertices))
        if bad.size:
            row = int(bad[0])
            raise ValueError(
                f"{name}[{row}] = {int(arr[row])} out of range "
                f"[0, num_vertices={num_vertices})"
            )
    loops = np.flatnonzero(src == dst)
    if loops.size:
        row = int(loops[0])
        raise ValueError(
            f"self-loop at edge row {row}: src[{row}] == dst[{row}] == {int(src[row])} "
            "(streaming partitioners require loop-free streams; strip self-loops first)"
        )
    if weights is not None:
        w = np.asarray(weights)
        if w.shape != src.shape:
            raise ValueError(
                f"weights must match the edge stream shape {src.shape}, got {w.shape}"
            )
        bad = np.flatnonzero(~np.isfinite(w.astype(np.float64)) | (w.astype(np.float64) < 0))
        if bad.size:
            row = int(bad[0])
            raise ValueError(
                f"weights[{row}] = {float(w[row])!r} must be finite and >= 0"
            )


def edge_weights_np(
    scorer: EdgeScorer, graph: Graph, src: np.ndarray, dst: np.ndarray
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """Per-edge replication-term weights (wu, wv) as f32 numpy, or None.

    Computed host-side from exact total degrees, so the JAX drivers and the
    numpy oracle consume the SAME arrays — degree weighting can never be a
    parity hazard. `src`/`dst` are the (possibly reordered) edge streams.
    """
    if not scorer.weighted:
        return None
    deg = graph.degrees().astype(np.float32)
    du, dv = deg[src], deg[dst]
    tot = du + dv
    wu = np.float32(2.0) - du / tot
    wv = np.float32(2.0) - dv / tot
    return wu, wv


# ------------------------------------------------------------- scan driver


@functools.partial(
    jax.jit, static_argnames=("num_parts", "num_vertices", "weighted", "balance")
)
def _streaming_scan(
    src, dst, wu, wv, *, num_parts: int, num_vertices: int,
    weighted: bool, balance: str, ce: float, cv: float, eps: float,
):
    E = src.shape[0]
    p = num_parts
    inv_e = p / jnp.float32(E)  # 1/(|E|/p)
    inv_v = p / jnp.float32(num_vertices)

    keep0 = jnp.zeros((p, num_vertices), dtype=jnp.bool_)
    e0 = jnp.zeros((p,), dtype=jnp.float32)
    v0 = jnp.zeros((p,), dtype=jnp.float32)

    def step(state, x):
        keep, e_count, v_count = state
        if weighted:
            u, v, w_u, w_v = x
        else:
            u, v = x
        mu = (~keep[:, u]).astype(jnp.float32)
        mv = (~keep[:, v]).astype(jnp.float32)
        base = w_u * mu + w_v * mv if weighted else mu + mv
        if balance == "static":
            norm = inv_e
        else:
            norm = 1.0 / (eps + (jnp.max(e_count) - jnp.min(e_count)))
        score = base + ce * e_count * norm + cv * v_count * inv_v
        i = jnp.argmin(score).astype(jnp.int32)
        e_count = e_count.at[i].add(1.0)
        v_count = v_count.at[i].add(mu[i] + mv[i])
        keep = keep.at[i, u].set(True).at[i, v].set(True)
        return (keep, e_count, v_count), i

    xs = (src, dst, wu, wv) if weighted else (src, dst)
    (keep, e_count, v_count), part = jax.lax.scan(step, (keep0, e0, v0), xs)
    return part, keep, e_count, v_count


def streaming_scan_partition(
    graph: Graph,
    num_parts: int,
    scorer: Union[str, EdgeScorer],
    *,
    ce: Optional[float] = None,
    cv: Optional[float] = None,
    eps: Optional[float] = None,
    order: Optional[np.ndarray] = None,
    sort_edges: Optional[bool] = None,
) -> PartitionResult:
    """Faithful sequential stream (one `lax.scan` step per edge) for any
    registered scorer. `ebg` ≡ scorer="ebv" with ce=alpha, cv=beta."""
    sc = get_scorer(scorer)
    ce, cv, eps = sc.coefficients(ce, cv, eps)
    if sort_edges is None:
        sort_edges = sc.sort_edges
    src = np.asarray(graph.src, dtype=np.int32)
    dst = np.asarray(graph.dst, dtype=np.int32)
    # Validate BEFORE the degree-sum reorder (which itself assumes in-range
    # ids) so offending rows are named in the caller's input order.
    validate_edge_stream(src, dst, num_vertices=graph.num_vertices)
    if order is None and sort_edges:
        order = degree_sum_order(graph)
    if order is not None:
        src, dst = src[order], dst[order]
    w = edge_weights_np(sc, graph, src, dst)
    zero = jnp.zeros((0,), jnp.float32)
    part, _, _, _ = _streaming_scan(
        jnp.asarray(src),
        jnp.asarray(dst),
        zero if w is None else jnp.asarray(w[0]),
        zero if w is None else jnp.asarray(w[1]),
        num_parts=num_parts,
        num_vertices=graph.num_vertices,
        weighted=sc.weighted,
        balance=sc.balance,
        ce=ce,
        cv=cv,
        eps=eps,
    )
    return PartitionResult(
        part=part, num_parts=num_parts, order=None if order is None else np.asarray(order)
    )


# ---------------------------------------------------------- chunked driver


def _score_commit_loop(
    e_count, v_count, mu0, mv0, valb, wub, wvb, *,
    num_parts: int, weighted: bool, balance: str, window: bool,
    ce: float, cv: float, eps: float, inv_e, inv_v,
    ub=None, vb=None,
):
    """The sequential exact in-block commit shared by every dense-membership
    block driver (the in-memory chunked scan, the out-of-core per-block
    step, and the shard_map'd sharded-state step — bit-parity between them
    is by construction, not by test alone). Scores the block's edges
    against the block-start miss tables (mu0/mv0: [p, B]), commits balance
    counters exactly and sequentially, and returns
    (e_count, v_count, parts). Pad edges (valid=False) are scored but
    never committed and route to the out-of-bounds row `num_parts`.
    `window=True` replays each commit's membership consequences onto later
    conflicted columns (needs ub/vb) — assignments bit-identical to the
    one-edge-at-a-time scan driver."""
    p = num_parts
    B = valb.shape[0]

    def body(j, carry):
        e_c, v_c, mu, mv, parts = carry
        if balance == "static":
            norm = inv_e
        else:
            norm = 1.0 / (eps + (jnp.max(e_c) - jnp.min(e_c)))
        gain = wub[j] * mu[:, j] + wvb[j] * mv[:, j] if weighted else mu[:, j] + mv[:, j]
        score = gain + ce * e_c * norm + cv * v_c * inv_v
        i = jnp.argmin(score).astype(jnp.int32)
        live = valb[j].astype(jnp.float32)
        e_c = e_c.at[i].add(live)
        v_c = v_c.at[i].add(live * (mu[i, j] + mv[i, j]))
        if window:
            # Speculative window commit: the block was scored in one
            # shot from block-start state; replay this commit onto the
            # remaining columns (clear the winner's miss rows where a
            # later edge touches the committed endpoints) so only
            # CONFLICTED edges see corrected scores — bit-identical
            # to the one-edge-at-a-time scan driver.
            hit_u = (ub == ub[j]) | (ub == vb[j])
            hit_v = (vb == ub[j]) | (vb == vb[j])
            mu = mu.at[i].set(jnp.where(hit_u & valb[j], 0.0, mu[i]))
            mv = mv.at[i].set(jnp.where(hit_v & valb[j], 0.0, mv[i]))
        return e_c, v_c, mu, mv, parts.at[j].set(jnp.where(valb[j], i, p))

    e_count, v_count, _, _, parts = jax.lax.fori_loop(
        0, B, body, (e_count, v_count, mu0, mv0, jnp.zeros((B,), jnp.int32))
    )
    return e_count, v_count, parts


@functools.partial(
    jax.jit,
    static_argnames=("num_parts", "num_vertices", "block", "backend", "weighted", "balance",
                     "window"),
)
def _streaming_chunked(
    src, dst, valid, wu, wv, num_real_edges, *, num_parts: int, num_vertices: int,
    block: int, backend: str, weighted: bool, balance: str,
    ce: float, cv: float, eps: float, window: bool = False,
):
    E = src.shape[0]
    p = num_parts
    assert E % block == 0
    # Balance terms are normalized by the REAL edge count — pad edges must
    # not dilute the ce term. Traced (not static) so graphs sharing a
    # padded shape share one compiled executable.
    inv_e = p / num_real_edges.astype(jnp.float32)
    inv_v = p / jnp.float32(num_vertices)

    e0 = jnp.zeros((p,), dtype=jnp.float32)
    v0 = jnp.zeros((p,), dtype=jnp.float32)
    blocks = [src.reshape(-1, block), dst.reshape(-1, block), valid.reshape(-1, block)]
    if weighted:
        blocks += [wu.reshape(-1, block), wv.reshape(-1, block)]

    if backend != "xla":
        # Packed uint32 bitset membership (32x smaller than the dense bool
        # table: p=32, V=1M -> 4 MB, VMEM-resident for the Pallas kernel).
        # The whole block sequence — membership score, argmin, exact
        # balance commit, bitset update — runs inside one fused
        # ops.ebg_commit_block call (ref oracle or Pallas kernel),
        # parameterized by the scorer's coefficient vector and weight
        # streams; assignments stay identical to the dense path because
        # membership is pinned to block-start state and the commit
        # arithmetic is term-for-term the same.
        with jax.named_scope("ebg.commit"):
            keep, e_count, v_count, part = ops.ebg_commit_block(
                jnp.zeros((p, (num_vertices + 31) // 32), dtype=jnp.uint32), e0, v0,
                *blocks[:3], alpha=ce, beta=cv, inv_e=inv_e, inv_v=inv_v, eps=eps,
                balance=balance, wu=blocks[3] if weighted else None,
                wv=blocks[4] if weighted else None, impl=backend, window=window,
            )
        return part.reshape(-1), keep, e_count, v_count

    # Dense (p, V) bool membership table, batched gathers for the score
    # phase.
    def step(state, uv_block):
        keep, e_count, v_count = state
        if weighted:
            ub, vb, valb, wub, wvb = uv_block  # [B]
        else:
            ub, vb, valb = uv_block
        # Vectorized membership lookups against block-start keep: (p, B),
        # then the shared sequential exact in-block commit
        # (`_score_commit_loop`). Pad edges are scored (uniform work
        # per lane) but never committed: they leave e_count/v_count
        # untouched and route to row `p`.
        mu0 = (~keep[:, ub]).astype(jnp.float32)
        mv0 = (~keep[:, vb]).astype(jnp.float32)
        e_count, v_count, parts = _score_commit_loop(
            e_count, v_count, mu0, mv0, valb,
            wub if weighted else None, wvb if weighted else None,
            num_parts=p, weighted=weighted, balance=balance, window=window,
            ce=ce, cv=cv, eps=eps, inv_e=inv_e, inv_v=inv_v, ub=ub, vb=vb,
        )
        # Batched keep update after the block commits; pad edges carry the
        # out-of-bounds row `p` and are dropped by the scatter.
        keep = keep.at[parts, ub].set(True, mode="drop")
        keep = keep.at[parts, vb].set(True, mode="drop")
        return (keep, e_count, v_count), parts

    keep0 = jnp.zeros((p, num_vertices), dtype=jnp.bool_)
    with jax.named_scope("ebg.commit"):
        (keep, e_count, v_count), part = jax.lax.scan(step, (keep0, e0, v0), tuple(blocks))
    return part.reshape(-1), keep, e_count, v_count


def streaming_chunked_partition(
    graph: Graph,
    num_parts: int,
    scorer: Union[str, EdgeScorer],
    *,
    ce: Optional[float] = None,
    cv: Optional[float] = None,
    eps: Optional[float] = None,
    block: int = 256,
    sort_edges: Optional[bool] = None,
    compute_backend: str = "xla",
    commit: str = "frozen",
) -> PartitionResult:
    """Blocked throughput variant of the stream (block=1 ≡ faithful) for
    any registered scorer.

    compute_backend "xla" scores against the dense bool membership table;
    "ref"/"pallas" run each block through the fused packed-bitset
    `repro.kernels.ops.ebg_commit_block` — assignments are identical.

    commit="frozen" (default) scores every edge in a block against the
    block-start membership (the chunked quality/throughput trade);
    commit="window" is the speculative window commit: the block is still
    scored in one vectorized shot, but each commit replays its membership
    consequences onto the remaining in-block columns, so only conflicted
    edges are rescored and the assignments are BIT-IDENTICAL to the scan
    driver at every block size (tests/test_megakernel.py pins this for
    all registered scorers).
    """
    check_compute_backend(compute_backend)
    if commit not in ("frozen", "window"):
        raise ValueError(f"commit must be 'frozen' or 'window', got {commit!r}")
    sc = get_scorer(scorer)
    ce, cv, eps = sc.coefficients(ce, cv, eps)
    if sort_edges is None:
        sort_edges = sc.sort_edges
    # Host phases under `partition.*` spans (`repro.obs`): validate, the
    # paper's degree-sum order (§IV-C), upload, then the commit launch.
    with obs.span("partition.validate"):
        src = np.asarray(graph.src, dtype=np.int32)
        dst = np.asarray(graph.dst, dtype=np.int32)
        # Validate BEFORE reorder and BEFORE the masked self-loop padding below
        # (pad rows are synthetic and exempt); rows are named in input order.
        validate_edge_stream(src, dst, num_vertices=graph.num_vertices)
    with obs.span("partition.order"):
        order = degree_sum_order(graph) if sort_edges else None
        if order is not None:
            src, dst = src[order], dst[order]
    with obs.span("partition.upload"):
        w = edge_weights_np(sc, graph, src, dst)
        E = src.shape[0]
        pad = (-E) % block
        valid = np.ones((E + pad,), bool)
        if pad:
            # Pad with self-loops on vertex 0, masked out of the commit loop
            # (and dropped from the result). Pad weights are never committed;
            # 1.0 keeps the scored lanes finite.
            src = np.concatenate([src, np.zeros((pad,), np.int32)])
            dst = np.concatenate([dst, np.zeros((pad,), np.int32)])
            valid[E:] = False
            if w is not None:
                one = np.ones((pad,), np.float32)
                w = (np.concatenate([w[0], one]), np.concatenate([w[1], one]))
        zero = jnp.zeros((0,), jnp.float32)
        streams = (
            jnp.asarray(src),
            jnp.asarray(dst),
            jnp.asarray(valid),
            zero if w is None else jnp.asarray(w[0]),
            zero if w is None else jnp.asarray(w[1]),
            jnp.float32(E),
        )
    with obs.span("partition.commit"):  # returns once the device work is launched
        part, _, _, _ = _streaming_chunked(
            *streams,
            num_parts=num_parts,
            num_vertices=graph.num_vertices,
            block=block,
            backend=compute_backend,
            weighted=sc.weighted,
            balance=sc.balance,
            ce=ce,
            cv=cv,
            eps=eps,
            window=commit == "window",
        )
    part = part[:E]
    return PartitionResult(part=part, num_parts=num_parts, order=order)


# ----------------------------------------------- stock scorer partitioners


@register_partitioner(
    "ebg",
    config=EBGConfig,
    deterministic=True,
    jit_compatible=True,
    scorer="ebv",
    description="Faithful EBG scan (paper Algorithm 1 + degree-sum order)",
)
def ebg_partition(
    graph: Graph,
    num_parts: int,
    *,
    alpha: float = 1.0,
    beta: float = 1.0,
    order: Optional[np.ndarray] = None,
    sort_edges: bool = True,
) -> PartitionResult:
    """Faithful EBG (Algorithm 1 + §IV-C degree-sum ordering)."""
    return streaming_scan_partition(
        graph, num_parts, EBV, ce=alpha, cv=beta, order=order, sort_edges=sort_edges
    )


@register_partitioner(
    "ebg_chunked",
    config=EBGConfig,
    deterministic=True,
    chunked=True,
    jit_compatible=True,
    benchmark_default=False,
    compute_backends=("xla", "ref", "pallas"),
    scorer="ebv",
    description="Blocked EBG throughput variant (block=1 ≡ faithful)",
)
def ebg_partition_chunked(
    graph: Graph,
    num_parts: int,
    *,
    alpha: float = 1.0,
    beta: float = 1.0,
    block: int = 256,
    sort_edges: bool = True,
    compute_backend: str = "xla",
    commit: str = "frozen",
) -> PartitionResult:
    """Blocked EBG (beyond-paper throughput variant; block=1 ≡ faithful,
    commit="window" ≡ faithful at ANY block size)."""
    return streaming_chunked_partition(
        graph, num_parts, EBV, ce=alpha, cv=beta, block=block,
        sort_edges=sort_edges, compute_backend=compute_backend, commit=commit,
    )


@register_partitioner(
    "hdrf",
    config=HDRFConfig,
    deterministic=True,
    chunked=True,
    jit_compatible=True,
    compute_backends=("xla", "ref", "pallas"),
    scorer="hdrf",
    description="HDRF [Petroni'15] on the streaming scorer core (block=1 ≡ faithful)",
)
def hdrf_partition(
    graph: Graph,
    num_parts: int,
    *,
    lam: float = 1.0,
    eps: float = 1.0,
    block: int = 256,
    sort_edges: bool = False,
    compute_backend: str = "xla",
    commit: str = "frozen",
) -> PartitionResult:
    """HDRF: highest-degree-replicated-first (paper baseline)."""
    return streaming_chunked_partition(
        graph, num_parts, HDRF, ce=lam, eps=eps, block=block,
        sort_edges=sort_edges, compute_backend=compute_backend, commit=commit,
    )


@register_partitioner(
    "greedy",
    config=GreedyConfig,
    deterministic=True,
    chunked=True,
    jit_compatible=True,
    compute_backends=("xla", "ref", "pallas"),
    scorer="greedy",
    description="PowerGraph Greedy [Gonzalez'12] on the streaming scorer core",
)
def greedy_partition(
    graph: Graph,
    num_parts: int,
    *,
    eps: float = 1.0,
    block: int = 256,
    sort_edges: bool = False,
    compute_backend: str = "xla",
    commit: str = "frozen",
) -> PartitionResult:
    """PowerGraph Greedy: A(u)∩A(v) heuristic (paper baseline)."""
    return streaming_chunked_partition(
        graph, num_parts, GREEDY, eps=eps, block=block,
        sort_edges=sort_edges, compute_backend=compute_backend, commit=commit,
    )
