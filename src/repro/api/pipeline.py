"""`GraphPipeline` — the end-to-end facade over the paper's stack.

    run = GraphPipeline(graph).partition("ebg", parts=8).build(symmetrize=True).run("cc")
    run.stats.total_messages, run.metrics.replication_factor, run.to_global()

Stages are lazy and cached on a shared partition-stage state, so fluent
views are cheap: `.partition(...)` starts a fresh stage; `.build(...)`
and repeated `.run(...)` calls on the same stage reuse the cached
`PartitionResult`, `PartitionMetrics`, and per-(symmetrize, pad) built
`SubgraphSet`s. If `.build` is never called, `.run` picks the build the
program needs (bidirectional programs symmetrize; the rest keep edge
direction).

`.run` executes ANY registered `VertexProgram` (or a custom instance with
an `init_fn`) in BOTH modes — `mode="sim"` batches all workers on one
device, `mode="dist"` shard_maps one subgraph per mesh device through the
same generic distributed stepper.

Distributed execution shares the same facade: `GraphPipeline.from_spec`
makes an abstract (shape-only) pipeline, and `.lower(mesh=...)` AOT-lowers
the shard_map'd BSP stepper for either an abstract spec or a concretely
built subgraph set — this is what the production dry-run drives.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.api.config import PartitionerConfig, check_compute_backend
from repro.api.registry import PartitionerSpec, check_num_parts, get_partitioner
from repro.core.metrics import PartitionMetrics, partition_metrics
from repro.core.types import Graph, PartitionResult
from repro.graph import algorithms as alg
from repro.graph.build import SubgraphSet, build_subgraphs
from repro.graph.engine import (
    BSPStats,
    VertexProgram,
    _assemble_stats,
    _kernel_value_boundary,
    check_driver,
    check_int32_kernel_labels,
    get_program,
    make_distributed_stepper,
    run_bsp_batch,
    subgraphs_to_arrays,
)

ProgramLike = Union[str, VertexProgram]


def _resolve_program(program: ProgramLike) -> VertexProgram:
    """Normalize a program handle to a runnable `VertexProgram`.

    Strings go through the engine registry; instances are accepted as long
    as they carry an `init_fn` (the facade needs initial values to run —
    register custom programs with `repro.graph.engine.register_program` or
    pass the instance directly)."""
    prog = get_program(program)
    if prog.init_fn is None:
        raise ValueError(
            f"program {prog.name!r} has no init_fn: GraphPipeline cannot build its "
            "initial values — set VertexProgram.init_fn, or drive it through "
            "repro.graph.engine.run_bsp with an explicit init_val"
        )
    return prog


def _translate_engine_kwargs(prog: VertexProgram, kw: dict) -> tuple[VertexProgram, dict]:
    """Facade-level conveniences: `num_iters` is the PageRank-speak alias of
    `max_supersteps`, and `damping` specializes the program instance."""
    if "num_iters" in kw:
        kw = dict(kw)
        kw["max_supersteps"] = kw.pop("num_iters")
    if "damping" in kw:
        kw = dict(kw)
        prog = dataclasses.replace(prog, damping=float(kw.pop("damping")))
    return prog, kw


def _normalize_axes(mesh, axes) -> tuple:
    if axes is None:
        return tuple(mesh.axis_names)
    return (axes,) if isinstance(axes, str) else tuple(axes)


# Default source (SSSP/BFS) depends only on the graph, not the partition —
# cache per graph object so a suite running 5 partitioners over one graph
# scans the edge list once. Keyed by id() with a liveness check (Graph holds
# jax arrays, so it is not hashable).
_SOURCE_CACHE: dict[int, tuple] = {}


def _default_source_for(graph: Graph) -> int:
    key = id(graph)
    ent = _SOURCE_CACHE.get(key)
    if ent is not None and ent[0]() is graph:
        return ent[1]
    cov = graph.covered_vertices()
    src_v = int(cov[np.argmax(graph.degrees()[cov])])
    _SOURCE_CACHE[key] = (weakref.ref(graph, lambda _: _SOURCE_CACHE.pop(key, None)), src_v)
    return src_v


# --------------------------------------------------------------- dry-run spec


@dataclasses.dataclass(frozen=True)
class SubgraphSpec:
    """Shape-only description of a padded SubgraphSet (for AOT lowering)."""

    num_parts: int
    max_v: int
    max_e: int
    max_msg: int = 2048
    addressing: str = "two_level"

    @classmethod
    def of(cls, sub: SubgraphSet) -> "SubgraphSpec":
        return cls(sub.num_parts, sub.max_v, sub.max_e, sub.max_msg, sub.addressing)

    def array_specs(self) -> tuple[dict, dict]:
        """ShapeDtypeStructs + statics matching `subgraphs_to_arrays`."""
        f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
        p = self.num_parts
        e2 = lambda dt: jax.ShapeDtypeStruct((p, self.max_e), dt)
        v2 = lambda dt: jax.ShapeDtypeStruct((p, self.max_v), dt)
        m3 = lambda dt: jax.ShapeDtypeStruct((p, p, self.max_msg), dt)
        arrays = dict(
            lsrc=e2(i32), ldst=e2(i32), weight=e2(f32), edge_mask=e2(b),
            lsrc_s=e2(i32), ldst_s=e2(i32), weight_s=e2(f32), edge_mask_s=e2(b),
            gid=v2(i32), vmask=v2(b), is_master=v2(b), out_degree=v2(f32),
            send_idx=m3(i32), recv_idx=m3(i32), msg_mask=m3(b), recv_mask=m3(b),
        )
        statics = dict(num_parts=p, max_v=self.max_v, max_e=self.max_e, max_msg=self.max_msg,
                       addressing=self.addressing)
        return arrays, statics

    def value_spec(self, prog: VertexProgram) -> jax.ShapeDtypeStruct:
        dt = jnp.int32 if prog.dtype == "int32" else jnp.float32
        return jax.ShapeDtypeStruct((self.num_parts, self.max_v + 1), dt)


@dataclasses.dataclass
class LoweredBSP:
    """AOT-lowered shard_map'd BSP stepper + its shardings."""

    spec: SubgraphSpec
    program: str
    mesh: object
    axes: tuple
    lowered: object
    compiled: object
    compile_s: float
    in_shardings: tuple


# ------------------------------------------------------------------ pipeline


class GraphPipeline:
    """Fluent partition → build → engine → metrics session (see module doc)."""

    def __init__(self, graph: Optional[Graph], *, weights: Optional[np.ndarray] = None):
        self.graph = graph
        self._weights = weights
        self._spec: Optional[SubgraphSpec] = None
        self._state: Optional[dict] = None  # partition-stage caches, shared by views
        self._build_params: Optional[dict] = None

    @classmethod
    def from_spec(cls, spec: SubgraphSpec) -> "GraphPipeline":
        """Abstract pipeline (shapes only) — supports `.lower` but not `.run`."""
        pipe = cls(None)
        pipe._spec = spec
        return pipe

    def _clone(self, *, state=None, build_params=None) -> "GraphPipeline":
        pipe = GraphPipeline(self.graph, weights=self._weights)
        pipe._spec = self._spec
        pipe._state = self._state if state is None else state
        pipe._build_params = self._build_params if build_params is None else build_params
        return pipe

    # ----------------------------------------------------------- partition

    def partition(
        self,
        partitioner: Union[str, PartitionerSpec] = "ebg",
        parts: int = 8,
        *,
        config: Optional[PartitionerConfig] = None,
        **overrides,
    ) -> "GraphPipeline":
        """Select a registered partitioner; returns a new pipeline view whose
        downstream stages are computed lazily and cached."""
        if self.graph is None:
            raise RuntimeError("abstract (from_spec) pipelines cannot partition a graph")
        spec = partitioner if isinstance(partitioner, PartitionerSpec) else get_partitioner(partitioner)
        check_num_parts(parts)  # fail fast here; spec.partition re-checks on the lazy path
        cfg = spec.make_config(config, **overrides)
        spec.check_overrides(overrides)
        state = dict(spec=spec, config=cfg, parts=parts, result=None, metrics=None, builds={})
        return self._clone(state=state, build_params={})

    def _stage(self) -> dict:
        if self._state is None:
            raise RuntimeError("no partition stage: call .partition(name, parts=...) first")
        return self._state

    @property
    def partitioner(self) -> PartitionerSpec:
        return self._stage()["spec"]

    @property
    def config(self) -> PartitionerConfig:
        return self._stage()["config"]

    @property
    def num_parts(self) -> int:
        return self._stage()["parts"]

    @property
    def result(self) -> PartitionResult:
        st = self._stage()
        if st["result"] is None:
            with obs.span("partition.run"):
                st["result"] = st["spec"].partition(self.graph, st["parts"], config=st["config"])
        return st["result"]

    @property
    def metrics(self) -> PartitionMetrics:
        st = self._stage()
        if st["metrics"] is None:
            st["metrics"] = partition_metrics(self.graph, self.result)
        return st["metrics"]

    # --------------------------------------------------------------- build

    def build(self, *, symmetrize: bool = False, pad_multiple: int = 8) -> "GraphPipeline":
        """Pin build parameters for subsequent `.run`/`.subgraphs` access."""
        self._stage()
        return self._clone(build_params=dict(symmetrize=symmetrize, pad_multiple=pad_multiple))

    def subgraphs_for(self, *, symmetrize: bool, pad_multiple: int = 8) -> SubgraphSet:
        st = self._stage()
        key = (bool(symmetrize), int(pad_multiple))
        if key not in st["builds"]:
            st["builds"][key] = build_subgraphs(
                self.graph,
                self.result,
                weights=self._weights,
                symmetrize=symmetrize,
                pad_multiple=pad_multiple,
            )
        return st["builds"][key]

    @property
    def subgraphs(self) -> SubgraphSet:
        bp = self._build_params or {}
        return self.subgraphs_for(
            symmetrize=bp.get("symmetrize", False), pad_multiple=bp.get("pad_multiple", 8)
        )

    # ----------------------------------------------------------------- run

    def default_source(self) -> int:
        """SSSP/BFS source: highest-degree covered vertex (benchmark convention)."""
        return _default_source_for(self.graph)

    def _build_params_for(self, prog: VertexProgram, symmetrize, pad_multiple) -> dict:
        # Explicit per-call arguments (not None) win over params pinned by
        # `.build`, which win over program defaults.
        bp = dict(self._build_params or {})
        if symmetrize is not None:
            bp["symmetrize"] = symmetrize
        if pad_multiple is not None:
            bp["pad_multiple"] = pad_multiple
        # Bidirectional programs (CC/REACH) treat the graph as undirected.
        bp.setdefault("symmetrize", bool(prog.bidirectional))
        bp.setdefault("pad_multiple", 8)
        return bp

    def _source_for(self, prog: VertexProgram, source) -> Optional[int]:
        if source is not None:
            return int(source)
        return self.default_source() if prog.needs_source else None

    def clear_builds(self) -> None:
        """Drop cached SubgraphSets (the partition result and metrics stay).
        Long-lived pipelines over several graphs can reclaim the padded
        build tensors once a benchmark section is done with them."""
        if self._state is not None:
            self._state["builds"].clear()

    def prepare(self, program: ProgramLike = "cc", *, symmetrize=None, pad_multiple: Optional[int] = None) -> "GraphPipeline":
        """Force partition + build (+ default source) caches, so a subsequent
        `.run` timing measures only the engine."""
        prog = _resolve_program(program)
        bp = self._build_params_for(prog, symmetrize, pad_multiple)
        self.subgraphs_for(**bp)
        if prog.needs_source:
            self.default_source()
        return self

    def run(
        self,
        program: ProgramLike = "cc",
        *,
        mode: str = "sim",
        symmetrize: Optional[bool] = None,
        pad_multiple: Optional[int] = None,
        source: Optional[int] = None,
        compute_backend: Optional[str] = None,
        driver: Optional[str] = None,
        **kw,
    ) -> "PipelineRun":
        """Execute any registered program over the partitioned graph and
        collect stats.

        mode="sim" batches all workers on one device (tests/benchmarks);
        mode="dist" shard_maps one subgraph per device (pass mesh=...) —
        BOTH modes run every program through the same generic engine.
        compute_backend routes the engine hot paths ("xla" | "ref" |
        "pallas"; default "xla"); driver selects the sim step loop
        ("fused" single-dispatch while_loop, the default, or "host" —
        one dispatch per superstep, kept for A/B). Extra kwargs flow to
        the engine (max_supersteps, inner_cap, exchange_period, tol,
        num_iters — the PageRank alias of max_supersteps — damping,
        block_e — the megakernel edge-block size for kernel backends,
        see docs/api.md "Performance guide" — ...),
        including the fault-tolerance knobs (checkpoint_every + ckpt_dir
        for superstep snapshots resumable via repro.resilience.resume_bsp,
        and fault_plan for deterministic fault injection — docs/api.md
        "Fault tolerance").
        """
        prog = _resolve_program(program)
        prog, kw = _translate_engine_kwargs(prog, kw)
        if compute_backend is not None:
            kw["compute_backend"] = check_compute_backend(compute_backend)
        if driver is not None:
            check_driver(driver)
            if mode != "sim":
                raise ValueError(
                    "driver= applies to mode='sim' only; mode='dist' always runs "
                    "the fused while_loop stepper"
                )
            kw["driver"] = driver
        if mode not in ("sim", "dist"):
            raise ValueError(f"unknown mode {mode!r}; expected 'sim' or 'dist'")
        sub = self.subgraphs_for(**self._build_params_for(prog, symmetrize, pad_multiple))
        src = self._source_for(prog, source)
        with obs.span("engine.run"):
            if mode == "sim":
                values, stats = alg.run_program(
                    sub, prog, num_vertices=self.graph.num_vertices, source=src, **kw
                )
            else:
                values, stats = self._run_distributed(prog, sub, source=src, **kw)
        return PipelineRun(pipeline=self, program=prog.name, values=values, stats=stats, subgraphs=sub)

    def run_batch(
        self,
        program: ProgramLike = "cc",
        sources=None,
        *,
        batch: Optional[int] = None,
        symmetrize: Optional[bool] = None,
        pad_multiple: Optional[int] = None,
        compute_backend: Optional[str] = None,
        **kw,
    ) -> "BatchRun":
        """Run a [B] batch of point queries of ONE program in a single
        fused dispatch over the shared subgraph structure.

        Source-rooted programs (SSSP/BFS) take `sources` — a [B] sequence
        of vertex ids, each validated before anything runs; source-free
        programs take `batch` (B identical whole-graph queries). Each
        query's values and `BSPStats` are bit-identical to a one-source
        `.run` call: convergence masking freezes finished queries while
        stragglers run, and per-query stats report the supersteps that
        query actually paid. For a persistent admission-queue/cache
        serving loop over the same machinery, use `.serve()`.
        """
        prog = _resolve_program(program)
        prog, kw = _translate_engine_kwargs(prog, kw)
        if compute_backend is not None:
            kw["compute_backend"] = check_compute_backend(compute_backend)
        sub = self.subgraphs_for(**self._build_params_for(prog, symmetrize, pad_multiple))
        vals, stats = run_bsp_batch(
            sub, prog, sources, batch=batch, num_vertices=self.graph.num_vertices, **kw
        )
        return BatchRun(
            pipeline=self,
            program=prog.name,
            values=np.asarray(vals[:, :, :-1]),
            stats=stats,
            subgraphs=sub,
            sources=tuple(int(s) for s in sources) if sources is not None else None,
        )

    def serve(self, **server_kwargs) -> "GraphQueryServer":
        """Open a persistent query-serving session over this pipeline's
        partitioned graph (admission queue, micro-batching, warm compiled
        executables — see `repro.serve.GraphQueryServer` for the knobs)."""
        from repro.serve import GraphQueryServer

        return GraphQueryServer(self, **server_kwargs)

    def _run_distributed(
        self,
        prog: VertexProgram,
        sub: SubgraphSet,
        *,
        mesh,
        axes=None,
        num_supersteps: Optional[int] = None,
        max_supersteps: Optional[int] = None,
        inner_cap: int = 10_000,
        tol: float = 0.0,
        source: Optional[int] = None,
        compute_backend: str = "xla",
        block_e: int = 512,
    ) -> tuple[np.ndarray, BSPStats]:
        check_int32_kernel_labels(prog, sub, compute_backend)
        if max_supersteps is not None:  # sim-speak (and the num_iters alias)
            num_supersteps = max_supersteps
        if num_supersteps is None:
            num_supersteps = prog.default_steps or 30
        axes = _normalize_axes(mesh, axes)
        ndev = int(np.prod([mesh.shape[a] for a in axes]))
        if ndev != sub.num_parts:
            raise ValueError(f"mesh axes {axes} span {ndev} devices but partition has {sub.num_parts} parts")
        arrays, statics = subgraphs_to_arrays(sub)
        with obs.span("engine.prepare"):
            stepper = make_distributed_stepper(
                mesh, axes, prog, statics, num_supersteps=num_supersteps, inner_cap=inner_cap,
                tol=tol, num_vertices=self.graph.num_vertices, compute_backend=compute_backend,
                block_e=block_e,
            )
            init = prog.init(sub, num_vertices=self.graph.num_vertices, source=source)
            # Two-level value boundary (host-side, before tracing): label-domain
            # programs run on dense ranks so kernels never see raw global ids.
            # Rank compression is order-preserving, so it commutes with the
            # runner's internal max→min negation; output decodes below.
            init, codec = _kernel_value_boundary(prog, sub, init, compute_backend)
        # A new jit of a new closure: every run traces and lowers again
        # (counted by `engine.trace`), then loads its executable from the cache.
        with obs.span("engine.dispatch"), mesh:
            val, _, steps, msgs_steps, iters_steps = jax.jit(stepper)(arrays, init)
        with obs.span("engine.fetch"):
            steps = int(steps)
            msgs_sw = np.asarray(msgs_steps, np.int64)[:steps]
            iters_sw = np.asarray(iters_steps, np.int64)[:steps]
            val = np.asarray(val)
        if codec is not None:
            val = codec.decode(val)  # gathered: the table is unsharded
        # Per-worker compute work from the returned inner-iteration buffer ×
        # per-worker edge counts — the same assembly the sim drivers use, so
        # sim and dist stats agree exactly.
        edges = np.asarray(sub.edge_mask.sum(axis=1), np.int64)
        stats = _assemble_stats(steps, msgs_sw, iters_sw, edges, prog, inner_cap)
        return np.asarray(val[:, :-1]), stats

    # --------------------------------------------------------------- lower

    def lower(
        self,
        *,
        mesh,
        axes=None,
        program: ProgramLike = "cc",
        num_supersteps: int = 4,
        inner_cap: int = 64,
        tol: float = 0.0,
        symmetrize: Optional[bool] = None,
        pad_multiple: Optional[int] = None,
        num_vertices: Optional[int] = None,
        compute_backend: str = "xla",
        block_e: int = 512,
    ) -> LoweredBSP:
        """AOT-lower the distributed BSP stepper (abstract or concrete) for
        ANY registered program.

        Kernel backends ("ref"/"pallas") run int32 programs (CC/BFS/REACH)
        through f32 — exact only for vertex ids below 2^24. Concrete
        pipelines are checked here; an abstract (from_spec) pipeline has no
        labels to check, so the CALLER must enforce the <2^24 precondition
        on the arrays eventually fed to the compiled stepper. Programs whose
        apply step renormalizes (PageRank) need `num_vertices=` when
        lowering from an abstract spec.
        """
        prog = get_program(program)
        check_compute_backend(compute_backend)
        axes = _normalize_axes(mesh, axes)
        nv = self.graph.num_vertices if self.graph is not None else int(num_vertices or 0)
        if prog.apply == "pagerank" and nv <= 0:
            raise ValueError(
                "lowering a pagerank-apply program from an abstract spec needs num_vertices="
            )
        if self._spec is not None:
            spec = self._spec
        else:
            sub = self.subgraphs_for(**self._build_params_for(prog, symmetrize, pad_multiple))
            check_int32_kernel_labels(prog, sub, compute_backend)
            spec = SubgraphSpec.of(sub)
        arrays, statics = spec.array_specs()
        stepper = make_distributed_stepper(
            mesh, axes, prog, statics, num_supersteps=num_supersteps, inner_cap=inner_cap,
            tol=tol, num_vertices=nv, compute_backend=compute_backend, block_e=block_e,
        )
        spec2 = P(axes, None)
        spec3 = P(axes, None, None)
        in_sh = (
            {k: NamedSharding(mesh, spec3 if v.ndim == 3 else spec2) for k, v in arrays.items()},
            NamedSharding(mesh, spec2),
        )
        with mesh:
            t0 = time.time()
            lowered = jax.jit(stepper, in_shardings=in_sh).lower(arrays, spec.value_spec(prog))
            compiled = lowered.compile()
            compile_s = time.time() - t0
        return LoweredBSP(
            spec=spec,
            program=prog.name,
            mesh=mesh,
            axes=axes,
            lowered=lowered,
            compiled=compiled,
            compile_s=compile_s,
            in_shardings=in_sh,
        )


@dataclasses.dataclass
class PipelineRun:
    """Result of one `GraphPipeline.run`: values + BSP stats + context."""

    pipeline: GraphPipeline
    program: str
    values: np.ndarray  # [p, max_v] per-(part, local-vertex) values
    stats: BSPStats
    subgraphs: SubgraphSet

    @property
    def metrics(self) -> PartitionMetrics:
        return self.pipeline.metrics

    @property
    def edges_per_worker(self) -> np.ndarray:
        return np.asarray(self.subgraphs.edge_mask.sum(axis=1))

    def to_global(self, reduce: str = "min") -> np.ndarray:
        """Per-vertex values collected from master replicas."""
        return alg.scatter_to_global(
            self.subgraphs, self.values, self.pipeline.graph.num_vertices, reduce=reduce
        )

    def num_components(self) -> int:
        """Distinct CC labels over covered vertices."""
        cov = self.pipeline.graph.covered_vertices()
        return int(np.unique(self.to_global()[cov]).shape[0])


@dataclasses.dataclass
class BatchRun:
    """Result of one `GraphPipeline.run_batch`: [B] queries of one program
    answered in one fused dispatch. `query(i)` views query i as a normal
    `PipelineRun` (same `.to_global()`, `.stats`, ... surface)."""

    pipeline: GraphPipeline
    program: str
    values: np.ndarray  # [B, p, max_v]
    stats: list  # [B] per-query BSPStats (each query's OWN supersteps)
    subgraphs: SubgraphSet
    sources: Optional[tuple]

    def __len__(self) -> int:
        return self.values.shape[0]

    def query(self, i: int) -> PipelineRun:
        return PipelineRun(
            pipeline=self.pipeline, program=self.program,
            values=self.values[i], stats=self.stats[i], subgraphs=self.subgraphs,
        )

    @property
    def supersteps_per_query(self) -> np.ndarray:
        """Supersteps each query actually paid under convergence masking
        (NOT B copies of the batch max)."""
        return np.asarray([s.supersteps for s in self.stats])
