"""Dry-run of the paper's subgraph-centric BSP engine at production scale.

Lowers the shard_map'd CC stepper for p=512 subgraphs (one per chip across
2 pods, subgraphs sharded over the flattened (pod, data, model) axes) with
Friendster-scale padded sizes: |E|≈3.6B directed edges → ~8M edges per
subgraph, ~1M local vertices, 2048-slot pairwise message buffers. The EBG
balance guarantees (Theorems 1/2) are what make these fixed paddings safe.

The lowering itself goes through the `repro.api` facade: an abstract
`GraphPipeline.from_spec(SubgraphSpec(...)).lower(mesh=...)` — the same
entry a concretely partitioned pipeline uses for distributed execution.
"""
from __future__ import annotations

from repro.api import GraphPipeline, SubgraphSpec
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import parse_collectives, roofline_terms

# Friendster |V| — abstract (shape-only) lowering has no graph to read it
# from, and renormalizing programs (PageRank) need it at trace time.
FRIENDSTER_NUM_VERTICES = 65_608_366


def friendster_spec(p: int, max_v: int = 1 << 20, max_e: int = 8 << 20, max_msg: int = 2048) -> SubgraphSpec:
    return SubgraphSpec(num_parts=p, max_v=max_v, max_e=max_e, max_msg=max_msg)


def run_graph_dryrun(
    *,
    multi_pod: bool = False,
    num_supersteps: int = 4,
    inner_cap: int = 64,
    compute_backend: str = "xla",
    program: str = "cc",
    partitioner: str = "ebg_chunked",
):
    """Lower the distributed stepper for any registered `VertexProgram`
    (`program="cc" | "sssp" | "pr" | "bfs" | "reach"`) at production scale.

    `partitioner` names the registered streaming partitioner whose balance
    behaviour the fixed paddings assume (any EdgeScorer instance: EBV
    guarantees them via Theorems 1/2; `hdrf`/`greedy` bound edge balance
    through their range term). The lowering itself is shape-only — the
    name is validated against the registry and recorded in the result.
    """
    from repro.api import get_partitioner

    spec_p = get_partitioner(partitioner)
    if spec_p.scorer is None:
        raise ValueError(
            f"partitioner {partitioner!r} is not a streaming EdgeScorer instance; "
            "the dry-run paddings assume a balance-bounded streaming partitioner"
        )
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)  # subgraphs over ALL axes: p == #chips
    p = len(mesh.devices.reshape(-1))
    low = GraphPipeline.from_spec(friendster_spec(p)).lower(
        mesh=mesh, axes=axes, program=program, num_supersteps=num_supersteps,
        inner_cap=inner_cap, num_vertices=FRIENDSTER_NUM_VERTICES,
        compute_backend=compute_backend,
    )
    mem = low.compiled.memory_analysis()
    cost = low.compiled.cost_analysis()
    coll = parse_collectives(low.compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(flops, hbm, coll.total_link_bytes)
    return dict(
        arch=f"graph_bsp_{low.program}",
        compute_backend=compute_backend,
        partitioner=spec_p.name,
        scorer=spec_p.scorer,
        shape=f"p{p}_friendster_scale",
        mesh="2x16x16" if multi_pod else "16x16",
        chips=p,
        compile_s=round(low.compile_s, 2),
        flops_per_device=flops,
        hbm_bytes_per_device=hbm,
        link_bytes_per_device=coll.total_link_bytes,
        collectives=coll.per_op,
        arg_bytes=mem.argument_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        per_device_hbm_total=mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        **terms,
    )
