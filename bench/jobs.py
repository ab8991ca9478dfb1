"""The jobs a traffic mix runs, and the comparison that decides `correct`.

A traffic file names its `kind`:

- `engine`: set-up partitions and builds the configuration's graph once;
  each job is one `GraphPipeline.run` of the traffic's `program`, from the
  call to its values on the host. Source programs take `roots` roots from
  the seed, among vertices of nonzero degree (Graph500's search keys), and
  cycle through them.
- `partition`: each job partitions the graph's host edge arrays on a fresh
  `GraphPipeline`, from the call to the assignment on the host.

`check` compares what the window's jobs produced with `bench.reference`,
after the program's state is freed. Each comparison gives one number per
traffic `compare` name; the traffic file holds its limit.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from bench import reference, work

# The engine's "unreached" hop count (repro.graph.engine.INF_I32).
UNREACHED_HOPS = 2**31 - 1


@dataclasses.dataclass
class Record:
    """One job of the window: host clock span, output, BSP counters."""

    t0_ns: int
    t1_ns: int
    out: np.ndarray
    stats: Optional[object] = None
    root: Optional[int] = None


class GraphData:
    """The configuration's graph as the benchmark made it (host arrays)."""

    def __init__(self, data: dict):
        self.src = data["src"]
        self.dst = data["dst"]
        self.weight = data["weight"]
        self.num_vertices = int(data["num_vertices"])

    def program_graph(self):
        from repro.core.types import Graph

        return Graph(src=self.src, dst=self.dst, num_vertices=self.num_vertices)

    def degrees(self) -> np.ndarray:
        return (np.bincount(self.src, minlength=self.num_vertices)
                + np.bincount(self.dst, minlength=self.num_vertices))

    def covered(self) -> np.ndarray:
        return np.flatnonzero(self.degrees())


def _partition_kwargs(config: dict) -> tuple[str, dict]:
    spec = dict(config["partitioner"])
    return spec.pop("name"), spec


class EngineJobs:
    """Partition and build once; each job is one engine run."""

    kind = "engine"

    def __init__(self, data: GraphData, config: dict, traffic: dict, seed: int):
        import jax

        from repro.api import GraphPipeline

        self.data, self.config, self.traffic = data, config, traffic
        self.program = traffic["program"]
        self.symmetrize = bool(traffic.get("symmetrize", False))
        self.kw = dict(traffic.get("run", {}))
        if config["mode"] == "dist":
            self.kw.update(mode="dist", mesh=jax.make_mesh((config["parts"],), ("workers",)))
        self.spans = {}
        name, pkw = _partition_kwargs(config)
        t = time.perf_counter()
        pipe = GraphPipeline(data.program_graph(), weights=data.weight)
        self.pipe = pipe.partition(name, parts=config["parts"], **pkw)
        self.pipe.result
        self.spans["partition_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.pipe.prepare(self.program, symmetrize=self.symmetrize)
        self.spans["build_s"] = time.perf_counter() - t
        self.roots = None
        if traffic.get("roots"):
            rng = np.random.default_rng(seed)
            self.roots = rng.choice(data.covered(), size=int(traffic["roots"]), replace=False)
        self.lower_bytes = work.engine_job_bytes(
            data.src, data.dst, data.num_vertices, symmetrize=self.symmetrize,
            weighted=traffic.get("weighted", False),
        )

    def run(self, i: int) -> tuple:
        root = None if self.roots is None else int(self.roots[i % len(self.roots)])
        kw = dict(self.kw) if root is None else dict(self.kw, source=root)
        run = self.pipe.run(self.program, symmetrize=self.symmetrize, **kw)
        return run.values, run.stats, root

    def close(self) -> dict:
        """Free the program's state; keep the host maps the check needs."""
        sub = self.pipe.subgraphs_for(symmetrize=self.symmetrize)
        maps = dict(gid=np.asarray(sub.gid), is_master=np.asarray(sub.is_master))
        del sub
        self.pipe = None
        return maps

    def check(self, records: list, maps: dict, seed: int) -> dict:
        d, t = self.data, self.traffic
        sample = _sample(records, int(t.get("check_jobs", len(records))), seed)
        covered = d.covered()
        src, dst = d.src, d.dst
        if self.symmetrize:
            src, dst = np.concatenate([d.src, d.dst]), np.concatenate([d.dst, d.src])
        compare = t["compare"]
        worst = 0.0
        if compare == "rank":
            want = reference.pagerank(src, dst, d.num_vertices, **t["reference"])[covered]
            for r in sample:
                got = _to_global(r.out, maps, d.num_vertices)[covered]
                err = np.abs(got - want) / want
                worst = max(worst, float(np.max(np.where(np.isnan(err), np.inf, err))))
            return {"rank_rel_err": worst}
        if compare != "hops":
            raise KeyError(f"unknown compare {compare!r}; known: hops, rank")
        adj = reference.csr(src, dst, d.num_vertices)
        mismatches = 0
        for r in sample:
            want = reference.hops(adj, r.root)[covered]
            got = _to_global(r.out, maps, d.num_vertices)[covered]
            got = np.where(got >= UNREACHED_HOPS, np.inf, got)
            mismatches += int(np.count_nonzero(got != want))
        return {"hops_mismatches": mismatches}


class PartitionJobs:
    """Each job partitions the host edge arrays on a fresh pipeline."""

    kind = "partition"

    def __init__(self, data: GraphData, config: dict, traffic: dict, seed: int):
        self.data, self.config, self.traffic = data, config, traffic
        self.graph = data.program_graph()
        self.spans = {}
        self.lower_bytes = None

    def run(self, i: int) -> tuple:
        from repro.api import GraphPipeline

        name, pkw = _partition_kwargs(self.config)
        res = GraphPipeline(self.graph).partition(name, parts=self.config["parts"], **pkw).result
        return res.part_in_input_order(), None, None

    def close(self) -> dict:
        self.graph = None
        return {}

    def check(self, records: list, maps: dict, seed: int) -> dict:
        d = self.data
        (first,) = _sample(records, 1, seed)
        regret = reference.ebv_regret(
            d.src, d.dst, first.out, d.num_vertices, self.config["parts"],
            **self.traffic.get("reference", {}),
        )
        differ = sum(int(np.count_nonzero(r.out != first.out)) for r in records)
        return {"ebv_regret_max": float(regret.max()), "repeat_mismatches": differ}


KINDS = {"engine": EngineJobs, "partition": PartitionJobs}


def make_jobs(data: dict, config: dict, traffic: dict, seed: int):
    try:
        kind = KINDS[traffic["kind"]]
    except KeyError:
        raise KeyError(f"unknown traffic kind {traffic.get('kind')!r}; known: {sorted(KINDS)}") from None
    return kind(GraphData(data), config, traffic, seed)


def _sample(records: list, k: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(len(records), size=min(k, len(records)), replace=False))
    return [records[i] for i in idx]


def _to_global(values: np.ndarray, maps: dict, num_vertices: int) -> np.ndarray:
    """Per-vertex values read at master replicas; NaN where no master holds
    one. A control's output is per-vertex already."""
    if values.ndim == 1:
        return values
    out = np.full(num_vertices, np.nan)
    sel = maps["is_master"] & (maps["gid"] >= 0)
    out[maps["gid"][sel]] = values[sel]
    return out
