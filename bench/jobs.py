"""The jobs a traffic mix runs.

A traffic file names its `kind`:

- `engine`: set-up partitions and builds the configuration's graph once;
  each job is one `GraphPipeline.run` of the traffic's `program`, from the
  call to its values on the host. Source programs cycle through the
  traffic's `roots`: a count, drawn from the seed among vertices of nonzero
  degree (Graph500's search keys), or a list of vertex ids, taken in its
  order.
- `partition`: each job partitions the graph's host edge arrays on a fresh
  `GraphPipeline`, from the call to the assignment on the host.

A traffic file also names its `compare`, `bench/compares/<compare>.py`:
its `check(jobs, records, maps, seed)` compares what the window's jobs
produced with `bench.reference`, after the program's state is freed, and
gives the numbers that the traffic's `limits` hold; its
`install_control(jobs)` puts the control in the program's place.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from bench import work


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


def draw_roots(spec, data: GraphData, seed: int) -> Optional[np.ndarray]:
    """A traffic's `roots`: `<count>` distinct vertices of nonzero degree
    drawn from the seed, or a list of vertex ids taken as it is. A listed id
    outside the graph or of zero degree is refused."""
    if isinstance(spec, list):
        if not spec:
            raise ValueError("roots: the list is empty")
        degrees = data.degrees()
        for v in spec:
            if not isinstance(v, int) or not 0 <= v < data.num_vertices:
                raise ValueError(f"root {v!r} is not a vertex id in [0, {data.num_vertices})")
            if degrees[v] == 0:
                raise ValueError(f"root {v} has degree 0")
        return np.asarray(spec, np.int64)
    if not spec:
        return None
    return np.random.default_rng(seed).choice(data.covered(), size=int(spec), replace=False)


class Jobs:
    """What both kinds share: the traffic's compare module decides `correct`."""

    compare = None  # the module `bench/compares/<compare>.py`

    def check(self, records: list, maps: dict, seed: int) -> dict:
        return self.compare.check(self, records, maps, seed)


class EngineJobs(Jobs):
    """Partition and build once; each job is one engine run."""

    kind = "engine"

    def __init__(self, data: GraphData, config: dict, traffic: dict, seed: int):
        import jax

        from repro.api import GraphPipeline

        self.data, self.config, self.traffic = data, config, traffic
        self.roots = draw_roots(traffic.get("roots"), data, seed)
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

    def arcs(self) -> tuple[np.ndarray, np.ndarray]:
        """The job's arcs: the graph's, and each reversed where the traffic
        symmetrizes."""
        d = self.data
        if self.symmetrize:
            return np.concatenate([d.src, d.dst]), np.concatenate([d.dst, d.src])
        return d.src, d.dst


class PartitionJobs(Jobs):
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


KINDS = {"engine": EngineJobs, "partition": PartitionJobs}


def make_jobs(data: dict, config: dict, traffic: dict, seed: int, compare) -> Jobs:
    """The traffic's jobs over the generated `data`, checked by `compare`
    (the module of `bench/compares/<compare>.py`)."""
    try:
        kind = KINDS[traffic["kind"]]
    except KeyError:
        raise KeyError(f"unknown traffic kind {traffic.get('kind')!r}; known: {sorted(KINDS)}") from None
    jobs = kind(GraphData(data), config, traffic, seed)
    jobs.compare = compare
    return jobs


def sample(records: list, k: int, seed: int) -> list:
    """`k` of the window's records (all, where it holds fewer), drawn from
    the seed, in window order."""
    rng = np.random.default_rng(seed)
    idx = np.sort(rng.choice(len(records), size=min(k, len(records)), replace=False))
    return [records[i] for i in idx]


def to_global(values: np.ndarray, maps: dict, num_vertices: int) -> np.ndarray:
    """Per-vertex values read at master replicas; NaN where no master holds
    one. A control's output is per-vertex already."""
    if values.ndim == 1:
        return values
    out = np.full(num_vertices, np.nan)
    sel = maps["is_master"] & (maps["gid"] >= 0)
    out[maps["gid"][sel]] = values[sel]
    return out
