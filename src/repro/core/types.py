"""Core graph / partition datatypes.

A graph is stored as a flat edge list (src, dst) of int32 vertex ids in
[0, num_vertices). Undirected graphs are represented by both directions
(paper §III). All partitioners consume the edge list and emit a per-edge
partition assignment in [0, num_parts) — an *edge partition* (vertex-cut),
which is what the subgraph-centric model consumes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro import obs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Edge-list graph. Arrays may be numpy or jax; int32 ids."""

    src: jax.Array  # [E]
    dst: jax.Array  # [E]
    num_vertices: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def degrees(self) -> np.ndarray:
        """Total (in+out) degree per vertex, numpy."""
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        deg = np.bincount(src, minlength=self.num_vertices)
        deg += np.bincount(dst, minlength=self.num_vertices)
        return deg.astype(np.int64)

    def covered_vertices(self) -> np.ndarray:
        """Sorted unique vertices incident to at least one edge. Isolated
        vertices have no replicas in any edge partition, so coverage is the
        domain for replication metrics, CC labels, and SSSP sources."""
        return np.unique(np.concatenate([np.asarray(self.src), np.asarray(self.dst)]))

    def validate(self) -> None:
        """Raise ValueError naming the offending field on malformed graphs
        (real exceptions, not `assert`s — they survive `python -O`)."""
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        if src.ndim != 1 or src.shape != dst.shape:
            raise ValueError(
                f"src/dst must be 1-D and the same shape; got src {src.shape}, dst {dst.shape}"
            )
        for name, arr in (("src", src), ("dst", dst)):
            if arr.min(initial=0) < 0:
                raise ValueError(f"{name} has negative vertex id {int(arr.min())}")
            if arr.max(initial=-1) >= self.num_vertices:
                raise ValueError(
                    f"{name} has vertex id {int(arr.max())} >= num_vertices={self.num_vertices}"
                )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionResult:
    """Result of an edge partitioner."""

    part: jax.Array  # [E] int32 in [0, num_parts)
    num_parts: int = dataclasses.field(metadata=dict(static=True))
    # Optional permutation applied to edges before assignment (EBG sorts
    # edges by degree-sum); part[i] corresponds to edge order[i] of the
    # ORIGINAL edge list when order is not None.
    order: Optional[jax.Array] = None

    def part_in_input_order(self) -> np.ndarray:
        """Per-edge assignment aligned with the original edge list."""
        with obs.span("partition.fetch"):
            part = np.asarray(self.part)  # waits for the partitioner's device work
            if self.order is None:
                return part
            out = np.empty_like(part)
            out[np.asarray(self.order)] = part
            return out


def edge_weights_placeholder(num_edges: int) -> np.ndarray:
    """Unit weights (paper's graphs are unweighted; SSSP uses unit/1.0)."""
    return np.ones((num_edges,), dtype=np.float32)
