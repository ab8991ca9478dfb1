"""BFS hop counts, exact.

On `check_jobs` window jobs drawn from the seed, every covered vertex's hop
count against `bench.reference.hops` over the job's arcs (symmetrized
where the traffic symmetrizes); `hops_mismatches` counts the differences.

Control: the reference with one guarantee broken, BFS over the directed
arcs only (no symmetrization).
"""
from __future__ import annotations

import numpy as np

from bench import control, jobs as jobs_mod, reference

# The engine's "unreached" hop count (repro.graph.engine.INF_I32).
UNREACHED_HOPS = 2**31 - 1


def check(jobs, records: list, maps: dict, seed: int) -> dict:
    d = jobs.data
    covered = d.covered()
    adj = reference.csr(*jobs.arcs(), d.num_vertices)
    mismatches = 0
    for r in jobs_mod.sample(records, int(jobs.traffic.get("check_jobs", len(records))), seed):
        want = reference.hops(adj, r.root)[covered]
        got = jobs_mod.to_global(r.out, maps, d.num_vertices)[covered]
        got = np.where(got >= UNREACHED_HOPS, np.inf, got)
        mismatches += int(np.count_nonzero(got != want))
    return {"hops_mismatches": mismatches}


def install_control(jobs) -> None:
    d = jobs.data
    adj = reference.csr(d.src, d.dst, d.num_vertices)
    control.answer_with(jobs, lambda root: reference.hops(adj, root))
