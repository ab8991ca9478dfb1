"""EBV partitions, edge by edge.

On one window job drawn from the seed, `bench.reference.ebv_regret` with
the traffic's `reference` parameters (alpha, beta): `ebv_regret_max` is
the largest regret of an edge's part over the best part's score.
`repeat_mismatches` counts the edges on which the window's jobs disagree
with that job (EBV is deterministic).

Control: the program's own approximate path, `commit="frozen"`, which
scores a whole block against block-start membership.
"""
from __future__ import annotations

import numpy as np

from bench import jobs as jobs_mod, reference


def check(jobs, records: list, maps: dict, seed: int) -> dict:
    d = jobs.data
    (first,) = jobs_mod.sample(records, 1, seed)
    regret = reference.ebv_regret(
        d.src, d.dst, first.out, d.num_vertices, jobs.config["parts"],
        **jobs.traffic.get("reference", {}),
    )
    differ = sum(int(np.count_nonzero(r.out != first.out)) for r in records)
    return {"ebv_regret_max": float(regret.max()), "repeat_mismatches": differ}


def install_control(jobs) -> None:
    jobs.config = dict(jobs.config, partitioner=dict(jobs.config["partitioner"], commit="frozen"))
