"""PageRank ranks, against the float64 power method.

On `check_jobs` window jobs drawn from the seed, every covered vertex's
rank against `bench.reference.pagerank` over the job's arcs with the
traffic's `reference` parameters; `rank_rel_err` is the largest relative
error (a vertex no master holds reads inf).

Control: the same power method with every value in bfloat16, the precision
below the program's float32 ranks.
"""
from __future__ import annotations

import numpy as np

from bench import control, jobs as jobs_mod, reference


def check(jobs, records: list, maps: dict, seed: int) -> dict:
    d, t = jobs.data, jobs.traffic
    covered = d.covered()
    want = reference.pagerank(*jobs.arcs(), d.num_vertices, **t["reference"])[covered]
    worst = 0.0
    for r in jobs_mod.sample(records, int(t.get("check_jobs", len(records))), seed):
        got = jobs_mod.to_global(r.out, maps, d.num_vertices)[covered]
        err = np.abs(got - want) / want
        worst = max(worst, float(np.max(np.where(np.isnan(err), np.inf, err))))
    return {"rank_rel_err": worst}


def pagerank_lowp(src, dst, n: int, *, damping: float, num_iters: int, dtype) -> np.ndarray:
    """The reference power method with every value in `dtype`."""
    import jax
    import jax.numpy as jnp

    src, dst = jnp.asarray(src), jnp.asarray(dst)
    outdeg = jnp.zeros((n,), dtype).at[src].add(1)
    share_of = jnp.where(outdeg > 0, 1 / jnp.maximum(outdeg, 1), 0).astype(dtype)
    rank = jnp.full((n,), 1 / n, dtype)
    for _ in range(num_iters):
        agg = jax.ops.segment_sum((rank * share_of)[src], dst, num_segments=n)
        rank = ((1 - damping) / n + damping * agg).astype(dtype)
    return np.asarray(rank.astype(jnp.float32), np.float64)


def install_control(jobs) -> None:
    import jax.numpy as jnp

    d = jobs.data
    ranks = pagerank_lowp(d.src, d.dst, d.num_vertices, dtype=jnp.bfloat16, **jobs.traffic["reference"])
    control.answer_with(jobs, lambda root: ranks)
