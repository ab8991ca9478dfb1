"""Controls: what `correct` must refuse, put in the program's place.

    python3 -m bench.control --workload <cell> --seeds <n> [<n> ...] --seconds <s>

For each seed this runs the cell as the benchmark does, then again with
the control in the program's place, and prints both runs' compared numbers
(one JSON line each). The benchmark's own runs never run a control.

- `hops` (BFS): the reference with one guarantee broken, BFS over the
  directed arcs only (no symmetrization).
- `rank` (PageRank, float32 ranks): the power method in bfloat16.
- `ebv` (partition): the program's own approximate path, `commit="frozen"`,
  which scores a whole block against block-start membership.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


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


def install(jobs) -> None:
    """Put the control in `jobs`' place (the hook of `bench.run.run_cell`)."""
    import jax.numpy as jnp

    from bench import reference

    d, t = jobs.data, jobs.traffic
    if jobs.kind == "partition":
        jobs.config = dict(jobs.config, partitioner=dict(jobs.config["partitioner"], commit="frozen"))
        return
    program_run = jobs.run
    compare = t["compare"]
    if compare == "hops":
        adj = reference.csr(d.src, d.dst, d.num_vertices)
        control = lambda root: reference.hops(adj, root)
    elif compare == "rank":
        ranks = pagerank_lowp(d.src, d.dst, d.num_vertices, dtype=jnp.bfloat16, **t["reference"])
        control = lambda root: ranks
    else:
        raise KeyError(f"no control for compare {compare!r}")

    def run(i):
        _, stats, root = program_run(i)
        return control(root), stats, root

    jobs.run = run


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(bench_run.ROOT / "src"))
    bench = bench_run.Bench()
    bench_run.use_compile_cache()
    device = bench_run.check_device(bench.cell(args.workload)["chips"], bench.peaks())
    for seed in args.seeds:
        for side, hook in (("program", None), ("control", install)):
            res = bench_run.run_cell(bench, args.workload, seed, args.seconds, False, device, hook=hook)
            print(json.dumps({"workload": args.workload, "seed": seed, "side": side,
                              "correct": res["correct"], "attempted": res["attempted"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
