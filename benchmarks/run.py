"""Benchmark entry point — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 0.25] [--quick]

Prints ``name,us_per_call,derived`` CSV lines at the end for harnesses.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25,
                    help="graph size multiplier vs DESIGN.md defaults")
    ap.add_argument("--quick", action="store_true", help="partition metrics only")
    # Names are validated against the repro.api registry after parsing, so
    # `--help` / usage errors stay import-cheap (no jax load).
    ap.add_argument("--partitioners", nargs="+", metavar="NAME", default=None,
                    help="registry subset, e.g. ebg hdrf greedy dbh "
                         "(default: every benchmark_default partitioner, which "
                         "includes the streaming-scorer baselines hdrf/greedy)")
    ap.add_argument("--compute-backends", nargs="+", metavar="BACKEND", default=["xla"],
                    help="engine hot-path impls to run (xla | ref | pallas); more than "
                         "one A/Bs the runtime section per backend and records the speedup")
    args = ap.parse_args(argv)

    from repro.api import COMPUTE_BACKENDS, benchmark_partitioners, partitioner_names
    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()

    known = partitioner_names()
    parts = list(benchmark_partitioners()) if args.partitioners is None else args.partitioners
    unknown = [n for n in parts if n not in known]
    if unknown:
        ap.error(f"unknown partitioner(s) {unknown}; registered: {list(known)}")
    backends = list(dict.fromkeys(args.compute_backends))  # dedup, keep order
    bad = [b for b in backends if b not in COMPUTE_BACKENDS]
    if bad:
        ap.error(f"unknown compute backend(s) {bad}; valid: {list(COMPUTE_BACKENDS)}")

    from benchmarks import messages, partition_tables, runtime

    csv: list[tuple[str, float, str]] = []

    t0 = time.time()
    res3 = partition_tables.main(args.scale, partitioners=parts)
    csv.append(("table1_table3_partition_metrics", (time.time() - t0) * 1e6,
                f"ebg_rep={res3['livejournal_like'].get('ebg', {}).get('replication_factor', 'n/a')}"))

    if not args.quick:
        t0 = time.time()
        res45 = messages.main(args.scale, partitioners=parts)
        ebg = res45["livejournal_like"].get("ebg", {})
        csv.append(("table4_table5_messages", (time.time() - t0) * 1e6,
                    f"ebg_msgs={ebg.get('total_messages', 'n/a')};maxmean={ebg.get('max_mean', 'n/a')}"))

        rt_by_backend = {}
        for backend in backends:
            t0 = time.time()
            # A/B runs warm up each backend first so wall_s (and the speedup
            # lines below) compare hot-path execution, not jit compiles.
            resrt = runtime.main(args.scale, partitioners=parts, compute_backend=backend,
                                 warmup=len(backends) > 1)
            rt_by_backend[backend] = resrt
            best = resrt[("livejournal_like", "cc")].get("ebg", {}).get("sim_runtime_s", "n/a")
            tag = "fig3_fig4_runtime" if backend == "xla" else f"fig3_fig4_runtime_{backend}"
            csv.append((tag, (time.time() - t0) * 1e6, f"ebg_cc={best}s"))
        # A/B: record wall-clock speedup of each backend vs the first one.
        base = backends[0]
        for other in backends[1:]:
            for (key, algo), row_b in rt_by_backend[base].items():
                row_o = rt_by_backend[other].get((key, algo), {})
                if "ebg" not in row_b or "ebg" not in row_o:
                    continue
                wall_b = max(row_b["ebg"]["wall_s"], 1e-3)
                wall_o = max(row_o["ebg"]["wall_s"], 1e-3)
                csv.append((f"backend_ab_{base}_vs_{other}[{key}/{algo}]", 0.0,
                            f"ebg_wall_speedup={wall_b / wall_o:.2f}x"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
