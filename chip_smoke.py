#!/usr/bin/env python3
"""Chip smoke: the graph system's main path once, end to end, on a TPU.

  python chip_smoke.py [--seed 0]              # one chip
  python chip_smoke.py --chips 4 [--seed 0]    # four chips: mode="dist" only

One chip: a Graph500 R-MAT graph (a=0.57, b=0.19, c=0.19, edge factor 16)
at scale 22 (2^22 vertices, 2^26 edges) is generated from the seed and
partitioned with the chunked EBV partitioner on the compiled Pallas commit
kernel. A second graph at scale 20 is partitioned, built into per-worker
subgraphs, run through cc, sssp, bfs, reach and pr on both engine backends
("xla" and the compiled Pallas megakernel), and served 32 BFS/SSSP point
queries: at scale 22 those phases would not fit the run's time limit.
Every phase checks its output: partition bit parity against the XLA and
scan partitioners, engine bit parity between backends plus agreement with
the numpy oracles, served answers equal to single-source runs.

Four chips: cc and pr at scale 20 with mode="dist" (shard_map +
all_to_all, one subgraph per chip) on both backends, against the same
programs in sim mode on one chip.

The walls printed along the way are smoke timings, not benchmark numbers.
The last line is the device report; it is printed only when every phase
passed. Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

PARTS = 8
SCALE = 22  # partition phase
ENGINE_SCALE = 20  # build, engine and serving phases; the --chips 4 run
PARITY_SCALE = 16
PROGRAMS = ("cc", "sssp", "bfs", "reach", "pr")
PR_ITERS = 20
PR_RTOL = 1e-3  # f32 engine vs f64 oracle, 20 power iterations
SERVE_QUERIES = 16  # per program (bfs, sssp)
SERVE_BATCH = 8


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"[smoke timing, not a benchmark] {name}: {time.perf_counter() - t0:.1f} s", flush=True)


def graph500(scale: int, seed: int):
    from repro.graph.generate import rmat

    return rmat(1 << scale, 16 << scale, a=0.57, b=0.19, c=0.19, seed=seed)


def stats_equal(a, b) -> bool:
    import numpy as np

    return a.supersteps == b.supersteps and all(
        np.array_equal(getattr(a, f), getattr(b, f))
        for f in ("messages_per_step_worker", "inner_iters_per_step", "comp_work_per_worker")
    )


# ------------------------------------------------------------- one chip


def partition_phase(g, seed: int) -> None:
    import numpy as np

    from repro.api import GraphPipeline
    from repro.core.metrics import theorem1_edge_bound, theorem2_vertex_bound

    with phase("partition ebg_chunked/window/pallas"):
        pipe = GraphPipeline(g).partition(
            "ebg_chunked", parts=PARTS, commit="window", compute_backend="pallas", block=1024
        )
        part = pipe.result.part_in_input_order()
    check(part.shape == (g.num_edges,) and part.min() >= 0 and part.max() < PARTS,
          "partition left edges unassigned")
    m = pipe.metrics
    b1 = theorem1_edge_bound(g.num_edges, PARTS, 1.0, 1.0)
    b2 = theorem2_vertex_bound(int(m.vertices_per_part.sum()), g.num_vertices, PARTS, 1.0, 1.0)
    check(m.edge_imbalance <= b1 and m.vertex_imbalance <= b2, f"EBV balance bounds broken: {m.row()}")
    print(f"partition: every edge assigned, replication factor {m.replication_factor:.4f}, "
          f"edge imbalance {m.edge_imbalance:.4f} <= {b1:.4f}, "
          f"vertex imbalance {m.vertex_imbalance:.4f} <= {b2:.4f}", flush=True)

    with phase(f"partition parity at scale {PARITY_SCALE}"):
        small = graph500(PARITY_SCALE, seed)
        parts = {}
        for name, kw in (("pallas", dict(commit="window", compute_backend="pallas")),
                         ("xla", dict(commit="window", compute_backend="xla"))):
            parts[name] = GraphPipeline(small).partition("ebg_chunked", parts=PARTS, **kw).result
        parts["scan"] = GraphPipeline(small).partition("ebg", parts=PARTS).result
        ref = np.asarray(parts["scan"].part)
        for name in ("pallas", "xla"):
            check(np.array_equal(np.asarray(parts[name].part), ref),
                  f"ebg_chunked[{name}] != ebg scan at scale {PARITY_SCALE}")
    print(f"partition parity: ebg_chunked pallas == xla == ebg scan on "
          f"{small.num_edges} edges (scale {PARITY_SCALE})", flush=True)


def oracle(g, name: str, source):
    from repro.graph import algorithms as alg

    if name == "cc":
        return alg.cc_reference(g)
    if name == "reach":
        return alg.reachability_reference(g)
    if name == "bfs":
        return alg.bfs_reference(g, source)
    if name == "sssp":
        return alg.sssp_reference(g, source)
    return alg.pagerank_reference(g, num_iters=PR_ITERS)


@contextlib.contextmanager
def recording_fused_bsp(calls: list):
    """Record the (abstract arguments, static options) of every fused BSP
    program `GraphPipeline.run` launches, so the exact program can be
    lowered again and inspected."""
    import jax

    from repro.graph import engine

    fused = engine._fused_bsp

    def record(sub, val, **kw):
        calls.append((jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (sub, val)), kw))
        return fused(sub, val, **kw)

    engine._fused_bsp = record
    try:
        yield fused
    finally:
        engine._fused_bsp = fused


def engine_phase(pipe):
    import numpy as np

    from repro.graph import engine

    g = pipe.graph
    covered = g.covered_vertices()
    source = pipe.default_source()
    for name in PROGRAMS:
        runs = {}
        for backend in ("xla", "pallas"):
            calls = []
            with phase(f"engine {name}/{backend}"), recording_fused_bsp(calls) as fused:
                runs[backend] = pipe.run(name, compute_backend=backend, num_iters=PR_ITERS) \
                    if name == "pr" else pipe.run(name, compute_backend=backend)
        # The pallas run's program carries the compiled Mosaic kernel.
        check(len(calls) == 1, f"{name}/pallas: expected one fused program, saw {len(calls)}")
        (abstract, kw), = calls
        check(kw["backend"] == "pallas" and "tpu_custom_call" in fused.lower(*abstract, **kw).as_text(),
              f"{name}: the pallas run's program has no tpu_custom_call")
        x, k = runs["xla"], runs["pallas"]
        check(np.array_equal(x.values, k.values), f"{name}: pallas values != xla values")
        check(stats_equal(x.stats, k.stats), f"{name}: pallas BSPStats != xla BSPStats")
        with phase(f"oracle {name}"):
            want = oracle(g, name, source)[covered]
        got = k.to_global()[covered]
        if name == "pr":
            check(np.allclose(got, want, rtol=PR_RTOL, atol=0.0),
                  f"pr: engine differs from the oracle beyond rtol {PR_RTOL}")
            err = float(np.max(np.abs(got - want) / want))
            detail = f"max relative error {err:.2e} <= {PR_RTOL}"
        else:
            if name == "sssp":  # the engine's f32 "unreached" is INF_F32, the oracle's inf
                got = np.where(got >= float(engine.INF_F32), np.inf, got)
            check(np.array_equal(got, want), f"{name}: engine differs from the oracle")
            detail = "exact"
        print(f"engine {name}: pallas == xla bitwise (values + BSPStats, "
              f"{k.stats.supersteps} supersteps), oracle agreement {detail}, "
              "the pallas run's program holds a tpu_custom_call", flush=True)


def serve_phase(pipe, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    covered = pipe.graph.covered_vertices()
    server = pipe.serve(compute_backend="pallas", max_batch=SERVE_BATCH)
    qids = []
    with phase(f"serve {2 * SERVE_QUERIES} queries (pallas, compile included)"):
        for name in ("bfs", "sssp"):
            for s in rng.choice(covered, SERVE_QUERIES, replace=False):
                qids.append((server.submit(name, int(s)), name, int(s)))
        server.drain()
    with phase("serve check against single-source runs (xla)"):
        for qid, name, s in qids:
            res = server.result(qid)
            check(res.ok, f"query {qid} ({name} from {s}) failed: {res}")
            one = pipe.run(name, source=s, compute_backend="xla")
            check(np.array_equal(res.values, one.values) and stats_equal(res.stats, one.stats),
                  f"served {name} from {s} != single-source run")
    counters = server.resilience_counters()
    cache = server.cache.stats()
    check(counters["degraded_batches"] == 0, f"serving degraded: {counters}")
    check(cache["compiles_per_key_max"] <= 1, f"an executable compiled twice: {cache}")
    print(f"serve: {len(qids)} answers == single-source runs, degraded_batches 0, "
          f"cache {cache}", flush=True)


def load(scale: int, seed: int):
    with phase(f"generate R-MAT scale {scale}"):
        g = graph500(scale, seed)
    print(f"graph: Graph500 R-MAT scale {scale}: {g.num_vertices} vertices, "
          f"{g.num_edges} edges after dedup, seed {seed}", flush=True)
    return g


def one_chip(seed: int) -> None:
    from repro.api import GraphPipeline

    partition_phase(load(SCALE, seed), seed)
    print(f"cut: build, engine and serving run at scale {ENGINE_SCALE}, not {SCALE}: on one "
          f"TPU v5e they take ~620 s at scale {ENGINE_SCALE}, and scale {SCALE} has "
          f"{2 ** (SCALE - ENGINE_SCALE)}x the edges, past the run's 1200 s limit", flush=True)
    g = load(ENGINE_SCALE, seed)
    with phase("partition for the engine (pallas)"):
        pipe = GraphPipeline(g).partition(
            "ebg_chunked", parts=PARTS, commit="window", compute_backend="pallas", block=1024
        )
        pipe.result
    with phase("build (symmetrized + directed)"):
        pipe.prepare("cc")
        pipe.prepare("bfs")
    engine_phase(pipe)
    serve_phase(pipe, seed)


# ---------------------------------------------------------- four chips


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.api import GraphPipeline

    g = load(ENGINE_SCALE, seed)
    p = len(jax.devices())
    with phase("partition + build"):
        pipe = GraphPipeline(g).partition("ebg_chunked", parts=p, commit="window",
                                          compute_backend="pallas", block=1024)
        pipe.prepare("cc")
        pipe.prepare("pr")
    mesh = jax.make_mesh((p,), ("workers",))
    for name in ("cc", "pr"):
        kw = dict(num_iters=PR_ITERS) if name == "pr" else {}
        for backend in ("xla", "pallas"):
            with phase(f"{name}/{backend} sim (one chip)"):
                sim = pipe.run(name, compute_backend=backend, **kw)
            with phase(f"{name}/{backend} dist ({p} chips)"):
                dist = pipe.run(name, mode="dist", mesh=mesh, compute_backend=backend, **kw)
            check(np.array_equal(sim.values, dist.values), f"{name}/{backend}: dist != sim values")
            check(stats_equal(sim.stats, dist.stats), f"{name}/{backend}: dist != sim BSPStats")
            print(f"dist {name}/{backend}: == sim bitwise (values + BSPStats, "
                  f"{dist.stats.supersteps} supersteps)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.kernels import dispatch
    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device: {json.dumps(device)}", flush=True)
    check(device["platform"] == "tpu", f"needs a TPU, found platform {device['platform']!r}")
    check(dispatch.default_interpret(None) is False, "Pallas would run in interpret mode")
    if args.chips == 4:
        check(device["count"] == 4, f"--chips 4 needs 4 devices, found {device['count']}")
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
