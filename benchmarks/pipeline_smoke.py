"""CI benchmark smoke: one partition → build → run pipeline at p=32.

Emits machine-readable `BENCH_pipeline.json` at the repo root so the perf
trajectory is tracked from PR 3 onward: partition wall, build wall
(vectorized vs legacy builder), a partition-quality section (replication
factor and edge/vertex imbalance per registered streaming EdgeScorer —
the paper's Table-III comparison regenerated on every CI run), and for
EVERY registered engine program (CC, SSSP, BFS, reachability, PageRank —
all through the one generic `VertexProgram` driver) the host- vs
fused-driver wall, supersteps/s, dispatch counts, and message stats, plus
a distributed-PageRank section (sim-vs-dist value match, messages,
supersteps) run on a forced 8-device host mesh in a subprocess, and a
serving section: batched-vs-sequential throughput at B=8
through the new `repro.serve` tier (asserted >= 2x), plus a synthetic
power-law trace replayed through the `GraphQueryServer` admission queue
(p50/p99 queue latency, padding waste, executable-cache hit rate; the
cache is asserted to compile at most once per (program, bucket)), and a
resilience section: crash/resume bit-parity
(`resume_matches_uninterrupted` asserted) plus a chaos serving trace with
injected transient faults (retry/shed counters; every query asserted to
terminate answered-or-named-failure), and a megakernel section (schema 6):
per-program xla-fused vs Pallas-superstep-megakernel walls with asserted
bit-parity (interpreter walls on a CPU host; the compiled path lights up
on accelerators) plus the window-commit partition wall vs the faithful
scan (`matches_scan` asserted) and the frozen chunked commit, and a
scale section (schema 7): the out-of-core pipeline — sharded rmat ->
external degree-sum order -> streamed partition -> streamed two-level
build -> CC — on a downscaled twin with per-stage wall + peak-RSS
metering and `matches_in_memory` (bit-parity against the fully
in-memory pipeline) asserted; `python -m benchmarks.scale_pipeline
--full` runs the same pipeline at 2^25 vertices / 2^27 edges. The main
partition/build stages also record the peak-RSS high-water mark.

Two speedup figures per engine program:
  - wall_speedup: measured host/fused wall ratio. On a CPU host, dispatch
    is cheap and per-superstep compute dominates, so this hovers near 1;
    on accelerators the per-step host round-trip is the cost the fused
    driver deletes.
  - dispatch_reduction: host dispatches per run (== supersteps) vs the
    fused driver's single dispatch — the structural, hardware-independent
    improvement (asserted >= 2x).

Usage: python -m benchmarks.pipeline_smoke [repeats]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from repro.api import GraphPipeline, list_partitioners
from repro.core.streaming import streaming_chunked_partition, streaming_scan_partition
from repro.graph.build import build_subgraphs, build_subgraphs_legacy
from repro.graph.generate import rmat

P = 32
OUT = Path(__file__).resolve().parents[1] / "BENCH_pipeline.json"
SRC = Path(__file__).resolve().parents[1] / "src"

# Every registered program, with its engine kwargs. PageRank runs its
# fixed-iteration mode; the rest run to fixpoint.
PROGRAMS = (("cc", {}), ("sssp", {}), ("bfs", {}), ("reach", {}), ("pr", {"num_iters": 20}))

_DIST_PR_CODE = """
import json
import numpy as np
from repro.api import GraphPipeline
from repro.graph.generate import rmat
from repro.launch.mesh import make_host_mesh

g = rmat(1 << 12, 40_000, seed=7, a=0.65, b=0.15, c=0.15)
pipe = GraphPipeline(g).partition("ebg_chunked", parts=8)
mesh = make_host_mesh(8)
sim = pipe.run("pr", num_iters=10)
import time
t0 = time.perf_counter()
dist = pipe.run("pr", mode="dist", mesh=mesh, num_iters=10)
wall = time.perf_counter() - t0
print(json.dumps({
    "p": 8,
    "supersteps": dist.stats.supersteps,
    "messages_total": dist.stats.total_messages,
    "messages_max_mean": round(float(dist.stats.max_mean), 3),
    "matches_sim": bool(np.array_equal(sim.values, dist.values)),
    "wall_s": round(wall, 4),
}))
"""


def _partition_quality_section(graph, main_pipe) -> dict:
    """Table-III row per registered streaming EdgeScorer: one chunked
    partitioner per scorer at the smoke p, through
    `repro.core.metrics.partition_metrics`. The main pipeline IS the ebv
    row — its cached partition/metrics are reused, not recomputed. Walls
    are NOT emitted here (the ebv partition is already cached and the
    others would pay jit compile): `partition.wall_s` is the tracked
    partition-perf number; this section tracks quality only."""
    rows = {}
    for spec in list_partitioners():
        if spec.scorer is None or not spec.chunked:
            continue
        pipe = main_pipe if spec.name == main_pipe.partitioner.name else (
            GraphPipeline(graph).partition(spec.name, parts=P)
        )
        rows[spec.scorer] = {"partitioner": spec.name, **pipe.metrics.row()}
    return rows


def _med(fn, repeats: int) -> float:
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


def _best(fn, repeats: int) -> float:
    """Min-of-repeats: the standard microbenchmark estimator for walls
    whose noise is one-sided (GC pauses, scheduler preemption only ever
    ADD time). The engine host-vs-fused ratios sit near 1 on a CPU host,
    where median-of-3 jitter used to flip speedups below 1.0."""
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return float(np.min(walls))


def _dist_pagerank_section() -> dict:
    """Distributed PageRank stats on an 8-device host mesh. XLA locks the
    device count at first init, so this runs in a subprocess with its own
    XLA_FLAGS (exactly how the system tests do it). The child is pinned to
    the CPU backend: the parent already holds any accelerator."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(SRC)
    out = subprocess.run(
        [sys.executable, "-c", _DIST_PR_CODE],
        capture_output=True, text=True, env=env, timeout=560,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"distributed PageRank child failed: {(out.stderr or out.stdout).strip()[-500:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _serving_section(repeats: int) -> dict:
    """The serving tier at smoke scale: one batched B=8 dispatch vs 8
    sequential single-query runs (same facade, same fused driver), then a
    synthetic power-law trace through the admission queue. Runs on the
    serve-smoke graph (4K vertices, p=8) — the per-query regime where a
    production server lives, not the one-big-job regime above."""
    from repro.serve.trace import synthetic_trace

    B = 8
    graph = rmat(1 << 12, 40_000, seed=11, a=0.65, b=0.15, c=0.15)
    pipe = GraphPipeline(graph).partition("ebg_chunked", parts=8)
    cov = graph.covered_vertices()
    srcs = [int(v) for v in cov[np.argsort(-graph.degrees()[cov])[:B]]]

    batch_run = pipe.run_batch("bfs", srcs)  # warmup doubles as the parity run
    singles = [pipe.run("bfs", source=s) for s in srcs]
    for i in range(B):  # the serving tier's core claim, held in CI too
        assert np.array_equal(batch_run.values[i], singles[i].values), i
        assert batch_run.stats[i].supersteps == singles[i].stats.supersteps, i
    seq_wall = _med(lambda: [pipe.run("bfs", source=s) for s in srcs], repeats)
    batch_wall = _med(lambda: pipe.run_batch("bfs", srcs), repeats)
    speedup = seq_wall / batch_wall

    server = pipe.serve(max_batch=B, max_delay_s=0.005)
    trace = synthetic_trace(graph, 96, rate_qps=4000.0, seed=3)
    report = server.run_trace(trace)  # run_trace pre-warms every (program, bucket)
    trace_row = report.row()

    assert speedup >= 2.0, (seq_wall, batch_wall)
    assert trace_row["cache"]["compiles_per_key_max"] <= 1, trace_row["cache"]
    assert trace_row["queries"] == 96, trace_row
    return {
        "graph": {"family": "serve_smoke", "num_vertices": graph.num_vertices,
                  "num_edges": graph.num_edges, "p": 8},
        "batch": {
            "program": "bfs",
            "B": B,
            "seq_wall_s": round(seq_wall, 4),
            "batch_wall_s": round(batch_wall, 4),
            "throughput_speedup": round(speedup, 2),
            "supersteps_per_query": batch_run.supersteps_per_query.tolist(),
        },
        "trace": trace_row,
    }


def _megakernel_section(repeats: int) -> dict:
    """Tentpole before/after (schema 6): the xla fused driver vs the Pallas
    superstep megakernel (`compute_backend="pallas"` routes the whole local
    stage through `ops.bsp_superstep`) for every registered program, plus
    the speculative window-commit partition wall vs the faithful scan and
    the frozen chunked commit.

    Off-TPU the megakernel runs under the Pallas INTERPRETER, so the pallas
    walls here track the parity cost on a CPU host, not accelerator
    speedup — the compiled path lights up on TPU. What CI holds the line on
    is the parity flags: values and BSPStats bit-identical to the xla path
    per program, and window-commit assignments identical to the scan.
    Runs on a smaller graph than the main engine section (interpreter
    walls, not device walls)."""
    block_e = 256
    graph = rmat(1 << 11, 12_000, seed=9, a=0.65, b=0.15, c=0.15)
    pipe = GraphPipeline(graph).partition("ebg_chunked", parts=8)
    programs: dict = {}
    for prog, kw in PROGRAMS:
        runs, wall = {}, {}
        for backend in ("xla", "pallas"):
            pipe.run(prog, compute_backend=backend, block_e=block_e, **kw)  # compile
            runs[backend] = pipe.run(prog, compute_backend=backend, block_e=block_e, **kw)
            wall[backend] = _best(
                lambda b=backend: pipe.run(prog, compute_backend=b, block_e=block_e, **kw),
                repeats,
            )
        x, k = runs["xla"], runs["pallas"]
        parity = (
            bool(np.array_equal(x.values, k.values))
            and x.stats.supersteps == k.stats.supersteps
            and bool(np.array_equal(x.stats.messages_per_step_worker,
                                    k.stats.messages_per_step_worker))
            and bool(np.array_equal(x.stats.inner_iters_per_step,
                                    k.stats.inner_iters_per_step))
        )
        programs[prog] = {
            "supersteps": x.stats.supersteps,
            "xla_wall_s": round(wall["xla"], 4),
            "pallas_wall_s": round(wall["pallas"], 4),
            "parity": parity,
        }

    scan = streaming_scan_partition(graph, 8, "ebv")
    win = streaming_chunked_partition(graph, 8, "ebv", block=block_e, commit="window")
    walls = {
        "scan_wall_s": _best(lambda: streaming_scan_partition(graph, 8, "ebv"), repeats),
        "frozen_wall_s": _best(
            lambda: streaming_chunked_partition(graph, 8, "ebv", block=block_e, commit="frozen"),
            repeats,
        ),
        "window_wall_s": _best(
            lambda: streaming_chunked_partition(graph, 8, "ebv", block=block_e, commit="window"),
            repeats,
        ),
    }
    window = {
        "scorer": "ebv",
        "block": block_e,
        **{k: round(v, 4) for k, v in walls.items()},
        "window_speedup_vs_scan": round(walls["scan_wall_s"] / walls["window_wall_s"], 2),
        "matches_scan": bool(np.array_equal(win.part, scan.part)),
    }
    return {
        "graph": {"family": "megakernel_smoke", "num_vertices": graph.num_vertices,
                  "num_edges": graph.num_edges, "p": 8},
        "block_e": block_e,
        "programs": programs,
        "parity_all": all(row["parity"] for row in programs.values()),
        "window_commit": window,
    }


def _resilience_section() -> dict:
    """Chaos smoke (schema 5): the fault-tolerance claims held in CI.

    1. Crash/resume bit-parity: run CC with checkpointing and a seeded
       worker crash, resume from the checkpoint directory, and assert
       values AND BSPStats are bit-identical to the uninterrupted run
       (`resume_matches_uninterrupted`).
    2. Chaos serving: a short trace through `run_graph_serve` with
       injected transient faults and stragglers — every query must
       terminate (answered within the retry budget or failed with a
       named reason), zero unhandled exceptions.
    """
    import shutil
    import tempfile

    from repro.launch.graph_serve import run_graph_serve
    from repro.resilience import FaultPlan, WorkerCrashError, resume_bsp

    graph = rmat(1 << 12, 40_000, seed=13, a=0.65, b=0.15, c=0.15)
    pipe = GraphPipeline(graph).partition("ebg_chunked", parts=8)
    base = pipe.run("cc")
    crash_step = max(1, base.stats.supersteps // 2)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_resilience_")
    try:
        t0 = time.perf_counter()
        try:
            pipe.run(
                "cc", checkpoint_every=1, ckpt_dir=ckpt_dir,
                fault_plan=FaultPlan(seed=5, crash_at_superstep=crash_step),
            )
            crashed = False
        except WorkerCrashError:
            crashed = True
        # CC builds the symmetrized subgraphs; resume against the SAME build
        # (the resume metadata fingerprints the SubgraphSet dims).
        vals, stats = resume_bsp(base.subgraphs, ckpt_dir=ckpt_dir)
        resume_wall = time.perf_counter() - t0
        matches = (
            bool(np.array_equal(np.asarray(vals)[:, :-1], base.values))
            and stats.supersteps == base.stats.supersteps
            and np.array_equal(stats.messages_per_step_worker,
                               base.stats.messages_per_step_worker)
            and np.array_equal(stats.inner_iters_per_step, base.stats.inner_iters_per_step)
        )
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    chaos = run_graph_serve(
        num_vertices=1 << 11, num_edges=16_000, parts=4, queries=48,
        rate_qps=4000.0, max_batch=8, seed=3,
        fault_seed=11, transient_prob=0.2, straggler_prob=0.15,
        straggler_delay_s=0.005, max_retries=4,
    )
    res = chaos["resilience"]
    assert res["terminated"] == 48, res  # every query accounted for
    assert res["answered"] + res["failed"] == 48, res
    # seed=11 is chosen so the deterministic draws actually fire: the
    # trace must exercise the retry path, not just pass fault-free.
    assert res["faults_injected"] > 0 and res["retries"] > 0, res
    return {
        "crash_resume": {
            "program": "cc",
            "crash_at_superstep": crash_step,
            "crashed": crashed,
            "resume_matches_uninterrupted": matches,
            "wall_s": round(resume_wall, 4),
        },
        "chaos_serving": {
            "queries": 48,
            "transient_prob": 0.2,
            "straggler_prob": 0.15,
            **res,
        },
    }


def main(repeats: int = 3, out_path: Path = OUT) -> dict:
    # twitter_like family at smoke scale: heavy-tailed rmat, p=32 workers.
    graph = rmat(1 << 14, 200_000, seed=7, a=0.65, b=0.15, c=0.15)
    pipe = GraphPipeline(graph).partition("ebg_chunked", parts=P)

    from benchmarks.scale_pipeline import peak_rss_mb, run_scale

    t0 = time.perf_counter()
    result = pipe.result
    partition_s = time.perf_counter() - t0
    partition_rss = peak_rss_mb()

    build_s = _med(lambda: build_subgraphs(graph, result, symmetrize=True), repeats)
    build_legacy_s = _med(lambda: build_subgraphs_legacy(graph, result, symmetrize=True), repeats)
    build_rss = peak_rss_mb()

    quality = _partition_quality_section(graph, pipe)

    engine: dict = {}
    totals = {"host": 0.0, "fused": 0.0, "dispatches_host": 0, "dispatches_fused": 0}
    for prog, kw in PROGRAMS:
        pipe.prepare(prog)
        pipe.run(prog, driver="host", **kw)  # compile outside the timers
        run = pipe.run(prog, driver="fused", **kw)  # warmup doubles as the stats run
        wall = {d: _best(lambda d=d: pipe.run(prog, driver=d, **kw), repeats) for d in ("host", "fused")}
        steps = run.stats.supersteps
        engine[prog] = {
            "supersteps": steps,
            "messages_total": run.stats.total_messages,
            "messages_max_mean": round(float(run.stats.max_mean), 3),
            "host": {
                "wall_s": round(wall["host"], 4),
                "supersteps_per_s": round(steps / wall["host"], 1),
                "dispatches": steps,
            },
            "fused": {
                "wall_s": round(wall["fused"], 4),
                "supersteps_per_s": round(steps / wall["fused"], 1),
                "dispatches": 1,
            },
            "wall_speedup": round(wall["host"] / wall["fused"], 2),
            "dispatch_reduction": steps,
        }
        totals["host"] += wall["host"]
        totals["fused"] += wall["fused"]
        totals["dispatches_host"] += steps
        totals["dispatches_fused"] += 1

    dist_pr = _dist_pagerank_section()
    serving = _serving_section(repeats)
    resilience = _resilience_section()
    megakernel = _megakernel_section(repeats)
    scale = run_scale()

    data = {
        "schema": 7,
        "graph": {"family": "twitter_like_smoke", "num_vertices": graph.num_vertices,
                  "num_edges": graph.num_edges, "p": P},
        "partition": {"partitioner": "ebg_chunked", "wall_s": round(partition_s, 3),
                      "peak_rss_mb": partition_rss},
        "partition_quality": quality,
        "build": {
            "wall_s": round(build_s, 3),
            "legacy_wall_s": round(build_legacy_s, 3),
            "speedup_vs_legacy": round(build_legacy_s / build_s, 2),
            "peak_rss_mb": build_rss,
        },
        "engine": {
            **engine,
            "total": {
                "host_wall_s": round(totals["host"], 4),
                "fused_wall_s": round(totals["fused"], 4),
                "wall_speedup": round(totals["host"] / totals["fused"], 2),
                "dispatch_reduction": round(totals["dispatches_host"] / totals["dispatches_fused"], 1),
            },
        },
        "dist": {"pr": dist_pr},
        "serving": serving,
        "resilience": resilience,
        "megakernel": megakernel,
        "scale": scale,
    }
    # The structural claims CI holds the line on: the fused driver turns
    # one-dispatch-per-superstep into one dispatch per run, distributed
    # PageRank (new with the VertexProgram engine) matches simulation, and
    # every registered streaming scorer produced a well-formed quality row
    # (the per-scorer replication/imbalance numbers themselves are the
    # tracked trajectory, not an asserted threshold).
    assert data["engine"]["total"]["dispatch_reduction"] >= 2.0, data["engine"]["total"]
    assert dist_pr.get("matches_sim", False), dist_pr
    assert set(quality) >= {"ebv", "hdrf", "greedy"}, quality
    for row in quality.values():
        assert row["replication_factor"] >= 1.0 and row["edge_imbalance"] >= 1.0, row
    # Fault-tolerance claims (schema 5): crash + resume is bit-identical
    # to the uninterrupted run, and the chaos trace lost nothing.
    assert resilience["crash_resume"]["crashed"], resilience["crash_resume"]
    assert resilience["crash_resume"]["resume_matches_uninterrupted"], resilience["crash_resume"]
    # Megakernel claims (schema 6): the Pallas superstep path is
    # bit-identical to xla for every program, window commits reproduce the
    # scan exactly, and the fused driver does not LOSE wall time vs host —
    # including reach, whose min-of-repeats wall used to flip below 1.0
    # under median-of-3 jitter.
    assert megakernel["parity_all"], megakernel["programs"]
    assert megakernel["window_commit"]["matches_scan"], megakernel["window_commit"]
    assert engine["reach"]["wall_speedup"] >= 1.0, engine["reach"]
    # Scale claims (schema 7): the out-of-core downscaled twin is
    # bit-identical to the in-memory pipeline, came from a real multi-shard
    # store, and ran under two-level addressing.
    assert scale["matches_in_memory"], scale
    assert scale["graph"]["num_shards"] >= 4, scale["graph"]
    assert scale["addressing"] == "two_level", scale

    out_path.write_text(json.dumps(data, indent=2) + "\n")
    e = data["engine"]["total"]
    progs = "/".join(name for name, _ in PROGRAMS)
    reps = " ".join(f"{k}={row['replication_factor']}" for k, row in quality.items())
    print(
        f"BENCH_pipeline [{progs}]: partition {partition_s:.2f}s | build {build_s:.3f}s "
        f"({data['build']['speedup_vs_legacy']}x vs legacy) | rep[{reps}] | "
        f"engine host {e['host_wall_s']:.3f}s "
        f"-> fused {e['fused_wall_s']:.3f}s ({e['wall_speedup']}x wall, "
        f"{e['dispatch_reduction']}x fewer dispatches) | dist pr msgs "
        f"{dist_pr.get('messages_total')} | serve B=8 "
        f"{serving['batch']['throughput_speedup']}x, cache hit "
        f"{serving['trace']['cache']['hit_rate']} | resume parity "
        f"{resilience['crash_resume']['resume_matches_uninterrupted']}, chaos retries "
        f"{resilience['chaos_serving']['retries']} | megakernel parity "
        f"{megakernel['parity_all']}, window "
        f"{megakernel['window_commit']['window_speedup_vs_scan']}x vs scan | scale "
        f"oc-parity {scale['matches_in_memory']}, rf {scale['replication_factor']}, "
        f"peak rss {scale['peak_rss_mb']}MB -> {out_path.name}"
    )
    return data


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3)
