"""The reduction from trace rows to device times, on hand-made rows and on a
small trace recorded on one TPU v5e (a scale-10 BFS window, committed
beside this file)."""
from __future__ import annotations

import gzip
import json
import pathlib

import pytest

from bench import run, trace
from bench_tiny import REPO

RECORDED = pathlib.Path(__file__).with_name("trace_rows_tiny_bfs.json.gz")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def test_union_and_cover():
    merged = trace.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert trace.covered_ns(merged, 2, 6) == 2
    assert trace.covered_ns(merged, 10, 20) == 0


def test_op_name():
    assert trace.op_name("%all_to_all.3 = f32[4,8]{1,0} all-to-all(f32[4,8] %x)") == "all_to_all.3"


def _hand_rows():
    host = "/host:CPU"
    return [
        (host, "python", trace.JOB_SPAN, 0, 100),
        (host, "python", trace.JOB_SPAN, 100, 100),
        (host, "main", "PJRT_LoadedExecutable_Execute", 0, 20),
        (host, "main", "device_get", 150, 50),
        # chip 0: a while op holding two body ops, then a gap, then one more op
        (DEV0, "XLA Ops", "%while = (s32[]) while(%t)", 20, 60),
        (DEV0, "XLA Ops", "%fusion.1 = f32[8] fusion(%a)", 20, 30),
        (DEV0, "XLA Ops", "%fusion.2 = f32[8] fusion(%b)", 50, 20),
        (DEV0, "XLA Ops", "%all_to_all.1 = f32[8] all-to-all(%c)", 120, 10),
        # chip 1: busier
        (DEV1, "XLA Ops", "%fusion.1 = f32[8] fusion(%a)", 10, 150),
        (DEV1, "XLA Ops", "%psum_invariant.2 = f32[] all-reduce(%d)", 160, 20),
    ]


def test_reduce_hand_rows():
    red = trace.reduce_events(_hand_rows(), [0, 1])
    assert red["window_s"] == 200e-9
    assert red["busy_s"] == pytest.approx((70e-9 + 170e-9) / 2)
    job0, job1 = red["jobs"]
    assert job0["busy_s"] == pytest.approx([60e-9, 90e-9])
    assert job1["busy_s"] == pytest.approx([10e-9, 80e-9])
    assert trace.mean_busiest(red["jobs"], "busy_s") == pytest.approx(85e-9)
    ops = dict(red["breakdown"]["device_ops"])
    # Self time: the while op's 60 ns less its body's 50 ns.
    assert ops == pytest.approx({"fusion.1": 30e-9, "fusion.2": 20e-9, "while": 10e-9,
                                 "all_to_all.1": 10e-9})
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["device_get", pytest.approx(70e-9)]
    assert [g[1] for g in gaps] == pytest.approx([70e-9, 40e-9, 20e-9])


def test_reduce_needs_job_spans():
    with pytest.raises(ValueError, match="no 'bench.job'"):
        trace.reduce_events([r for r in _hand_rows() if r[2] != trace.JOB_SPAN], [0])


def _obs(red, kind="engine"):
    peaks = run.Bench(REPO).peaks()["TPU v5 lite"]
    return dict(kind=kind, records=[], trace=red, spans={"build_s": 2.0}, lower_bytes=819e9 * 1e-9,
                peaks=peaks)


def test_readers_on_hand_rows():
    bench = run.Bench(REPO)
    obs = _obs(trace.reduce_events(_hand_rows(), [0, 1]))
    assert bench.reader("engine_device_s")(obs) == pytest.approx(85e-9)
    assert bench.reader("engine_hbm_share")(obs) == pytest.approx(100 / 85)
    assert bench.reader("device_idle.job")(obs) == pytest.approx(40.0)
    assert bench.reader("build_s")(obs) == 2.0
    for name in ("engine_device_s", "engine_hbm_share"):  # the PageRank cells' own names
        assert bench.reader(f"{name}.pr")(obs) == bench.reader(name)(obs)
    assert bench.reader("device_idle.pr")(obs) == pytest.approx(40.0)
    part = _obs(obs["trace"], kind="partition")
    assert bench.reader("partition_device_s")(part) == pytest.approx(85e-9)
    assert bench.reader("device_idle.partition")(part) == pytest.approx(40.0)
    assert bench.reader("engine_device_s")(part) is None


def test_reduce_recorded_trace():
    """Five BFS jobs of a scale-10 graph traced on one TPU v5e: the numbers
    this reduction gave when the trace was committed."""
    with gzip.open(RECORDED, "rt") as f:
        rows = [tuple(r) for r in json.load(f)]
    red = trace.reduce_events(rows, [0])
    assert len(red["jobs"]) == 5
    assert red["window_s"] == pytest.approx(0.051739279)
    assert red["busy_s"] == pytest.approx(0.026141884)
    busy = [j["busy_s"][0] for j in red["jobs"]]
    assert busy == pytest.approx([0.005475034, 0.005474991, 0.005064135, 0.005063954, 0.00506377])
    assert all(j["busy_s"][0] <= j["span_s"] for j in red["jobs"])
    ops = red["breakdown"]["device_ops"]
    assert [n for n, _ in ops[:2]] == ["fusion.45", "fusion.44"]
    assert ops[0][1] == pytest.approx(0.012276669)
    assert sum(t for _, t in ops) <= red["busy_s"]
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) == trace.TOP
    assert gaps[0] == ["tpu::System::TransferFromDevice=>IssueEvent", pytest.approx(0.002288929)]
    assert sum(t for _, t in gaps) <= red["window_s"] - red["busy_s"]
