"""The benchmark harness on the CPU at a tiny size: discovery by name, the
device checks, the result line, and each cell's run end to end."""
from __future__ import annotations

import json
import types

import numpy as np
import pytest

from bench import run
from bench_tiny import CPU_DEVICE, REPO, make_root

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return run.Bench(make_root(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    bench = run.Bench(REPO)
    entry = bench.cell(cell)
    config = bench.config(entry["config"])
    traffic = bench.traffic(entry["traffic"])
    assert config["chips"] == entry["chips"]
    assert traffic["kind"] in ("engine", "partition")
    assert set(traffic["limits"]) and all(v >= 0 for v in traffic["limits"].values())
    for m in bench.metrics(cell, trace=True):
        assert callable(bench.reader(m["name"]))
    assert {m["name"] for m in bench.metrics(cell, trace=False)} >= {"setup_s"}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_is_silent_off_its_kind(metric):
    """A reader that finds nothing to read returns None, never 0."""
    read = run.Bench(REPO).reader(metric)
    obs = dict(kind="neither", records=[], trace={"jobs": [], "busy_s": 0.0, "window_s": 1.0},
               spans={}, lower_bytes=None, peaks={})
    assert read(obs) is None


def test_added_files_are_found_and_run(tmp_path):
    """A new configuration, traffic mix and metric are files only."""
    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/g500-s20.json").read_text())
    cfg["generator"]["edge_factor"] = 8
    (root / "bench/configs/g500-ef8.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/bfs.json").read_text())
    traffic["check_jobs"] = 1
    (root / "bench/traffic/bfs-one.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/generate_s.py").write_text(
        "def read(obs):\n    return obs['spans'].get('generate_s')\n")
    spec["configs"].append(dict(spec["configs"][0], name="g500-ef8",
                                file="bench/configs/g500-ef8.json"))
    spec["workloads"].append(dict(name="g500-ef8.bfs-one", config="g500-ef8",
                                  traffic="bfs-one", chips=1, why="test"))
    spec["per_layer"].append(dict(name="generate_s", unit="s", better="lower", source="host_clock",
                                  layer="set-up", moves="setup_s",
                                  workloads=["g500-ef8.bfs-one"]))
    for m in spec["end_to_end"]:
        if m["name"] == "job_s":
            m["workloads"].append("g500-ef8.bfs-one")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = run.Bench(root)
    assert [m["name"] for m in bench.metrics("g500-ef8.bfs-one", trace=True)] == ["generate_s"]
    assert bench.reader("generate_s")(dict(spans={"generate_s": 1.5})) == 1.5
    res = run.run_cell(bench, "g500-ef8.bfs-one", 11, 0.2, False, CPU_DEVICE)
    assert res["correct"] and set(res["metrics"]) == {"setup_s", "job_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(tiny, cell):
    res = run.run_cell(tiny, cell, 2**31 + 12345, 0.3, False, CPU_DEVICE)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = {m["name"] for m in tiny.metrics(cell, trace=False)}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_result_line_schema(tiny, capsys):
    res = run.run_cell(tiny, "g500-s20.bfs", 3, 0.2, False, CPU_DEVICE)
    run.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert err.strip().splitlines()[-1].startswith("check hops_mismatches: 0 (limit 0)")


def test_same_seed_same_roots_of_nonzero_degree(tiny):
    """Graph500's search keys: drawn from the seed among all vertices of
    nonzero degree, not only the hubs."""
    seen = []

    def keep(jobs):
        seen.append((jobs.roots, jobs.data.degrees()))

    for seed in (99, 99, 2**33 + 99):
        assert run.run_cell(tiny, "g500-s20.bfs", seed, 0.1, False, CPU_DEVICE, hook=keep)["correct"]
    (a, deg), (b, _), (c, _) = seen
    assert a.size == 64 and np.unique(a).size == 64
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(deg[a] > 0)
    assert np.median(deg[a]) < np.sort(deg)[-64]  # not the top-degree vertices


def _fake_devices(monkeypatch, kind: str, count: int):
    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


def test_refuses_a_device_that_is_not_a_tpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.check_device(1, run.Bench(REPO).peaks())


def test_refuses_a_device_kind_missing_from_the_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v9 imaginary", 1)
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        run.check_device(1, run.Bench(REPO).peaks())


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", 1)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        run.check_device(4, run.Bench(REPO).peaks())
    _fake_devices(monkeypatch, "TPU v5 lite", 4)
    assert run.check_device(4, run.Bench(REPO).peaks()) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_peaks_name_their_source():
    peaks = run.Bench(REPO).peaks()
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops"] == 197e12
    assert all("source" in p and p["source"] for p in peaks.values())


def test_benchmark_json_names_only_files_under_its_paths():
    for entry in SPEC["configs"]:
        assert entry["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert (REPO / entry["file"]).is_file()
    for cell in SPEC["workloads"]:
        assert (REPO / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
