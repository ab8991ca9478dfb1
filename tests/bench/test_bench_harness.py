"""The benchmark harness on the CPU at a tiny size: discovery by name, the
device checks, the result line, and each cell's run end to end."""
from __future__ import annotations

import hashlib
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
    assert callable(bench.load("generators", config["generator"]["name"]).generate)
    compare = bench.load("compares", traffic["compare"])
    assert callable(compare.check) and callable(compare.install_control)
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


GRID_GENERATOR = '''"""A weighted 2-D grid of `rows` x `cols` vertices: each lattice edge both
ways, with one integer weight in 1..1000 drawn from the seed on the device."""
import jax
import numpy as np

from bench.generators import seed_key


def generate(seed, *, rows, cols):
    ids = np.arange(rows * cols, dtype=np.int32).reshape(rows, cols)
    u = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    v = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    w = np.asarray(jax.random.randint(seed_key(seed), (u.size,), 1, 1001)).astype(np.float32)
    return dict(src=np.concatenate([u, v]), dst=np.concatenate([v, u]),
                weight=np.concatenate([w, w]), num_vertices=rows * cols)
'''

DISTANCES_COMPARE = '''"""SSSP distances, exact (integer weights keep float32 sums exact), against
scipy's Dijkstra. Control: Bellman-Ford in bfloat16."""
import functools

import numpy as np
from scipy.sparse import csgraph

from bench import control, jobs as jobs_mod, reference

UNREACHED = 3.0e38  # the engine's float32 "unreached"


def check(jobs, records, maps, seed):
    d = jobs.data
    covered = d.covered()
    adj = reference.csr(d.src, d.dst, d.num_vertices, d.weight)
    mismatches = 0
    for r in jobs_mod.sample(records, int(jobs.traffic.get("check_jobs", len(records))), seed):
        want = csgraph.dijkstra(adj, indices=r.root)[covered]
        got = jobs_mod.to_global(r.out, maps, d.num_vertices)[covered]
        mismatches += int(np.count_nonzero(np.where(got >= UNREACHED, np.inf, got) != want))
    return {"dist_mismatches": mismatches}


def bellman_ford(src, dst, weight, n, root, dtype):
    import jax
    import jax.numpy as jnp

    src, dst, weight = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(weight, dtype)
    dist = jnp.full((n,), jnp.inf, dtype).at[root].set(0)
    for _ in range(n):
        new = jnp.minimum(dist, jax.ops.segment_min(dist[src] + weight, dst, num_segments=n))
        if bool(jnp.all(new == dist)):
            break
        dist = new
    return np.asarray(dist.astype(jnp.float32), np.float64)


def install_control(jobs):
    import jax.numpy as jnp

    d = jobs.data
    answer = functools.lru_cache(lambda root: bellman_ford(
        d.src, d.dst, d.weight, d.num_vertices, root, jnp.bfloat16))
    control.answer_with(jobs, answer)
'''


def _add_cell(root, cell: str, *, config: dict, traffic: dict) -> run.Bench:
    """New files for a cell `<config>.<traffic>` and its entries in the
    temp root's `BENCHMARK.json`, with `job_s` reported there."""
    config_name, traffic_name = cell.split(".", 1)
    (root / f"bench/configs/{config_name}.json").write_text(json.dumps(config))
    (root / f"bench/traffic/{traffic_name}.json").write_text(json.dumps(traffic))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    if config_name not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append(dict(spec["configs"][0], name=config_name,
                                    file=f"bench/configs/{config_name}.json"))
    spec["workloads"].append(dict(name=cell, config=config_name, traffic=traffic_name,
                                  chips=1, why="test"))
    for m in spec["end_to_end"]:
        if m["name"] == "job_s":
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return run.Bench(root)


def test_added_generator_and_compare_are_found_and_run(tmp_path):
    """A weighted SSSP deployment is new files only: a generator, a compare
    with its control, a configuration and a traffic with listed roots."""
    from bench import control

    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    (root / "bench/generators/grid.py").write_text(GRID_GENERATOR)
    (root / "bench/compares/distances.py").write_text(DISTANCES_COMPARE)
    g500 = json.loads((root / "bench/configs/g500-s20.json").read_text())
    config = dict(g500, generator=dict(name="grid", rows=16, cols=16), parts=4)
    traffic = dict(kind="engine", program="sssp", symmetrize=False, weighted=True,
                   run={"max_supersteps": 100000}, roots=[0, 137, 255], check_jobs=3,
                   compare="distances", limits={"dist_mismatches": 0})
    bench = _add_cell(root, "grid16.sssp", config=config, traffic=traffic)
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited

    res = run.run_cell(bench, "grid16.sssp", 2**33 + 5, 0.3, False, CPU_DEVICE)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "job_s"}
    res = run.run_cell(bench, "grid16.sssp", 2**33 + 5, 0.3, False, CPU_DEVICE, hook=control.install)
    assert not res["correct"], res["checks"]
    assert res["checks"]["dist_mismatches"]["value"] > 0


LISTED_SEED = 2**31 + 777


def _listed_bfs_root(tmp_path, roots) -> run.Bench:
    root = make_root(tmp_path)
    config = json.loads((root / "bench/configs/g500-s20.json").read_text())
    traffic = json.loads((root / "bench/traffic/bfs.json").read_text())
    return _add_cell(root, "g500-s20.bfs-listed", config=config, traffic=dict(traffic, roots=roots))


def _tiny_degrees(bench: run.Bench) -> np.ndarray:
    params = dict(bench.config("g500-s20")["generator"])
    g = bench.load("generators", params.pop("name")).generate(LISTED_SEED, **params)
    n = g["num_vertices"]
    return np.bincount(g["src"], minlength=n) + np.bincount(g["dst"], minlength=n)


def test_listed_roots_are_taken_in_order_and_cycled(tmp_path):
    deg = _tiny_degrees(run.Bench(make_root(tmp_path / "probe")))
    covered = np.flatnonzero(deg)
    listed = [int(covered[-1]), int(covered[3]), int(covered[len(covered) // 2])]
    bench = _listed_bfs_root(tmp_path / "root", listed)
    seen = []

    def record_roots(jobs):
        assert jobs.roots.tolist() == listed
        program_run = jobs.run

        def run_and_record(i):
            out = program_run(i)
            seen.append((i, out[2]))
            return out

        jobs.run = run_and_record

    res = run.run_cell(bench, "g500-s20.bfs-listed", LISTED_SEED, 0.5, False, CPU_DEVICE,
                       hook=record_roots)
    assert res["correct"], res["checks"]
    assert seen[0] == (-1, listed[-1])  # the warm-up
    assert [i for i, _ in seen[1:]] == list(range(len(seen) - 1))
    assert [r for _, r in seen[1:]] == [listed[i % 3] for i in range(len(seen) - 1)]
    assert len(seen) - 1 > 3, "too few jobs in the window to see the list cycle"


@pytest.mark.parametrize("bad", ["out_of_range", "negative", "zero_degree", "not_an_int"])
def test_listed_root_is_refused_at_set_up(tmp_path, bad):
    deg = _tiny_degrees(run.Bench(make_root(tmp_path / "probe")))
    root = {"out_of_range": deg.size, "negative": -1, "not_an_int": 1.5,
            "zero_degree": int(np.flatnonzero(deg == 0)[0])}[bad]
    bench = _listed_bfs_root(tmp_path / "root", [int(np.flatnonzero(deg)[0]), root])
    match = "has degree 0" if bad == "zero_degree" else "is not a vertex id"
    with pytest.raises(ValueError, match=rf"root {root!r} {match}"):
        run.run_cell(bench, "g500-s20.bfs-listed", LISTED_SEED, 0.1, False, CPU_DEVICE)


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


# The first roots and the sha256 of all 64 (int64 bytes) that seed 99 drew
# at the tiny size before generators and compares moved into files.
ROOTS_99_HEAD = [166, 717, 938, 626, 296, 1001, 936, 330]
ROOTS_99_SHA = "b5f2c2426626e7f6bb0488a87b08b04cb2457cdae0b4813c47054e419eb05a69"


def test_same_seed_same_roots_of_nonzero_degree(tiny):
    """Graph500's search keys: drawn from the seed among all vertices of
    nonzero degree, not only the hubs, as they were drawn before."""
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
    assert a.tolist()[:8] == ROOTS_99_HEAD
    assert hashlib.sha256(np.asarray(a, np.int64).tobytes()).hexdigest() == ROOTS_99_SHA


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
    for entry in SPEC["configs"]:
        generator = json.loads((REPO / entry["file"]).read_text())["generator"]["name"]
        assert (REPO / "bench" / "generators" / f"{generator}.py").is_file()
    for cell in SPEC["workloads"]:
        traffic = REPO / "bench" / "traffic" / f"{cell['traffic']}.json"
        assert traffic.is_file()
        compare = json.loads(traffic.read_text())["compare"]
        assert (REPO / "bench" / "compares" / f"{compare}.py").is_file()
    for m in SPEC["per_layer"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
