"""The benchmark: one cell, one seed, one measured window.

    python3 -m bench.run --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name under the checkout: the cell in
`BENCHMARK.json`, its configuration in the file that entry names, the
configuration's graph generator in `bench/generators/<name>.py`, its
traffic mix in `bench/traffic/<traffic>.json`, the mix's answer comparison
and its control in `bench/compares/<compare>.py`, each per-layer metric's
reader in `bench/metrics/<metric>.py`, the device's peaks in
`bench/peaks.json`. Adding a cell, a configuration, a generator, a mix, a
comparison or a metric adds files only. An end-to-end metric named
`<quantity>.<suffix>` (`job_s.pr`) reports `<quantity>` in the cells it
lists, under a bound of its own.

A run: check the device (a TPU, as many chips as the cell asks for, a kind
listed in the peaks), make the graph on the device from the seed, set up
the jobs (partition and build for engine mixes) and warm them up with one
job: that is `setup_s`, from process start. Then jobs run back to back;
the window closes at the first completion after `--seconds`. With
`--trace 1` the profiler records the window and the per-layer metrics are
read from it. After the window: the device's peak memory, then the
program's state is freed and the mix's comparison checks the window's
answers against the plain references in `bench.reference`. Each compared
number and its limit go to standard error as the last lines, and into the
result, which is the last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse
import contextlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


class Bench:
    """`BENCHMARK.json` and the files it names, found by name under `root`."""

    def __init__(self, root=ROOT):
        self.root = pathlib.Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules = {}

    def _entry(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def peaks(self) -> dict:
        return json.loads((self.root / "bench" / "peaks.json").read_text())

    def metrics(self, cell: str, trace: bool) -> list:
        """The cell's end-to-end metrics, or with `trace` its per-layer ones."""
        entries = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in entries if cell in m.get("workloads", [cell])]

    def load(self, kind: str, name: str):
        """The module `bench/<kind>/<name>.py` under `root`, loaded once per
        `Bench`: a per-layer metric's reader (`metrics`), a graph generator
        (`generators`), or an answer comparison with its control
        (`compares`). An unknown name is a KeyError listing the known ones."""
        path = self.root / "bench" / kind / f"{name}.py"
        if path not in self._modules:
            if not path.is_file():
                known = sorted(p.stem for p in path.parent.glob("*.py") if not p.name.startswith("_"))
                raise KeyError(f"unknown {kind[:-1]} {name!r}; known: {known}")
            spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]

    def reader(self, metric: str):
        """The `read(obs)` function of `bench/metrics/<metric>.py`."""
        return self.load("metrics", metric).read


def check_device(chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; exits unless it is a TPU with at least
    `chips` chips of a kind listed in the peaks."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found platform {dev.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    if dev.device_kind not in peaks:
        raise SystemExit(
            f"bench: device kind {dev.device_kind!r} is not in bench/peaks.json ({sorted(peaks)})"
        )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


class CompileCounter:
    """Counts the programs this process compiles and those it loads from the
    persistent cache. JAX times both under one event, so the loads are
    counted apart and taken off. It offers no way to drop a listener, so
    one pair is registered per process and every counter reads it."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    _totals = None  # [compiled or loaded, loaded]

    def __init__(self):
        import jax

        if CompileCounter._totals is None:
            CompileCounter._totals = [0, 0]
            jax.monitoring.register_event_duration_secs_listener(CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
        self._start = list(CompileCounter._totals)

    @staticmethod
    def _on_duration(event: str, duration: float, **kwargs) -> None:
        if event == CompileCounter.EVENT:
            CompileCounter._totals[0] += 1

    @staticmethod
    def _on_event(event: str, **kwargs) -> None:
        if event == CompileCounter.HIT:
            CompileCounter._totals[1] += 1

    def counts(self) -> tuple[int, int]:
        """(compiled, loaded from the cache) since this counter was made."""
        built, loaded = (t - s for t, s in zip(CompileCounter._totals, self._start))
        return built - loaded, loaded


def use_compile_cache() -> None:
    """The program's persistent cache (a fixed directory in the checkout, or
    `JAX_COMPILATION_CACHE_DIR`), holding every program however fast it
    compiles, so that only a cell's first run in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(bench: Bench, workload: str, seed: int, seconds: float, trace: bool,
             device: dict, hook=None) -> dict:
    """One run of a cell on `device` (already checked); returns the result.
    `hook(jobs)`, where given, may replace the timed path before the
    warm-up: controls and planted faults use it."""
    import jax

    from bench import jobs as jobs_mod, trace as trace_mod

    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if config["chips"] != cell["chips"]:
        raise SystemExit(f"bench: {workload} asks for {cell['chips']} chips, "
                         f"its configuration for {config['chips']}")
    params = dict(config["generator"])
    generator = bench.load("generators", params.pop("name"))
    compare = bench.load("compares", traffic["compare"])
    compiles = CompileCounter()
    t = time.perf_counter()
    data = generator.generate(seed, **params)
    generate_s = time.perf_counter() - t
    jobs = jobs_mod.make_jobs(data, config, traffic, seed, compare)
    jobs.spans["generate_s"] = generate_s
    if hook is not None:
        hook(jobs)
    jobs.run(-1)  # warm-up: compiles, or loads from the cache, every program
    setup_s = time.perf_counter() - T_START
    spans = ", ".join(f"{k} {v:.3f} s" for k, v in jobs.spans.items())
    print(f"bench: set-up {setup_s:.3f} s ({spans}), {len(data['src'])} edges, "
          "%d compilations, %d loads from the cache" % compiles.counts(), file=sys.stderr, flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles = CompileCounter()
    records = []
    annotate = jax.profiler.TraceAnnotation if trace else (lambda name: contextlib.nullcontext())
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only; no per-call Python events
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_open = time.perf_counter_ns()
    deadline = t_open + int(seconds * 1e9)
    while True:
        with annotate(trace_mod.JOB_SPAN):
            t0 = time.perf_counter_ns()
            out, stats, root = jobs.run(len(records))
            t1 = time.perf_counter_ns()
        records.append(jobs_mod.Record(t0, t1, out, stats, root))
        if t1 >= deadline:
            break
    window_s = (t1 - t_open) / 1e9
    if trace:
        jax.profiler.stop_trace()
    per_job = ", ".join(
        f"{(r.t1_ns - r.t0_ns) / 1e9:.3f} s" + (f" / {r.stats.supersteps} supersteps" if r.stats else "")
        for r in records[:8]) + (", ..." if len(records) > 8 else "")
    print(f"bench: window {window_s:.3f} s, {len(records)} jobs ({per_job}), inside the window "
          "%d compilations, %d loads from the cache" % compiles.counts(), file=sys.stderr, flush=True)

    used = jax.devices()[: cell["chips"]]
    device = dict(device, memory_peak_bytes=max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used))
    result = {"metrics": {}, "device": device}
    if trace:
        reduced = trace_mod.reduce_dir(trace_dir, [d.id for d in used])
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs = dict(kind=jobs.kind, records=records, trace=reduced, spans=jobs.spans,
                   lower_bytes=jobs.lower_bytes, peaks=bench.peaks()[device["kind"]])
        for m in bench.metrics(workload, trace=True):
            value = bench.reader(m["name"])(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    else:
        edges = len(data["src"])
        e2e = {"setup_s": setup_s, "job_s": window_s / len(records),
               "partition_eps": edges * len(records) / window_s}
        for m in bench.metrics(workload, trace=False):
            value = e2e[m["name"].split(".", 1)[0]]
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    t = time.perf_counter()
    maps = jobs.close()
    checks = jobs.check(records, maps, seed)
    print(f"bench: check {time.perf_counter() - t:.3f} s", file=sys.stderr, flush=True)
    limits = traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result.update(correct=correct, attempted=len(records), failed=0 if correct else len(records))
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    bench = Bench()
    cell = bench.cell(args.workload)
    use_compile_cache()
    device = check_device(cell["chips"], bench.peaks())
    print(f"bench: device {json.dumps(device)}", flush=True)
    emit(run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), device))
    return 0


def emit(result: dict) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
