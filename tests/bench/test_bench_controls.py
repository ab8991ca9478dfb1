"""`correct` must come out false for each cell's control and for each fault
its timed path can have, with the rest of a run driven as the benchmark
drives it (the device check skipped, tiny sizes, the CPU)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run
from bench_tiny import CPU_DEVICE, REPO, make_root

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
ENGINE_CELLS = [c for c in CELLS if not c.endswith(".partition")]
SEED = 2**31 + 4242


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return run.Bench(make_root(tmp_path_factory.mktemp("tiny")))


def _run(bench, cell, hook=None):
    return run.run_cell(bench, cell, SEED, 0.2, False, CPU_DEVICE, hook=hook)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny, cell):
    assert _run(tiny, cell)["correct"]
    res = _run(tiny, cell, control.install)
    assert not res["correct"], res["checks"]


def _alter_answer(jobs):
    """A wrong answer where it is produced: one value of every job's output."""
    produce = jobs.run

    def run_altered(i):
        out, stats, root = produce(i)
        out = np.array(out)
        if jobs.kind == "partition":
            k = out.size // 2
            out[k] = (out[k] + 1) % jobs.config["parts"]
        else:
            maps = jobs.pipe.subgraphs_for(symmetrize=jobs.symmetrize)
            slot = np.argwhere(np.asarray(maps.is_master) & (np.asarray(maps.gid) >= 0))[0]
            out[tuple(slot)] = out[tuple(slot)] * 1.5 + 1
        return out, stats, root

    jobs.run = run_altered


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(tiny, cell):
    res = _run(tiny, cell, _alter_answer)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ENGINE_CELLS)
def test_superstep_returning_its_state_is_not_correct(tiny, cell, monkeypatch):
    from repro.graph import engine

    def unchanged(prog, sub, val, *args, **kwargs):
        p = val.shape[0]
        return val, jnp.zeros((p,), jnp.int32), jnp.zeros((p,), jnp.int32), jnp.float32(0.0)

    jax.clear_caches()
    monkeypatch.setattr(engine, "_superstep", unchanged)
    try:
        res = _run(tiny, cell)
    finally:
        jax.clear_caches()
    assert not res["correct"], res["checks"]


DIST_SCRIPT = r"""
import json, pathlib, sys, tempfile
import jax, jax.numpy as jnp
sys.path[:0] = ["src", "tests/bench"]
from bench import run
from bench_tiny import CPU_DEVICE, make_root
bench = run.Bench(make_root(pathlib.Path(tempfile.mkdtemp()), dist=True))
device = dict(CPU_DEVICE, count=len(jax.devices()))
sound = run.run_cell(bench, "g500-s20-x4.pr", 77, 0.2, False, device)
# The exchange between chips left out: every device gets its own sends back.
jax.lax.all_to_all = lambda x, *a, **k: jnp.swapaxes(x, 0, 1)
jax.clear_caches()
fault = run.run_cell(bench, "g500-s20-x4.pr", 77, 0.2, False, device)
print(json.dumps([sound["correct"], fault["correct"], sound["checks"], fault["checks"]]))
"""


def test_exchange_left_out_is_not_correct_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", DIST_SCRIPT], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, fault, sound_checks, fault_checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert sound, sound_checks
    assert not fault, fault_checks
