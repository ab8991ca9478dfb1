"""End-to-end behaviour tests for the full system.

The dry-run and distributed-engine tests need >1 placeholder device, and
XLA locks the device count at first init — so those run in subprocesses
with their own XLA_FLAGS (exactly how launch/dryrun.py works).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=560
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_training_end_to_end_loss_drops():
    from repro.launch import train

    losses = train.main(["--preset", "tiny", "--steps", "40", "--log-every", "100"])
    assert np.mean(losses[-5:]) < losses[0] - 0.5


def test_serving_end_to_end():
    from repro.launch import serve

    out = serve.main(["--preset", "tiny", "--tokens", "8", "--batch", "2"])
    assert np.asarray(out).shape == (2, 8)


def test_distributed_bsp_matches_simulation():
    _run(
        """
import numpy as np, jax
from repro.core import ebg_partition
from repro.graph.generate import make_graph
from repro.graph.build import build_subgraphs
from repro.graph import algorithms as alg
from repro.graph.engine import CC, init_cc, make_distributed_stepper, subgraphs_to_arrays

g = make_graph("tiny_powerlaw")
res = ebg_partition(g, 8)
sub = build_subgraphs(g, res, symmetrize=True)
labels_sim, stats_sim = alg.connected_components(sub)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("workers",))
arrays, statics = subgraphs_to_arrays(sub)
stepper = make_distributed_stepper(mesh, "workers", CC, statics, num_supersteps=10, inner_cap=100)
with mesh:
    val, msgs, steps, msgs_steps, iters_steps = jax.jit(stepper)(arrays, init_cc(sub))
assert np.array_equal(labels_sim, np.asarray(val[:, :-1]))
# Convergence exit: the while_loop stops early and its per-step message
# series matches the simulation driver's (same superstep semantics).
steps = int(steps)
assert steps == stats_sim.supersteps < 10
assert np.array_equal(np.asarray(msgs_steps)[:steps], stats_sim.messages_per_step_worker)
assert np.array_equal(np.asarray(msgs), stats_sim.messages_per_worker)
assert np.array_equal(np.asarray(iters_steps)[:steps], stats_sim.inner_iters_per_step)
print("OK")
"""
    )


def test_dryrun_lowers_on_multidevice_mesh():
    """Reduced-config train_step lowers + compiles on an 8-device 2-axis mesh
    (same code path as the 512-chip production dry-run)."""
    _run(
        """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch.sharding import batch_shardings, opt_state_shardings, param_shardings
from repro.models.pspec import activation_axes
from repro.models.steps import make_train_step
from repro.models.transformer import init_params
from repro.optim.adam import AdamWConfig, init_opt_state

cfg = configs.reduced_config("phi3_5_moe")
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
p_shard = param_shardings(cfg, params_shape, mesh)
opt = AdamWConfig()
opt_shape = jax.eval_shape(lambda: init_opt_state(params_shape, opt))
o_shard = opt_state_shardings(p_shard, mesh)
batch = dict(tokens=jax.ShapeDtypeStruct((8, 32), jnp.int32),
             targets=jax.ShapeDtypeStruct((8, 32), jnp.int32))
b_shard = batch_shardings(batch, mesh)
step = make_train_step(cfg, opt)
with mesh, activation_axes(mesh, dp=("data",), tp="model"):
    lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                      out_shardings=(p_shard, o_shard, None)).lower(params_shape, opt_shape, batch)
    compiled = lowered.compile()
assert compiled.memory_analysis() is not None
cost = compiled.cost_analysis()
assert cost.get("flops", 0) > 0
print("OK")
"""
    )


def test_roofline_collective_parser():
    from repro.launch.roofline import parse_collectives

    hlo = """
  %ag = f32[32,1024,256]{2,1,0} all-gather(%x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = bf16[1000]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %aa = f32[8,128]{1,0} all-to-all(%z), replica_groups=[64,8]<=[512]
  %cp = f32[4,4]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    s = parse_collectives(hlo)
    assert s.per_op["all-gather"]["count"] == 1
    ag_bytes = 32 * 1024 * 256 * 4 * 15 / 16
    assert abs(s.per_op["all-gather"]["bytes"] - ag_bytes) < 1
    ar_bytes = 2 * 1000 * 2 * 3 / 4
    assert abs(s.per_op["all-reduce"]["bytes"] - ar_bytes) < 1
    assert s.per_op["all-to-all"]["count"] == 1
    assert s.total_link_bytes > 0


def test_dryrun_records_exist_and_complete():
    """The committed dry-run sweep must cover every runnable cell × mesh."""
    from repro import configs

    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run sweep not generated yet")
    missing = []
    for arch in configs.ARCHS:
        for shape in configs.runnable_shapes(arch):
            for mesh in ("sp", "mp"):
                f = d / f"{arch}__{shape}__{mesh}__baseline.json"
                if not f.exists():
                    missing.append(f.name)
    assert not missing, missing
    rec = json.loads((d / "llama3_2_3b__train_4k__sp__baseline.json").read_text())
    assert rec["flops_per_device"] > 0 and rec["bottleneck"] in ("compute", "memory", "collective")


def test_moe_ep_shard_map_matches_reference():
    """The §Perf `ep` plan (manual shard_map MoE dispatch) must be
    numerically identical to the GSPMD scatter path, gradients included."""
    _run(
        """
import numpy as np, jax, jax.numpy as jnp
from repro import configs
from repro.models import moe as MOE
from repro.models.pspec import activation_axes
from repro.models.transformer import init_params

cfg = configs.reduced_config("phi3_5_moe")
params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
p = jax.tree.map(lambda x: x[0], params["groups"]["layer_0"])["moe"]
rng = np.random.default_rng(0)
x = jnp.array(rng.standard_normal((4, 16, cfg.d_model)), jnp.float32)
y_ref = MOE.moe_ffn(cfg, p, x)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
with mesh, activation_axes(mesh, dp=("data",), tp="model", ep_shard_map=True):
    y_ep = jax.jit(lambda p, x: MOE.moe_ffn_ep(cfg, p, x))(p, x)
    g = jax.jit(jax.grad(lambda p, x: MOE.moe_ffn_ep(cfg, p, x).sum()))(p, x)
assert float(jnp.abs(y_ep - y_ref).max()) < 1e-4
assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))
print("OK")
"""
    )


def test_perf_plan_records_exist():
    """§Perf hillclimb artifacts: every logged plan has a JSON record."""
    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run sweep not generated yet")
    for f in [
        "kimi_k2__train_4k__sp__ep+cap1.json",
        "jamba_1_5_large__train_4k__sp__ep+vp+sp.json",
        "llama3_2_3b__decode_32k__sp__don+repl.json",
        "phi3_5_moe__train_4k__sp__ep.json",
    ]:
        assert (d / f).exists(), f
    base = json.loads((d / "kimi_k2__train_4k__sp__baseline.json").read_text())
    opt = json.loads((d / "kimi_k2__train_4k__sp__ep+cap1.json").read_text())
    assert opt["bound_s"] < base["bound_s"] / 10  # ≥10x hillclimb win locked in


def test_expert_placement_beats_random():
    from repro.core.placement import ebg_expert_placement, placement_report

    rng = np.random.default_rng(0)
    E, D, T = 64, 8, 50_000
    pop = 1.0 / (1 + np.arange(E)) ** 0.9
    pop /= pop.sum()
    pairs = rng.choice(E, size=(T, 2), p=pop)
    perm = ebg_expert_placement(pairs, E, D)
    rep = placement_report(pairs, perm, E, D)
    rand = placement_report(pairs, np.argsort(rng.random(E)), E, D)
    assert rep["load_max_mean"] < rand["load_max_mean"]
    # permutation sanity
    assert sorted(perm.tolist()) == list(range(E))
