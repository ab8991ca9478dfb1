"""Substrate tests: checkpoint/restart fault tolerance, data determinism,
optimizer behaviour, elastic resharding, EBG expert placement."""
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt as CKPT
from repro.data.pipeline import DataConfig, batch_at_step, shard_batch_at_step
from repro.optim.adam import AdamWConfig, apply_updates, init_opt_state


def test_checkpoint_roundtrip(tmp_path):
    tree = dict(a=jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                b=dict(c=jnp.ones((5,), jnp.bfloat16), step=jnp.int32(7)))
    CKPT.save(tmp_path, 3, tree)
    assert CKPT.latest_step(tmp_path) == 3
    got = CKPT.restore(tmp_path, 3, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restart_bitwise_identical(tmp_path):
    """Kill-and-restart: a resumed run reproduces the uninterrupted run."""
    from repro.launch import train as T

    # uninterrupted 30 steps
    losses_full = T.main(["--preset", "tiny", "--steps", "30", "--log-every", "100"])
    # interrupted at 15 + resumed
    ck = str(tmp_path / "ck")
    T.main(["--preset", "tiny", "--steps", "15", "--ckpt-dir", ck, "--ckpt-every", "15",
            "--log-every", "100"])
    losses_resumed = T.main(["--preset", "tiny", "--steps", "30", "--ckpt-dir", ck,
                             "--resume", "--log-every", "100"])
    np.testing.assert_allclose(losses_resumed[-15:], losses_full[-15:], rtol=1e-5)


def test_partial_checkpoint_ignored(tmp_path):
    """A dir without manifest.json (killed mid-write) must be invisible."""
    (tmp_path / "step_00000009").mkdir(parents=True)
    assert CKPT.latest_step(tmp_path) is None
    CKPT.save(tmp_path, 5, dict(x=jnp.ones(3)))
    assert CKPT.latest_step(tmp_path) == 5


def test_data_pipeline_determinism_and_sharding():
    cfg = DataConfig(vocab=1000, seq_len=16, global_batch=8)
    a = batch_at_step(cfg, 3)
    b = batch_at_step(cfg, 3)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = batch_at_step(cfg, 4)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))
    # shards are disjoint slices of the same deterministic stream
    s0 = shard_batch_at_step(cfg, 3, 0, 2)
    s1 = shard_batch_at_step(cfg, 3, 1, 2)
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(np.asarray(s0["tokens"]), np.asarray(s1["tokens"]))


def test_adamw_converges_quadratic():
    opt = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1, total_steps=300)
    params = dict(w=jnp.array([5.0, -3.0]))
    state = init_opt_state(params, opt)
    for _ in range(150):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, m = apply_updates(params, grads, state, opt)
    assert float(jnp.abs(params["w"]).max()) < 0.3
    assert float(m["grad_norm"]) >= 0


def test_adamw_bf16_state_and_compression():
    opt = AdamWConfig(state_dtype=jnp.bfloat16, compress_grads="bf16",
                      warmup_steps=1, total_steps=10)
    params = dict(w=jnp.ones((4, 4)))
    state = init_opt_state(params, opt)
    assert state["mu"]["w"].dtype == jnp.bfloat16
    grads = dict(w=jnp.full((4, 4), 0.5))
    params2, state2, _ = apply_updates(params, grads, state, opt)
    assert np.isfinite(np.asarray(params2["w"])).all()


def test_elastic_reshard_devices():
    """Gather a sharded tree and re-put to a different layout (1 device CPU
    degenerates to identity but exercises the full code path)."""
    from repro.launch.elastic import reshard
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((1,), ("data",))
    tree = dict(w=jnp.ones((8, 8)))
    sh = dict(w=NamedSharding(mesh, P("data", None)))
    out = reshard(tree, sh)
    assert out["w"].sharding == sh["w"]


def test_multihost_shard_equivalence():
    """Concatenated host shards == the global batch (elastic data path)."""
    cfg = DataConfig(vocab=100, seq_len=8, global_batch=8)
    full = [shard_batch_at_step(cfg, 0, i, 4)["tokens"] for i in range(4)]
    assert sum(x.shape[0] for x in full) == 8
