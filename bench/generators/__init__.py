"""Graphs made on the device from the run's seed, one generator a file.

`bench/generators/<name>.py` exposes `generate(seed, **params)`; a
configuration's `generator` entry names the file and gives the params. It
returns host arrays `src`, `dst` (int32), `weight` (float32 or None) and
`num_vertices`. `bench.run.Bench.load` finds the file under the checkout,
so a new generator is a new file. `seed_key` is shared by them.
"""
from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed: `jax.random.key` keeps only
    the low 32 bits, so the high bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
