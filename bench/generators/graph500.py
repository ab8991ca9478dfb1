"""Graph500 Kernel-1 R-MAT (one uniform draw per edge and level picks the
quadrant), Graph500's random vertex-label permutation and random edge
order, then self-loops and duplicate edges dropped, as the program's
`Graph` requires.

It is one jitted program whose shapes depend only on the configuration, so
every seed shares one compiled program. The host only compacts the kept
rows with a boolean index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import seed_key


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a", "b", "c"))
def _graph500_device(key, *, scale: int, edge_factor: int, a: float, b: float, c: float):
    n = edge_factor << scale
    k_levels, k_perm, k_order = jax.random.split(key, 3)
    ab, abc = a + b, a + b + c

    def level(carry, k):
        src, dst = carry
        r = jax.random.uniform(k, (n,))
        src = src * 2 + (r >= ab)
        dst = dst * 2 + ((r >= a) & (r < ab)) + (r >= abc)
        return (src, dst), None

    zero = jnp.zeros((n,), jnp.int32)
    (src, dst), _ = jax.lax.scan(level, (zero, zero), jax.random.split(k_levels, scale))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    dup = jnp.concatenate([jnp.zeros((1,), bool), (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])])
    drop = (dup | (src == dst)).astype(jnp.int32)
    # Kept edges first, in a random order (Graph500 permutes the edge list).
    order = jax.random.bits(k_order, (n,), jnp.uint32)
    _, _, src, dst = jax.lax.sort((drop, order, src, dst), num_keys=2)
    return src, dst, n - drop.sum()


def generate(seed: int, *, scale: int, edge_factor: int, a: float, b: float, c: float) -> dict:
    """A Graph500 R-MAT graph as host arrays (see `bench.generators`)."""
    src, dst, kept = _graph500_device(
        seed_key(seed), scale=scale, edge_factor=edge_factor, a=a, b=b, c=c
    )
    kept = int(kept)
    return dict(src=np.asarray(src[:kept]), dst=np.asarray(dst[:kept]), weight=None,
                num_vertices=1 << scale)
