"""The benchmark's on-device generators, run here on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from bench import generators

G500 = dict(name="graph500", edge_factor=16, a=0.57, b=0.19, c=0.19)


@pytest.mark.parametrize("spec", [dict(G500, scale=10), dict(G500, scale=12, edge_factor=8)])
@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 1])
def test_same_seed_same_graph(spec, seed):
    a, b = generators.generate(spec, seed), generators.generate(spec, seed)
    for key in ("src", "dst"):
        assert np.array_equal(a[key], b[key])
    c = generators.generate(spec, seed + 1)
    assert not np.array_equal(a["src"], c["src"]) or not np.array_equal(a["dst"], c["dst"])


def test_seeds_past_32_bits_differ():
    """jax.random.key keeps 32 bits; the high bits are folded in."""
    spec = dict(G500, scale=8)
    a = generators.generate(spec, 7)
    b = generators.generate(spec, 7 + 2**32)
    assert not np.array_equal(a["src"], b["src"])


def test_graph500_edge_count_and_skew():
    scale = 12
    g = generators.generate(dict(G500, scale=scale), 3)
    src, dst, n = g["src"], g["dst"], g["num_vertices"]
    drawn = 16 << scale
    assert n == 1 << scale
    # Dedup removes the repeats R-MAT draws at this skew, and nothing else.
    assert 0.6 * drawn < src.size <= drawn
    assert not np.any(src == dst)
    keys = src.astype(np.int64) * n + dst
    assert np.unique(keys).size == keys.size
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    assert deg.max() > 20 * deg.mean()  # hubs: a power-law tail
    assert np.count_nonzero(deg == 0) > 0.05 * n  # and many isolated vertices
    # The label permutation spreads hubs over the id space.
    assert np.argmax(deg) != 0


def test_unknown_generator_is_an_error():
    with pytest.raises(KeyError, match="unknown generator"):
        generators.generate({"name": "nope"}, 0)
