"""The benchmark's on-device generators, found by name and run here on the CPU."""
from __future__ import annotations

import hashlib

import numpy as np
import pytest

from bench import run
from bench_tiny import REPO

G500 = dict(edge_factor=16, a=0.57, b=0.19, c=0.19)


@pytest.fixture(scope="module")
def graph500():
    return run.Bench(REPO).load("generators", "graph500").generate


@pytest.mark.parametrize("params", [dict(G500, scale=10), dict(G500, scale=12, edge_factor=8)])
@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 1])
def test_same_seed_same_graph(graph500, params, seed):
    a, b = graph500(seed, **params), graph500(seed, **params)
    for key in ("src", "dst"):
        assert np.array_equal(a[key], b[key])
    c = graph500(seed + 1, **params)
    assert not np.array_equal(a["src"], c["src"]) or not np.array_equal(a["dst"], c["dst"])


# sha256 of src and dst (int32 bytes, src first) and the kept count, taken
# from the generator before it moved into its own file.
PINNED = {
    5: (12060, "aadc704179848e1c604935a420829350b38dc6e0fb4c14980e684f9ae26e66fe"),
    2**32 + 77: (11999, "323f339a8fd402e4550070bfd2d3ba56d1975551cc35607dd0d75de3664579a0"),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_graph500_is_pinned(graph500, seed):
    g = graph500(seed, scale=10, **G500)
    assert g["src"].dtype == g["dst"].dtype == np.int32
    digest = hashlib.sha256(g["src"].tobytes() + g["dst"].tobytes()).hexdigest()
    assert (g["src"].size, digest) == PINNED[seed]


def test_seeds_past_32_bits_differ(graph500):
    """jax.random.key keeps 32 bits; the high bits are folded in."""
    a = graph500(7, scale=8, **G500)
    b = graph500(7 + 2**32, scale=8, **G500)
    assert not np.array_equal(a["src"], b["src"])


def test_graph500_edge_count_and_skew(graph500):
    scale = 12
    g = graph500(3, scale=scale, **G500)
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
    with pytest.raises(KeyError, match=r"unknown generator 'nope'; known: \['graph500'\]"):
        run.Bench(REPO).load("generators", "nope")


@pytest.mark.parametrize("kind, known", [("metrics", "build_s"), ("compares", "hops")])
def test_unknown_name_lists_the_known(kind, known):
    with pytest.raises(KeyError, match=rf"unknown {kind[:-1]} 'nope'; known: \[.*'{known}'"):
        run.Bench(REPO).load(kind, "nope")


def test_a_file_is_loaded_once_per_bench():
    bench = run.Bench(REPO)
    assert bench.load("generators", "graph500") is bench.load("generators", "graph500")
    assert bench.reader("build_s") is bench.reader("build_s")
