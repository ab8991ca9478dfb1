"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode accepts kernels that Mosaic refuses (vector gathers from
VMEM, unaligned DMA slices, VMEM that outgrows the chip), so these tests
ask the TPU compiler itself, for a v5e that is described, not attached.
They compile only — nothing runs — at the per-worker widths of
`chip_smoke.py` (a scale-22 Graph500 R-MAT on 8 workers), and record the
largest widths that still fit VMEM (docs/api.md "VMEM budget").

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bsp_superstep import bsp_superstep_pallas
from repro.kernels.ebg_commit import ebg_commit_block_pallas, tiles_shape

PARTS = 8
SMOKE_NUM_OUT = 655_361  # per-worker value width at scale 22 (max_v + dump slot)
SMOKE_VERTICES = 1 << 22
EDGES = 1 << 20  # edge streams live in HBM: their length does not touch VMEM
BLOCK = 1024
# Largest widths the compiler accepts (docs/api.md "VMEM budget").
MAX_NUM_OUT = 16_711_680
MAX_BITSET_VERTICES = 133_169_152  # p = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_bsp(one_chip, combine, num_out, edges=EDGES):
    streams = [_sds(one_chip, (PARTS, edges), dt) for dt in (jnp.int32, jnp.int32, jnp.float32)]
    values = [_sds(one_chip, (PARTS, num_out), jnp.float32)] * (2 if combine == "sum" else 1)
    fn = jax.jit(lambda *a: bsp_superstep_pallas(
        *a, num_out=num_out, combine=combine, inner_cap=64, interpret=False))
    return fn.lower(*streams, *values).compile()


def _compile_ebg(one_chip, num_vertices, window, weighted=False, nblk=4):
    vw = (num_vertices + 31) // 32
    blocks = [_sds(one_chip, (nblk, BLOCK), dt)
              for dt in (jnp.int32, jnp.int32, jnp.bool_, jnp.float32, jnp.float32)]
    fn = jax.jit(lambda *a: ebg_commit_block_pallas(
        *a, balance="range" if weighted else "static", weighted=weighted, window=window,
        interpret=False))
    return fn.lower(
        _sds(one_chip, (PARTS, vw), jnp.uint32), _sds(one_chip, (PARTS,), jnp.float32),
        _sds(one_chip, (PARTS,), jnp.float32), *blocks, _sds(one_chip, (5,), jnp.float32),
    ).compile()


def _compiles(build) -> bool:
    try:
        build()
    except Exception:  # the compiler's refusal (VMEM) — the fact being measured
        return False
    return True


def _largest(build, lo, hi, unit):
    """Largest size in [lo, hi) that compiles, to within `unit`."""
    assert _compiles(lambda: build(lo)) and not _compiles(lambda: build(hi))
    while hi - lo > unit:
        mid = (lo + hi) // 2 // unit * unit
        lo, hi = (mid, hi) if _compiles(lambda: build(mid)) else (lo, mid)
    return lo


@pytest.mark.parametrize("combine", ["min", "sum"])
def test_bsp_superstep_compiles_at_smoke_width(one_chip, combine):
    compiled = _compile_bsp(one_chip, combine, SMOKE_NUM_OUT)
    assert "tpu_custom_call" in compiled.as_text()


def test_bsp_superstep_vmapped_compiles(one_chip):
    """The serving tier vmaps the kernel over queries; the batch folds into
    the worker grid instead of batching HBM refs."""
    streams = [_sds(one_chip, (PARTS, EDGES), dt) for dt in (jnp.int32, jnp.int32, jnp.float32)]
    fn = jax.vmap(lambda a, b, c, d: bsp_superstep_pallas(
        a, b, c, d, num_out=SMOKE_NUM_OUT, inner_cap=64, interpret=False),
        in_axes=(None, None, None, 0))
    compiled = jax.jit(fn).lower(
        *streams, _sds(one_chip, (8, PARTS, SMOKE_NUM_OUT), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("window", [False, True], ids=["frozen", "window"])
@pytest.mark.parametrize("weighted", [False, True], ids=["ebv", "hdrf"])
def test_ebg_commit_compiles_at_smoke_width(one_chip, window, weighted):
    compiled = _compile_ebg(one_chip, SMOKE_VERTICES, window, weighted)
    assert "tpu_custom_call" in compiled.as_text()


def test_ebg_commit_tiles_one_block_compiles(one_chip):
    """The out-of-core driver's launch: one [B] block on a bitset kept in
    the kernel's tiled layout, with no layout conversion around it."""
    vw = (SMOKE_VERTICES + 31) // 32
    stream = [_sds(one_chip, (4096,), dt) for dt in (jnp.int32, jnp.int32, jnp.bool_)]
    fn = jax.jit(lambda t, e, v, *s: ops.ebg_commit_tiles(
        t, e, v, *s, num_parts=PARTS, alpha=1.0, beta=1.0, inv_e=1e-6, inv_v=1e-6,
        window=True, interpret=False))
    compiled = fn.lower(
        _sds(one_chip, tiles_shape(PARTS, vw), jnp.int32), _sds(one_chip, (PARTS,), jnp.float32),
        _sds(one_chip, (PARTS,), jnp.float32), *stream,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "transpose" not in text


def test_vmem_limits_match_docs(one_chip):
    """Record the largest per-worker value width and bitset vertex count
    that still compile; docs/api.md quotes these numbers."""
    num_out = _largest(lambda n: _compile_bsp(one_chip, "min", n, edges=4096),
                       1 << 20, 1 << 26, 1 << 16)
    vertices = _largest(lambda v: _compile_ebg(one_chip, v, window=True),
                        1 << 22, 1 << 28, 1 << 20)
    print(f"VMEM limits: num_out {num_out}, bitset vertices {vertices} (p={PARTS})")
    assert num_out == MAX_NUM_OUT >= SMOKE_NUM_OUT
    assert vertices == MAX_BITSET_VERTICES >= SMOKE_VERTICES
