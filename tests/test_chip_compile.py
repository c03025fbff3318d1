"""The device path's programs compile for a TPU v5e.

Nothing here runs on a chip.  Each test lowers a program at the size
``chip_smoke.py`` runs it and compiles it for a v5e chip that
``jax.experimental.topologies`` describes; the TPU compiler installed
with JAX then refuses what the chip's compiler would refuse (block
shapes off the (8, 128) tiling, element types the chip lacks, programs
that do not fit its memory).  Interpret mode, which every other kernel
test uses, hides all of that.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and under
several test workers only the worker that runs this file may load it.
The persistent compilation cache is off around the compiles, since a
compile for a described chip is written to it but cannot be read back.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.batched import _cells_tables_kernel
from repro.kernels.bloom.bloom import BYTE_BLOCK, _bloom_probe_jit
from repro.kernels.subsetdp.subsetdp import _subset_prod_jit, default_row_block

#: the smoke's Sec. V grid: 1,000,000 gradle requests give V=14,922 view
#: versions of n=3 caches; fna and fno over 8 penalties are C=16 cells
#: in G=2 (costs, fno) groups
SEC_V = dict(v=14_922, n=3, c=16, g=2)
#: v5e HBM per chip
V5E_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler, or it is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < V5E_BYTES


def test_cells_tables_f64_compiles_for_v5e(one_chip):
    """The phase-2 table program of ``run_grid(backend="jax")`` at the
    smoke's size, in float64 as the engine runs it: XLA emulates f64 on
    v5e, and the program fits one chip."""
    v, n, c, g = SEC_V["v"], SEC_V["n"], SEC_V["c"], SEC_V["g"]
    with jax.enable_x64(True):
        compiled = _cells_tables_kernel.lower(
            _spec((g, n), jnp.float64, one_chip),
            _spec((g,), jnp.bool_, one_chip),
            _spec((c,), jnp.int64, one_chip),
            _spec((c,), jnp.float64, one_chip),
            _spec((v, n), jnp.float64, one_chip),
            _spec((v, n), jnp.float64, one_chip)).compile()
    assert _fits(compiled)
    out = compiled.out_info
    assert out.shape == (c, v << n, n) and out.dtype == jnp.bool_


def test_bloom_probe_compiles_for_v5e(one_chip):
    """8 caches x 10,000 entries x bpe 14 (filters padded to whole byte
    blocks) probing 4,096 keys: a Mosaic kernel, not interpret mode."""
    n, mbytes, keys = 8, 9 * BYTE_BLOCK, 4096
    assert mbytes * 8 >= 10_000 * 14
    compiled = _bloom_probe_jit.lower(
        _spec((n, mbytes), jnp.uint8, one_chip),
        _spec((keys,), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        k=10, key_block=256, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (keys, n)
    assert _fits(compiled)


@pytest.mark.parametrize("n", [8, 12])
def test_subset_dp_f32_compiles_for_v5e(one_chip, n):
    rows = 4096
    compiled = _subset_prod_jit.lower(
        _spec((1,), jnp.float32, one_chip),
        _spec((rows, n), jnp.float32, one_chip),
        n=n, row_block=default_row_block(n), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (rows, 1 << n)
    assert _fits(compiled)


def test_subset_dp_f64_refused_before_the_compiler(one_chip):
    """v5e has no float64: a compiled-mode f64 call raises a TypeError
    that says so, instead of Mosaic's X64 rewrite error."""
    with jax.enable_x64(True), pytest.raises(TypeError, match="no float64"):
        _subset_prod_jit.lower(
            _spec((1,), jnp.float64, one_chip),
            _spec((4096, 8), jnp.float64, one_chip),
            n=8, row_block=default_row_block(8), interpret=False)
    # interpret mode still serves float64 (the CPU exactness path)
    rhos = np.random.default_rng(0).uniform(0.0, 1.0, (16, 3))
    with jax.enable_x64(True):
        out = _subset_prod_jit(jnp.asarray([100.0]), jnp.asarray(rhos), n=3,
                               row_block=16, interpret=True)
        assert out.dtype == jnp.float64
