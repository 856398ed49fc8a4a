"""The main path compiles for a TPU v5e at real sizes, with no chip attached.

The TPU compiler is installed with jax: a topology described here compiles
what the chip's compiler would, and refuses what it would refuse (an
unaligned slice, a float iota, too much VMEM).  Interpret mode, which every
other test runs, cannot see those refusals.  Each case lowers with
``interpret=False`` and compiles for one chip of a described ``v5e:2x2``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import VetEngine
from repro.kernels.changepoint.ops import (auto_block, changepoint_pallas,
                                           changepoint_pallas_rows)
from repro.kernels.windowvet.kernel import fused_window_vet_scan

FLEET_ROWS = 16384  # windows in one tick of a 16k-stream fleet
ARENA = 1 << 23  # f32 records: the pow2 arena of such a tick (~7.3 M live)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep it out of the cache.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("lmax", [64, 256, 1024])
def test_fused_window_vet_launch_compiles(one_chip, lmax):
    rows = (FLEET_ROWS,)
    compiled = fused_window_vet_scan.lower(
        _spec((ARENA,), jnp.float32, one_chip),
        _spec(rows, jnp.int32, one_chip), _spec(rows, jnp.int32, one_chip),
        _spec(rows, jnp.float32, one_chip),
        lmax=lmax, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [64, 1000, 8192])
def test_changepoint_scan_compiles(one_chip, n):
    compiled = changepoint_pallas.lower(
        _spec((n,), jnp.float32, one_chip), omega=3, block=auto_block(n),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,n", [(1, 64), (1024, 64), (16384, 64),
                                    (64, 1000)])
def test_batched_changepoint_scan_compiles(one_chip, rows, n):
    """The monitor's scan of every stream's ring in one launch (rows
    padded to a power of two; a 64-window ring; one long ring)."""
    compiled = changepoint_pallas_rows.lower(
        _spec((rows, n), jnp.float32, one_chip), omega=3,
        block=auto_block(n), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jax_gather_batch_compiles(one_chip):
    batch = VetEngine("jax")._make_batch_fn()
    compiled = batch.lower(
        _spec((FLEET_ROWS, 256), jnp.float32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_pallas_gather_batch_compiles(one_chip):
    """The pallas engine's gather path (the bucketed rows the fused kernel
    does not serve) maps the one-series scan over rows: its one-row block
    stays lowerable once the map adds a row axis."""
    batch = VetEngine("pallas", interpret=False)._make_batch_fn()
    compiled = batch.lower(
        _spec((FLEET_ROWS, 256), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
