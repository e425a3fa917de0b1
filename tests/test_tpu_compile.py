"""Compile-only checks of every Pallas kernel for a TPU v5e.

The TPU compiler is installed even where no chip is attached: each test
compiles a kernel's jitted wrapper, with ``interpret=False``, for one chip
of a described ``v5e:2x2`` topology at the widths the main path hands it,
and asserts the compiled program holds the kernel as a
``tpu_custom_call``.  That catches what interpret mode cannot: primitives
Mosaic has no lowering for, block shapes off the 8x128 tiling, vector
casts it refuses, and kernels that overrun VMEM.  Nothing runs, so these
tests say nothing about results or speed.

Widths: a GS interval is 500 events x 10 accesses = 5,000 rows; an SL
interval 500 x 4 = 2,000 rows, batched over a chunk of 4 intervals; the
sharded route probes a 10,000-uid table (2,500 buckets); the megakernel
is compiled at its fit bounds (``MEGA_MAX_ROWS`` rows x
``MEGA_MAX_CELLS / MEGA_MAX_ROWS`` slots).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.hash_probe import kernel as HK
from repro.kernels.hash_probe.ops import hash_probe
from repro.kernels.megakernel import kernel as MK
from repro.kernels.megakernel.ops import MEGA_MAX_CELLS, MEGA_MAX_ROWS
from repro.kernels.radix_partition.ops import radix_partition_rank
from repro.kernels.runtime import tpu_kernels_in
from repro.kernels.segscan.ops import segscan_affine, segscan_max

GS_ROWS = 500 * 10
SL_ROWS = 500 * 4
LANES = 128


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def compiled_kernels(fn, *shapes):
    return tpu_kernels_in(jax.jit(fn).lower(*shapes).compile().as_text())


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_v5e_reports_lite_kind(v5e):
    kind = next(iter(v5e.device_set)).device_kind
    assert kind == "TPU v5 lite"
    assert autotune.ladder_bounds(kind) == autotune.LADDER_BOUNDS[
        "tpu v5 lite"]


@pytest.mark.parametrize("block", [256, 128, 512, 1024])  # candidates
def test_segscan_affine_compiles_at_gs_interval(v5e, block):
    f32 = partial(spec, v5e, dtype=jnp.float32)
    fn = partial(segscan_affine, interpret=False, block_rows=block)
    found = compiled_kernels(fn, f32((GS_ROWS, LANES)), f32((GS_ROWS, LANES)),
                             spec(v5e, (GS_ROWS,), jnp.bool_))
    assert found["segscan_affine"] == 1, found


def test_segscan_max_compiles_at_gs_interval(v5e):
    fn = partial(segscan_max, interpret=False, block_rows=256)
    found = compiled_kernels(fn, spec(v5e, (GS_ROWS, LANES), jnp.float32),
                             spec(v5e, (GS_ROWS,), jnp.bool_))
    assert found["segscan_max"] == 1, found


@pytest.mark.parametrize("n_buckets", [64, 2047])
def test_radix_partition_compiles_at_sl_chunk(v5e, n_buckets):
    # a chunk of 4 SL intervals partitioned in one dispatch; 64 buckets is
    # the v5e ladder bound, 2047 (+1 dump bucket) the kernel's own bound
    fn = partial(radix_partition_rank, n_buckets=n_buckets, use_pallas=True,
                 interpret=False, block_rows=256)
    found = compiled_kernels(fn, spec(v5e, (4, SL_ROWS), jnp.int32))
    assert found["radix_partition"] == 1, found


def test_hash_probe_compiles_for_sharded_route(v5e):
    n_buckets = 2 * (-(-10_000 // HK.ASSOC))
    f32 = partial(spec, v5e, dtype=jnp.float32)
    fn = partial(hash_probe, interpret=False, block_q=128)
    found = compiled_kernels(fn, spec(v5e, (GS_ROWS,), jnp.int32),
                             f32((n_buckets, HK.ASSOC)),
                             f32((n_buckets, HK.ASSOC)))
    assert found["hash_probe"] == 1, found


def test_megakernel_compiles_at_fit_bounds(v5e):
    rows, slots = MEGA_MAX_ROWS, MEGA_MAX_CELLS // MEGA_MAX_ROWS
    f32 = partial(spec, v5e, dtype=jnp.float32)
    fn = partial(MK.fused_chain_pallas, interpret=False)
    found = compiled_kernels(fn, f32((rows, LANES)), f32((rows, 1)),
                             f32((rows, 1)), f32((rows, 1)),
                             spec(v5e, (rows, 1), jnp.int32),
                             f32((rows, LANES)), f32((slots, LANES)))
    assert found["fused_chain"] == 1, found
