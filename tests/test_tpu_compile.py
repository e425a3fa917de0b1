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
``MEGA_MAX_CELLS / MEGA_MAX_ROWS`` slots).  The sharded chunk program of
the 4-chip cell ``gs_mp4.backlog`` is compiled whole for the 2x2 mesh.
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
def v5e_2x2():
    """A described v5e:2x2, with the persistent compilation cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    """One chip of the described v5e:2x2."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


def test_sharded_chunk_program_compiles_on_the_2x2_mesh(v5e_2x2,
                                                       monkeypatch):
    """``gs_mp4``'s chunk program (GS, 10,000 records, four 500-event
    intervals per chunk, ``exchange_slack`` 8) for the 4-chip mesh, routed
    as on the chip; its all-to-alls are in the op table's exchange phase."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.apps import ALL_APPS
    from repro.core.scheduler import EngineConfig
    from repro.core.sharded_stream import ShardedStream, stream_mesh
    from repro.runtime.telemetry import op_phase_table
    kind = v5e_2x2.devices[0].device_kind
    canon = autotune._canon_kind
    monkeypatch.setattr(autotune, "_canon_kind",
                        lambda k=None: canon(kind if k is None else k))
    mesh = stream_mesh((4,), ("dev",), devices=v5e_2x2.devices)
    app = ALL_APPS["gs"]
    eng = ShardedStream(app, app.make_store(), EngineConfig(), mesh,
                        exchange_slack=8.0)
    assert eng.chunk_route(500) == ("mega", "segscan")
    rep = partial(spec, NamedSharding(mesh, P()))
    batched = dict(keys=rep((4, 500, 10), jnp.int32),
                   is_read=rep((4, 500), jnp.bool_),
                   values=rep((4, 500, 10), jnp.float32))
    blocks = spec(NamedSharding(mesh, P("dev")),
                  (4 * (eng.own.per + 1), 1), jnp.float32)
    text = eng._impl.lower(blocks, batched, jax.ShapeDtypeStruct(
        (), jnp.int32)).compile().as_text()
    table = op_phase_table(text)
    assert table["program"] == "jit_tstream_chunk_sharded"
    a2a = re.findall(r"^\s+%?([\w.\-]+) = \S+ all-to-all\(", text, re.M)
    assert len(a2a) >= 2, "the exchange out and its reverse"
    assert {table["phases"][op] for op in a2a} == {"exchange"}


def test_sl_chunk_program_walks_chains_lane_dense(v5e, monkeypatch):
    """``sl_paper``'s chunk program (SL, abort repass, four 500-event
    intervals per chunk) for one v5e: each lockstep level loop (four
    dependency levels, first pass and repass) carries its per-op arrays
    with the op axis minor, ``f32[1,2000]``, no ``[2000,1]`` column laid
    out op-major (one op per 128-lane row), and not the table."""
    import re

    import numpy as np
    from repro.apps import ALL_APPS
    from repro.core.scheduler import DualModeEngine, EngineConfig
    kind = next(iter(v5e.device_set)).device_kind
    canon = autotune._canon_kind
    monkeypatch.setattr(autotune, "_canon_kind",
                        lambda k=None: canon(kind if k is None else k))
    app = ALL_APPS["sl"]
    store = app.make_store()
    cfg = EngineConfig(abort_repass=True)
    eng = DualModeEngine(app, store, cfg)
    sample = app.gen_events(np.random.default_rng(0), 500)
    batched = {k: spec(v5e, (4,) + np.shape(v), np.asarray(v).dtype)
               for k, v in sample.items()}
    text = eng.chunk_program().lower(
        spec(v5e, store.values.shape, store.values.dtype), batched,
        spec(v5e, (), jnp.int32)).compile().as_text()
    carries = [m.group(1) for m in re.finditer(
        r"^\s+%?while[\w.\-]* = \((.*?)\) while\(", text, re.M)]
    rounds = [c for c in carries if f"f32[1,{SL_ROWS}]" in c]
    assert len(rounds) == 2 * (cfg.max_dep_levels + 1), carries
    for c in rounds:
        assert f"[{SL_ROWS},1]{{1,0" not in c, c
        assert f"[{store.values.shape[0]},1]" not in c, c
