"""Sharded fused streaming (DESIGN.md §2.5).

Contracts pinned here:

1. The sharded fused ``run_stream`` (owner-routed exchange, per-shard
   restructure/coefficient hoisting) is **bit-identical** to the
   single-device fused driver — across all four apps, all three chain-
   shard layouts, key skew, multi-partition transactions, the abort
   repass, and the forced dependency-cycle residue.  (Subprocess with a
   forced 8-device host mesh.)
2. Exchange-capacity overflow is *accounted*, never silent.
3. The hash-probe uid->owner lookup (flag-gated hot-path use of
   ``kernels/hash_probe``) routes identically to the direct gather.
4. ``make_local_store`` is the one local-store constructor and sets
   every field consistently (the historical per-socket/everything bodies
   omitted ``table_base``/``table_capacity``).
5. The segment-relative segmented scans produce bit-identical chain
   results at any array offset — the property the sharded schedule's
   bit-identity rests on.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ownership import (bucket_by_owner, build_ownership,
                                  exchange_capacity, make_local_store,
                                  permute_values, route_gather,
                                  unpermute_values, unroute_gather)
from repro.core.restructure import segmented_scan_affine
from repro.core.types import make_store


# ---------------------------------------------------------------------------
# subprocess: bit-identity vs the single-device fused driver (8 devices)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def worker_verdicts():
    worker = os.path.join(os.path.dirname(__file__),
                          "sharded_stream_worker.py")
    proc = subprocess.run([sys.executable, worker], capture_output=True,
                          text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", [
    "gs/shared_nothing", "tp/shared_nothing", "sl/shared_nothing",
    "ob/shared_nothing", "gs/shared_per_socket", "tp/shared_per_socket",
    "gs/shared_everything", "tp/shared_everything", "gs/skew",
    "gs/multipartition", "sl/abort_repass", "sl/residue",
    "gs/partition_restructure", "sl/partition_restructure",
])
def test_sharded_bit_identical(worker_verdicts, case):
    v = worker_verdicts[case]
    assert v["ok"], f"{case}: {v.get('why')}"


def test_exchange_overflow_is_accounted(worker_verdicts):
    v = worker_verdicts["overflow"]
    assert v["ok"], v
    assert v["dropped"] > 0


def test_hash_probe_routing_matches_gather(worker_verdicts):
    v = worker_verdicts["hash_probe_route"]
    assert v["ok"], v.get("why")


# ---------------------------------------------------------------------------
# unified local-store construction (in-process; no mesh needed)
# ---------------------------------------------------------------------------
def test_make_local_store_fields_consistent():
    """One helper, consistent fields — regression for the historical
    copy-pasted bodies that omitted table_base/table_capacity."""
    vals = jnp.zeros((17, 2))
    ls = make_local_store(vals)
    assert ls.table_base == (0,)
    assert ls.table_capacity == (16,)
    assert ls.table_is_max == (False,)
    assert ls.slot_is_max is None
    assert ls.pad_uid == 16

    flags = jnp.zeros((17,), bool).at[3].set(True)
    lsm = make_local_store(vals, flags)
    assert lsm.table_base == (0,) and lsm.table_capacity == (16,)
    assert lsm.table_is_max == (True,)
    np.testing.assert_array_equal(np.asarray(lsm.uid_is_max()),
                                  np.asarray(flags))


def test_ownership_permutation_roundtrip_and_max_flags():
    store = make_store([10, 10], 3, is_max=[False, True])
    own = build_ownership(store, 4)
    assert own.per == 5 and own.s_pad == 20
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.uniform(size=(21, 3)).astype(np.float32))
    vals = vals.at[-1].set(0.0)
    back = unpermute_values(own, permute_values(own, vals))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(vals))
    # max flags follow the permutation: slots of table 1 stay max-typed
    sim = np.asarray(own.slot_is_max)
    fwd = np.asarray(own.fwd)
    for uid in range(20):
        assert sim[fwd[uid]] == (uid >= 10)


# ---------------------------------------------------------------------------
# owner-routed bucketing (in-process)
# ---------------------------------------------------------------------------
def test_bucket_roundtrip_and_overflow_count():
    rng = np.random.default_rng(1)
    dst = jnp.asarray(rng.integers(0, 4, 40).astype(np.int32)).at[5].set(4)
    plan = bucket_by_owner(dst, 4, cap=20)
    assert int(plan.dropped) == 0
    field = jnp.arange(40, dtype=jnp.int32) * 10
    bucketed = route_gather(plan, field, -1)
    ret = unroute_gather(plan, bucketed.reshape(80), 4, 20, pad_value=-7)
    exp = np.where(np.asarray(dst) < 4, np.asarray(field), -7)
    np.testing.assert_array_equal(np.asarray(ret), exp)

    tight = bucket_by_owner(dst, 4, cap=2)
    counts = np.bincount(np.asarray(dst), minlength=5)[:4]
    assert int(tight.dropped) == int(np.maximum(counts - 2, 0).sum())


def test_exchange_capacity_policy():
    assert exchange_capacity(100, 8, 2.0) == 26       # 2x balanced share
    assert exchange_capacity(100, 8, 1.0) == 13       # floor: exact share
    assert exchange_capacity(100, 8, 100.0) == 100    # clamp: worst case
    assert exchange_capacity(1, 8, 2.0) == 1


# ---------------------------------------------------------------------------
# segment-relative scan: offset invariance (bit-identity foundation)
# ---------------------------------------------------------------------------
def test_segmented_scan_offset_invariant():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.uniform(0.5, 1.5, (16, 2)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-1, 1, (16, 2)).astype(np.float32))
    seg = jnp.zeros(16, bool).at[0].set(True).at[5].set(True).at[11].set(True)
    A, B = segmented_scan_affine(a, b, seg)
    # the middle segment (rows 5..10) moved to offset 3 of another array
    pre_a = jnp.asarray(rng.uniform(0.5, 1.5, (3, 2)).astype(np.float32))
    a2 = jnp.concatenate([pre_a, a[5:11], a[:2]])
    b2 = jnp.concatenate([pre_a * 0, b[5:11], b[:2]])
    seg2 = jnp.zeros(11, bool).at[0].set(True).at[3].set(True).at[9].set(True)
    A2, B2 = segmented_scan_affine(a2, b2, seg2)
    np.testing.assert_array_equal(np.asarray(A[5:11]), np.asarray(A2[3:9]))
    np.testing.assert_array_equal(np.asarray(B[5:11]), np.asarray(B2[3:9]))


def test_stream_mesh_is_auto_and_explicit_mesh_is_refused():
    """JAX's make_mesh defaults to Explicit axes, under which
    ``ShardedStream``'s gathers are refused at trace time; it takes Auto
    meshes (``stream_mesh``) and names the constructor otherwise."""
    import jax
    from jax.sharding import AxisType

    from repro.apps import ALL_APPS
    from repro.core.scheduler import DualModeEngine, EngineConfig
    from repro.core.sharded_stream import stream_mesh
    mesh = stream_mesh((1,), ("dev",))
    assert mesh.axis_types == (AxisType.Auto,)
    app = ALL_APPS["gs"]
    store = app.make_store()
    DualModeEngine(app, store, EngineConfig(), mesh=mesh)
    explicit = jax.make_mesh((1,), ("dev",),
                             axis_types=(AxisType.Explicit,))
    with pytest.raises(ValueError, match="stream_mesh"):
        DualModeEngine(app, store, EngineConfig(), mesh=explicit)
