"""Sharded fused streaming worker (subprocess: forces 8 host devices).

Each check compares the sharded fused driver against the single-device
fused driver and reports a JSON verdict; the pytest wrapper
(`tests/test_sharded_stream.py`) asserts on the verdicts.  Bit-identity
here means **bitwise equality** of every per-interval output and the
final state (DESIGN.md §2.5).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import ALL_APPS                               # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig  # noqa: E402
from repro.core.sharded_stream import stream_mesh              # noqa: E402

MESH1 = stream_mesh((8,), ("dev",))
MESH2 = stream_mesh((2, 4), ("socket", "core"))


def bit_identical(app_name, layout, mesh, *, n_events=128, interval=32,
                  slack=8.0, seed=11, cfg=None, mutate=None,
                  gen_kwargs=None, cfg_ref=None):
    app = ALL_APPS[app_name]
    rng = np.random.default_rng(seed)
    stream = app.gen_events(rng, n_events, **(gen_kwargs or {}))
    if mutate:
        mutate(stream)
    store = app.make_store()
    cfg = cfg or EngineConfig()
    ref = DualModeEngine(app, store, cfg_ref or cfg)
    outs_r, vals_r = ref.run_stream(store.values, stream, interval,
                                    fused=True)
    eng = DualModeEngine(app, store, cfg, mesh=mesh, layout=layout,
                        exchange_slack=slack)
    outs_s, vals_s = eng.run_stream(store.values, stream, interval)
    st = eng.last_exchange_stats
    if int(np.sum(st["dropped"])) != 0:
        return dict(ok=False, why="unexpected exchange drops")
    if not np.array_equal(np.asarray(vals_s), np.asarray(vals_r)):
        return dict(ok=False, why="final state differs")
    for i, (a, b) in enumerate(zip(outs_s, outs_r)):
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return dict(ok=False, why=f"output {k} interval {i} differs")
    return dict(ok=True, shipped=int(st["shipped"][0]),
                capacity=int(st["capacity"]))


def overdraw(stream):
    stream["amount"] = (stream["amount"] * 100).astype(np.float32)


def check_overflow():
    """Tiny capacity forces drops; the engine must COUNT them (and the
    run completes — degraded, not crashed)."""
    app = ALL_APPS["gs"]
    rng = np.random.default_rng(9)
    stream = app.gen_events(rng, 64)
    store = app.make_store()
    eng = DualModeEngine(app, store, EngineConfig(), mesh=MESH1,
                        exchange_slack=1.0)
    eng.run_stream(store.values, stream, 32)
    st = eng.last_exchange_stats
    dropped = int(np.sum(st["dropped"]))
    return dict(ok=dropped > 0, dropped=dropped,
                capacity=int(st["capacity"]))


def check_probe_parity():
    """Hash-probe uid->owner routing (flag-gated) must route identically
    to the direct-addressed gather."""
    app = ALL_APPS["gs"]
    rng = np.random.default_rng(9)
    stream = app.gen_events(rng, 64)
    store = app.make_store()
    e1 = DualModeEngine(app, store, EngineConfig(), mesh=MESH1,
                        exchange_slack=8.0)
    o1, v1 = e1.run_stream(store.values, stream, 32)
    e2 = DualModeEngine(app, store, EngineConfig(use_hash_probe_route=True),
                        mesh=MESH1, exchange_slack=8.0)
    o2, v2 = e2.run_stream(store.values, stream, 32)
    if not np.array_equal(np.asarray(v1), np.asarray(v2)):
        return dict(ok=False, why="state differs")
    for a, b in zip(o1, o2):
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return dict(ok=False, why=f"output {k} differs")
    return dict(ok=True)


def main():
    out = {}

    def run(name, fn, *a, **kw):
        try:
            out[name] = fn(*a, **kw)
        except Exception as e:  # pragma: no cover - surfaced via verdict
            traceback.print_exc(file=sys.stderr)
            out[name] = dict(ok=False, why=f"{type(e).__name__}: {e}")

    # every app under shared_nothing (assoc fast path + sharded lockstep)
    for app_name in ("gs", "tp", "sl", "ob"):
        run(f"{app_name}/shared_nothing", bit_identical, app_name,
            "shared_nothing", MESH1)
    # every layout (2-D mesh) for both associative apps (TP has
    # heterogeneous max tables -> exercises permuted slot_is_max)
    for layout, mesh in (("shared_nothing", MESH2),
                         ("shared_per_socket", MESH2),
                         ("shared_everything", MESH1)):
        for app_name in ("gs", "tp"):
            run(f"{app_name}/{layout}", bit_identical, app_name, layout,
                mesh)
    # key skew and multi-partition transactions
    run("gs/skew", bit_identical, "gs", "shared_nothing", MESH1, seed=5,
        gen_kwargs=dict(theta=0.95), slack=8.0)
    run("gs/multipartition", bit_identical, "gs", "shared_nothing", MESH1,
        seed=7, gen_kwargs=dict(n_partitions=16, mp_ratio=0.5, mp_len=6))
    # abort repass under heavy failure + forced dependency residue
    run("sl/abort_repass", bit_identical, "sl", "shared_nothing", MESH1,
        seed=3, cfg=EngineConfig(scheme="tstream", abort_repass=True),
        mutate=overdraw, n_events=96, interval=24)
    run("sl/residue", bit_identical, "sl", "shared_nothing", MESH1, seed=3,
        cfg=EngineConfig(scheme="tstream", max_dep_levels=0),
        mutate=overdraw, n_events=96, interval=24)
    # radix-partition restructure backbone: the sharded driver forced onto
    # the partition rung must match the lexsort single-device reference
    # bit for bit (segscan fast path + gated lockstep path)
    run("gs/partition_restructure", bit_identical, "gs", "shared_nothing",
        MESH1, cfg=EngineConfig(restructure_method="partition"),
        cfg_ref=EngineConfig(restructure_method="lexsort"))
    run("sl/partition_restructure", bit_identical, "sl", "shared_nothing",
        MESH1, cfg=EngineConfig(restructure_method="partition"),
        cfg_ref=EngineConfig(restructure_method="lexsort"))
    # exchange-capacity overflow accounting + hash-probe routing
    run("overflow", check_overflow)
    run("hash_probe_route", check_probe_parity)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
