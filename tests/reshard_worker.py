"""Elastic-resharding worker (subprocess: forces 8 host devices).

Engine-level contracts of live skew-aware migration (DESIGN.md §2.10),
reported as JSON verdicts for tests/test_elastic_reshard.py:

* **Migrate mid-stream, stay bitwise**: a skew storm (calm -> aligned
  Zipf hot phase -> calm) trips the controller's ``reshard`` knob; the
  service live-migrates hot slots at a punctuation boundary and every
  interval output AND the final state stay bit-identical to the
  never-migrated single-device monolithic run on the same in-order
  events — across all four apps and both the tstream and mvlk schemes.
* **Crash during migration**: an injected ``reshard.apply`` crash lands
  after the rows moved but before any snapshot records the migrated
  run; restore + replay re-derives the same reshard decision from the
  same records and the resumed run is bitwise identical to the
  uninterrupted elastic run (and to the single-device reference).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import sys
import tempfile
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.apps import ALL_APPS                                 # noqa: E402
from repro.core.intervals import (PhasedReplaySource,           # noqa: E402
                                  WatermarkPolicy)
from repro.core.scheduler import DualModeEngine, EngineConfig   # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402
from repro.runtime.controller import ControllerConfig           # noqa: E402
from repro.runtime.faults import (RESHARD_APPLY, Fault,         # noqa: E402
                                  FaultPlane, InjectedCrashError)
from repro.runtime.service import ServiceConfig, StreamService  # noqa: E402

MESH = stream_mesh((8,), ("dev",))
INTERVAL = 64
JITTER = 4
# reshard-only controller: every other knob's lattice is empty
CTL = ControllerConfig(window=4, sustain=2, cooldown=4, slack_widen=False,
                       reshard_imbalance=3.0, reshard_max_moves=24)


def app_kwargs(app_name):
    # TP's segment table must stay divisible by align_mod=8
    return dict(n_segments=96) if app_name == "tp" else {}


def storm_source(app, base, seed=7):
    """calm -> aligned-Zipf hot phase -> calm, all one seeded stream."""
    hot = dict(base, theta=2.5, align_mod=8)
    return PhasedReplaySource(
        app.gen_events,
        [(4 * INTERVAL, base), (8 * INTERVAL, hot), (4 * INTERVAL, base)],
        seed=seed, arrival_batch=37, jitter=JITTER)


def elastic_cfg(**kw):
    return ServiceConfig(punct_interval=INTERVAL, chunk_intervals=2,
                         watermark=WatermarkPolicy(allowed_lateness=JITTER),
                         controller=CTL, **kw)


def _outputs_equal(a_list, b_list):
    for i, (a, b) in enumerate(zip(a_list, b_list)):
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                return f"output {k} interval {i} differs"
    if len(a_list) != len(b_list):
        return f"interval count {len(a_list)} != {len(b_list)}"
    return None


def _single_device_ref(app, store, scheme, src):
    eng1 = DualModeEngine(app, store, EngineConfig(scheme=scheme))
    return eng1.run_stream(store.values, src.in_order_events, INTERVAL,
                           fused=True)


def check_migrate_bitwise(app_name, scheme):
    app = ALL_APPS[app_name]
    kw = app_kwargs(app_name)
    store = app.make_store(**kw)
    src = storm_source(app, kw)
    outs_ref, vals_ref = _single_device_ref(app, store, scheme, src)

    eng8 = DualModeEngine(app, store, EngineConfig(scheme=scheme),
                          mesh=MESH, exchange_slack=8.0)
    rec = StreamService(eng8, elastic_cfg()).run(storm_source(app, kw))

    place = rec.stats.get("placement")
    if not place or not place["migrations"]:
        return dict(ok=False, why=f"no migration fired: {place}")
    if place["moved_rows"] <= 0:
        return dict(ok=False, why="migration fired but moved no rows")
    if not any(d["knob"] == "reshard" for d in rec.decisions):
        return dict(ok=False, why="no reshard decision in the trace")
    if not place["owners"]:
        return dict(ok=False, why="engine left on striping placement")
    if rec.stats["drops"]["exchange"]:
        return dict(ok=False, why="exchange dropped ops during the storm")
    if not np.array_equal(rec.final_values, np.asarray(vals_ref)):
        return dict(ok=False, why="final state differs vs 1dev reference")
    why = _outputs_equal(rec.outputs, outs_ref)
    if why:
        return dict(ok=False, why=f"vs 1dev reference: {why}")
    return dict(ok=True, migrations=len(place["migrations"]),
                moved=place["moved_rows"], imbalance=place["imbalance"])


def check_reshard_crash_recovery(app_name, scheme):
    app = ALL_APPS[app_name]
    kw = app_kwargs(app_name)
    store = app.make_store(**kw)
    src = storm_source(app, kw)
    outs_1, vals_1 = _single_device_ref(app, store, scheme, src)

    def fresh():
        return DualModeEngine(app, store, EngineConfig(scheme=scheme),
                              mesh=MESH, exchange_slack=8.0)

    with tempfile.TemporaryDirectory() as d:
        ref = StreamService(fresh(), elastic_cfg(
            snapshot_every=4, ckpt_dir=os.path.join(d, "ref"))).run(
                storm_source(app, kw))
        if not ref.stats["placement"]["migrations"]:
            return dict(ok=False, why="reference run never migrated")

        cfg = elastic_cfg(snapshot_every=4, ckpt_dir=os.path.join(d, "go"))
        plane = FaultPlane([Fault(site=RESHARD_APPLY, at=0, kind="crash")])
        svc = StreamService(fresh(), cfg)
        try:
            svc.run(storm_source(app, kw), faults=plane)
            return dict(ok=False, why="injected reshard crash did not fire")
        except InjectedCrashError:
            pass
        crashed = svc.last_run
        if not crashed.migrations:
            return dict(ok=False, why="crash fired before any migration")
        if not crashed.snapshots:
            return dict(ok=False, why="no snapshot before the crash")

        rec = StreamService(fresh(), cfg).resume(storm_source(app, kw))
        snap = rec.stats["replayed"] // INTERVAL
        if not rec.stats["placement"]["migrations"]:
            return dict(ok=False, why="resumed run never re-migrated")
        # consistent layout: the replayed trace folds to the same plan
        # (same ownership overrides) as the uninterrupted run
        if rec.stats["controller"]["plan"] != ref.stats["controller"]["plan"]:
            return dict(ok=False, why="resumed plan differs: "
                        f"{rec.stats['controller']['plan']} vs "
                        f"{ref.stats['controller']['plan']}")
        if not np.array_equal(rec.final_values, ref.final_values):
            return dict(ok=False,
                        why="final state differs vs uninterrupted elastic")
        if not np.array_equal(rec.final_values, np.asarray(vals_1)):
            return dict(ok=False, why="final state differs vs 1dev")
        why = _outputs_equal(rec.outputs, ref.outputs[snap:])
        if why:
            return dict(ok=False, why=f"post-resume {why}")
        why = _outputs_equal(crashed.outputs,
                             ref.outputs[: len(crashed.outputs)])
        if why:
            return dict(ok=False, why=f"pre-crash {why}")
        return dict(ok=True, resumed_from=snap,
                    migrations=len(rec.stats["placement"]["migrations"]))


def main():
    out = {}

    def run(name, fn, *a):
        try:
            out[name] = fn(*a)
        except Exception as e:  # pragma: no cover - surfaced via verdict
            traceback.print_exc(file=sys.stderr)
            out[name] = dict(ok=False, why=f"{type(e).__name__}: {e}")

    cases = [("gs", "tstream"), ("sl", "tstream"), ("ob", "tstream"),
             ("tp", "tstream"), ("gs", "mvlk"), ("ob", "mvlk")]
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    for app_name, scheme in cases:
        if only and only not in (app_name, f"{app_name}/{scheme}"):
            continue
        run(f"{app_name}/{scheme}/migrate", check_migrate_bitwise,
            app_name, scheme)
    if not only or only in ("gs", "gs/tstream"):
        run("gs/tstream/crash", check_reshard_crash_recovery,
            "gs", "tstream")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
