import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=8"]))

"""Fig. 14 worker (subprocess: needs 8 placeholder devices).

NUMA-aware configurations -> chain-shard layouts on a (socket=2, core=4)
mesh, on the **fused sharded streaming path** (DESIGN.md §2.5): for each
layout the whole stream runs as one owner-routed sharded program,
verified bit-for-bit against the single-device fused driver, with
exchange drop accounting surfaced (never silent).  The historical
replicate-everything per-batch ``evaluate_sharded`` is kept as the
baseline rows (verified against the sequential oracle), so the exchange
win is measured, not assumed.  Prints JSON per layout.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps import GS                                       # noqa: E402
from repro.core.blotter import build_opbatch                    # noqa: E402
from repro.core.engines import evaluate                         # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig   # noqa: E402
from repro.core.sharded import LAYOUTS, evaluate_sharded        # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo               # noqa: E402


# ---------------------------------------------------------------------------
# Skew-storm mode (``reshard`` argv): elastic resharding A/B (DESIGN.md §2.10)
# ---------------------------------------------------------------------------
# One seeded storm -- calm, a mild *aligned* ramp (the whole Zipf head
# collides on one ownership residue class), the theta=2.5 peak, calm --
# replayed under three provisioning policies:
#
#   static-slack8   worst-case capacity: never drops, big exchange shapes
#   static-slack2   lean capacity, no migration: overflow-drops in the storm
#   elastic-slack2  lean capacity + skew-aware migration: the ramp trips the
#                   controller before the peak lands, so it keeps slack-2
#                   shapes AND zero drops
#
# Per-phase aggregates exclude the first chunk and migration chunks (both
# pay an XLA compile; steady-state throughput is the claim — the one-time
# migration cost is reported separately on each row as ``migrations`` /
# ``apply_s``).

RESHARD_SIZES = dict(
    # interval, phase lengths (intervals), ramp theta, trigger, moves
    full=dict(interval=256, calm=4, ramp=6, peak=8, ramp_theta=0.2,
              imbalance=1.4, moves=64, lean=2.0),
    smoke=dict(interval=64, calm=2, ramp=4, peak=4, ramp_theta=0.6,
               imbalance=1.4, moves=24, lean=8.0),
)


def _storm_source(app, spec):
    from repro.core.intervals import PhasedReplaySource
    iv = spec["interval"]
    return PhasedReplaySource(
        app.gen_events,
        [(spec["calm"] * iv, {}),
         (spec["ramp"] * iv, dict(theta=spec["ramp_theta"], align_mod=8)),
         (spec["peak"] * iv, dict(theta=2.5, align_mod=8)),
         (spec["calm"] * iv, {})],
        seed=11, arrival_batch=128, jitter=4)


PHASE_NAMES = ("calm", "ramp", "peak", "cooldown")


def _reshard_run(app, store, mesh, spec, slack, elastic):
    from repro.core.intervals import WatermarkPolicy
    from repro.runtime.controller import ControllerConfig
    from repro.runtime.service import ServiceConfig, StreamService

    ctl = None
    if elastic:
        ctl = ControllerConfig(window=4, sustain=2, cooldown=4,
                               slack_widen=False,
                               reshard_imbalance=spec["imbalance"],
                               reshard_max_moves=spec["moves"])
    eng = DualModeEngine(app, store, EngineConfig(), mesh=mesh,
                         exchange_slack=slack)
    cfg = ServiceConfig(punct_interval=spec["interval"], chunk_intervals=2,
                        watermark=WatermarkPolicy(allowed_lateness=4),
                        chunk_record_ring=64, controller=ctl)
    src = _storm_source(app, spec)
    rec = StreamService(eng, cfg).run(src)
    trace_out = os.environ.get("RESHARD_TRACE_OUT")
    if elastic and trace_out:
        with open(trace_out, "w") as f:
            for d in rec.decisions:
                f.write(json.dumps(d) + "\n")

    place = rec.stats.get("placement") or {}
    migs = place.get("migrations", [])
    mig_g = {m["g"] for m in migs}
    phases = {}
    for c in rec.chunk_records:
        ph = src.phase_of_interval(c["g0"], spec["interval"])
        d = phases.setdefault(ph, dict(events=0, lat_s=0.0, drops=0,
                                       chunks=0))
        d["drops"] += int(c.get("x_drop", 0))
        # steady state only: skip the compile chunk + migration chunks
        if c["i"] == 0 or c["g0"] in mig_g:
            continue
        d["events"] += int(c["events"])
        d["lat_s"] += float(c["lat_s"])
        d["chunks"] += 1
    plan = (f"elastic-slack{slack:g}" if elastic
            else f"static-slack{slack:g}")
    shared = dict(plan=plan, slack=slack, elastic=elastic,
                  capacity=int(rec.stats["exchange"]["capacity"]),
                  migrations=len(migs),
                  moved_rows=int(place.get("moved_rows", 0)),
                  apply_s=float(sum(m["apply_s"] for m in migs)),
                  imbalance=place.get("imbalance"))
    rows = []
    for ph, d in sorted(phases.items()):
        rows.append(dict(shared, phase=PHASE_NAMES[ph],
                         events_per_s=(d["events"] / d["lat_s"]
                                       if d["lat_s"] else 0.0),
                         wall_s=d["lat_s"], chunks=d["chunks"],
                         drops=d["drops"]))
    rows.append(dict(shared, phase="all",
                     events_per_s=rec.sustained_events_per_s(),
                     wall_s=float(sum(c["lat_s"]
                                      for c in rec.chunk_records)),
                     chunks=len(rec.chunk_records),
                     drops=int(rec.stats["drops"]["exchange"])))
    return rows


def main_reshard(size):
    from repro.apps import GS
    spec = RESHARD_SIZES["smoke" if size == "smoke" else "full"]
    mesh = stream_mesh((8,), ("dev",))
    store = GS.make_store()
    lean = spec["lean"]
    rows = []
    rows += _reshard_run(GS, store, mesh, spec, 8.0, elastic=False)
    if lean != 8.0:
        rows += _reshard_run(GS, store, mesh, spec, lean, elastic=False)
    rows += _reshard_run(GS, store, mesh, spec, lean, elastic=True)
    print(json.dumps(rows))


def main():
    mesh = stream_mesh((2, 4), ("socket", "core"))
    rng = np.random.default_rng(14)
    store = GS.make_store()

    # ---- per-batch baseline (replicate-everything), oracle-verified -----
    events = {k: jnp.asarray(v) for k, v in GS.gen_events(rng, 512).items()}
    ops, _ = build_opbatch(GS, store, events, jnp.int32(0))
    _, oracle_vals, _ = evaluate(store, ops, GS.funs, "lock")
    oracle = np.asarray(oracle_vals)[:-1]

    # ---- fused sharded streaming, bit-checked vs single-device fused ----
    n_events, interval = 2048, 512
    stream = GS.gen_events(np.random.default_rng(15), n_events)
    ref = DualModeEngine(GS, store, EngineConfig())
    outs_ref, vals_ref = ref.run_stream(store.values, stream, interval,
                                        fused=True)

    out = {}
    for layout in LAYOUTS:
        with mesh:
            fn = jax.jit(lambda o: evaluate_sharded(store, o, GS.funs,
                                                    mesh, layout))
            lowered = fn.lower(ops)
            compiled = lowered.compile()
            res = analyze_hlo(compiled.as_text(), mesh.size)
            vals = np.asarray(jax.block_until_ready(fn(ops)))
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(fn(ops))
            secs = (time.perf_counter() - t0) / 3
        ok = bool(np.allclose(vals, oracle, rtol=1e-4, atol=1e-4))

        eng = DualModeEngine(GS, store, EngineConfig(), mesh=mesh,
                            layout=layout, exchange_slack=4.0)
        outs_s, vals_s = eng.run_stream(store.values, stream, interval)
        jax.block_until_ready(vals_s)
        t0 = time.perf_counter()
        for _ in range(3):
            outs_s, vals_s = eng.run_stream(store.values, stream, interval)
            jax.block_until_ready(vals_s)
        stream_secs = (time.perf_counter() - t0) / 3
        st = eng.last_exchange_stats
        bit_ok = bool(np.array_equal(np.asarray(vals_s),
                                     np.asarray(vals_ref)))
        for a, b in zip(outs_s, outs_ref):
            for k in a:
                bit_ok &= bool(np.array_equal(np.asarray(a[k]),
                                              np.asarray(b[k])))

        out[layout] = dict(
            correct=ok,
            wall_s=secs,
            coll_bytes=res["coll_bytes"],
            wire_bytes_per_device=res["wire_bytes_per_device"],
            fused_bit_identical=bit_ok,
            fused_wall_s=stream_secs,
            fused_events_per_s=n_events / stream_secs,
            fused_dropped=int(np.sum(st["dropped"])),
            fused_exchange_capacity=int(st["capacity"]),
        )
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "reshard":
        main_reshard(sys.argv[2] if len(sys.argv) > 2 else "quick")
    else:
        main()
