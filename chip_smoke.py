"""Chip smoke test: the TStream service path on one TPU, checked end to end.

Drives ``StreamService`` -> ``DualModeEngine`` -> the fused ``lax.scan``
over device-resident state at the paper's deployment scale (10,000-record
tables, 500-event punctuation intervals, 32 intervals = 16,000 events per
phase), and checks every result against a reference:

1. GS (Grep and Sum; 10 accesses per transaction, theta 0.6, read ratio
   0.5) on the default XLA path, with periodic snapshots, against the
   sequential ``lock`` oracle run on the host CPU (``rtol=1e-5``).
2. SL (Streaming Ledger) with ``abort_repass=True`` — the gated lockstep
   path with aborts — against the ``lock`` oracle on the host CPU.
3. GS with ``use_pallas=True``: the Pallas kernels the resolved
   restructure rung uses must appear as ``tpu_custom_call`` in the
   compiled chunk program, and results must match phase 1 within the
   tolerance the CPU tests hold that rung to.

Each phase prints compile seconds, steady events/s, p50/p99 latency
(closed loop: the source is pulled as fast as admission allows) and
``peak_bytes_in_use``.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failed phase exits non-zero first.

``--four-chips`` runs only the cross-chip path: GS and SL on a 4-chip
``shared_nothing`` mesh through the service, with a skew storm
(``align_mod=4``, theta 2.5) that makes the controller migrate state
(``apply_resharding``) at a punctuation boundary; outputs and final state
must equal the 1-chip fused run of the same events bitwise.

Run on a TPU host from the repository root:

    python chip_smoke.py
    python chip_smoke.py --four-chips

Without a TPU it exits non-zero before doing any work.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
INTERVAL = 500        # events per punctuation interval (paper default)
CHUNK = 4             # intervals per device dispatch
N_INTERVALS = 32      # intervals per phase
SNAPSHOT_EVERY = 16   # intervals between snapshots
ORACLE_TOL = dict(rtol=1e-5, atol=1e-5)   # examples/quickstart.py
PALLAS_TOL = dict(rtol=1e-6, atol=1e-6)   # tests/test_fused_stream.py


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded path and its "
                         "comparison with the 1-chip run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated event stream")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# helpers (JAX is imported by main() before any of these run)
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs


def check(ok, msg):
    """A phase check that ``python -O`` cannot strip."""
    if not ok:
        raise AssertionError(msg)


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def compare(label, outs, vals, ref_outs, ref_vals, tol):
    """Outputs and final state against a reference: floats within
    ``tol``, everything else (flags, counts) exactly.  ``tol=None`` asks
    for bitwise equality."""
    import numpy as np
    check(len(outs) == len(ref_outs),
          f"{label}: {len(outs)} intervals vs {len(ref_outs)} in the "
          f"reference")

    def same(a, b, what):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape,
              f"{label}: {what} shape {a.shape} vs {b.shape}")
        if tol is None or not np.issubdtype(a.dtype, np.floating):
            check(np.array_equal(a, b), f"{label}: {what} differs")
        else:
            np.testing.assert_allclose(a, b, err_msg=f"{label}: {what}",
                                       **tol)

    same(vals, ref_vals, "final state")
    for i, (o, r) in enumerate(zip(outs, ref_outs)):
        check(set(o) == set(r), f"{label}: output keys differ")
        for k in o:
            same(o[k], r[k], f"output {k!r} of interval {i}")


def oracle(app, cfg, events):
    """The sequential ``lock`` scheme on the host CPU: the repository's
    reference semantics, independent of the device under test."""
    import dataclasses

    import jax
    from repro.core.scheduler import DualModeEngine
    with jax.default_device(jax.devices("cpu")[0]):
        store = app.make_store()
        eng = DualModeEngine(app, store,
                             dataclasses.replace(cfg, scheme="lock",
                                                 use_pallas=False))
        outs, vals = eng.run_stream(store.values, events, INTERVAL)
        return outs, jax.device_get(vals)


def serve(eng, cfg, source, clock):
    """A warm run that compiles every program the phase needs (chunk,
    output, snapshot), then the measured run on the same shapes.  The
    replay source yields the same arrivals on every pass."""
    from repro.runtime.service import StreamService
    c0 = clock.total
    shutil.rmtree(cfg.ckpt_dir, ignore_errors=True)
    StreamService(eng, cfg).run(source, max_intervals=SNAPSHOT_EVERY)
    c1 = clock.total
    shutil.rmtree(cfg.ckpt_dir, ignore_errors=True)
    run = StreamService(eng, cfg).run(source)
    check(run.snapshots, "no snapshot was taken")
    pct = run.latency_percentiles((50, 99))
    return run, dict(compile_s=c1 - c0,
                     compile_s_in_window=clock.total - c1,
                     events_per_s=run.sustained_events_per_s(),
                     p50_ms=pct["p50"] * 1e3, p99_ms=pct["p99"] * 1e3,
                     snapshots=len(run.snapshots))


def service_cfg(name, controller=None):
    from repro.runtime.service import ServiceConfig
    return ServiceConfig(
        punct_interval=INTERVAL, chunk_intervals=CHUNK,
        snapshot_every=SNAPSHOT_EVERY, keep_last=2, controller=controller,
        ckpt_dir=os.path.join(ROOT, "results", "chip_smoke", name))


def report(name, device, metrics):
    line = dict(phase=name, device_kind=device.device_kind, **metrics,
                peak_bytes_in_use=peak_bytes(device))
    print("[phase] " + json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------
def phase_gs(device, clock, seed):
    from repro.apps import GS
    from repro.core.intervals import ReplaySource
    from repro.core.scheduler import DualModeEngine, EngineConfig
    cfg = EngineConfig(scheme="tstream")
    source = ReplaySource(GS.gen_events, N_INTERVALS * INTERVAL, seed=seed)
    eng = DualModeEngine(GS, GS.make_store(), cfg)
    run, m = serve(eng, service_cfg("gs"), source, clock)
    ref_outs, ref_vals = oracle(GS, cfg, source.in_order_events)
    compare("gs vs lock oracle", run.outputs, run.final_values, ref_outs,
            ref_vals, ORACLE_TOL)
    report("gs", device, m)
    return source, run


def phase_sl(device, clock, seed):
    from repro.apps import SL
    from repro.core.intervals import ReplaySource
    from repro.core.scheduler import DualModeEngine, EngineConfig
    cfg = EngineConfig(scheme="tstream", abort_repass=True)
    source = ReplaySource(SL.gen_events, N_INTERVALS * INTERVAL, seed=seed)
    eng = DualModeEngine(SL, SL.make_store(), cfg)
    run, m = serve(eng, service_cfg("sl"), source, clock)
    ref_outs, ref_vals = oracle(SL, cfg, source.in_order_events)
    compare("sl vs lock oracle", run.outputs, run.final_values, ref_outs,
            ref_vals, ORACLE_TOL)
    rejected = sum(int(o["rejected"].sum()) for o in run.outputs)
    report("sl_abort_repass", device, dict(m, rejected_transfers=rejected))


def phase_pallas(device, clock, source, gs_run):
    import numpy as np
    from repro.apps import GS
    from repro.core.scheduler import DualModeEngine, EngineConfig
    from repro.kernels.runtime import default_interpret, tpu_kernels_in
    check(not default_interpret(), "Pallas kernels would run interpreted")
    cfg = EngineConfig(scheme="tstream", use_pallas=True)
    store = GS.make_store()
    eng = DualModeEngine(GS, store, cfg)
    c0 = clock.total
    want = eng.pallas_kernels(INTERVAL)
    check(want, "the resolved rung runs no Pallas kernel")
    events = source.in_order_events
    batched = {k: np.asarray(v[:CHUNK * INTERVAL]).reshape(
        (CHUNK, INTERVAL) + v.shape[1:]) for k, v in events.items()}
    found = tpu_kernels_in(eng.chunk_lowered_text(eng.carry_in(store.values),
                                                  batched))
    print(f"[pallas] interpret=False rung kernels {list(want)}; "
          f"tpu_custom_call in the chunk program: {dict(found)}",
          flush=True)
    missing = [k for k in want if not found[k]]
    check(not missing, f"kernels missing from the chunk program: {missing}")
    c_lowered = clock.total - c0
    run, m = serve(eng, service_cfg("gs_pallas"), source, clock)
    m["compile_s"] += c_lowered
    compare("gs pallas vs gs xla", run.outputs, run.final_values,
            gs_run.outputs, gs_run.final_values, PALLAS_TOL)
    report("gs_pallas", device, dict(m, kernels=dict(found)))


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------
def phase_four_chips(devices, clock, seed):
    import jax
    from repro.apps import ALL_APPS
    from repro.core.intervals import PhasedReplaySource
    from repro.core.scheduler import DualModeEngine, EngineConfig
    from repro.core.sharded_stream import stream_mesh
    from repro.runtime.controller import ControllerConfig
    from repro.runtime.service import StreamService

    mesh = stream_mesh((4,), ("dev",), devices=devices[:4])
    # reshard-only controller: every other knob's lattice is empty
    ctl = ControllerConfig(window=2, sustain=2, cooldown=8,
                           slack_widen=False, reshard_imbalance=3.0,
                           reshard_max_moves=24)
    cases = (("gs", EngineConfig(scheme="tstream")),
             ("sl", EngineConfig(scheme="tstream", abort_repass=True)))
    for name, cfg in cases:
        app = ALL_APPS[name]
        # calm -> hot head aligned on one owner -> calm
        hot = dict(theta=2.5, align_mod=4)
        quarter = N_INTERVALS // 4 * INTERVAL
        source = PhasedReplaySource(
            app.gen_events, [(quarter, {}), (2 * quarter, hot),
                             (quarter, {})], seed=seed)
        store = app.make_store()
        with jax.default_device(devices[0]):
            ref_outs, ref_vals = DualModeEngine(app, store, cfg).run_stream(
                store.values, source.in_order_events, INTERVAL)
            ref_vals = jax.device_get(ref_vals)
        eng = DualModeEngine(app, store, cfg, mesh=mesh, exchange_slack=8.0)
        scfg = service_cfg(f"{name}_4chips", controller=ctl)
        shutil.rmtree(scfg.ckpt_dir, ignore_errors=True)
        c0, t0 = clock.total, time.perf_counter()
        run = StreamService(eng, scfg).run(source)
        wall = time.perf_counter() - t0
        place = run.stats["placement"]
        check(place["migrations"], f"{name}: no migration fired: {place}")
        check(place["moved_rows"] > 0, f"{name}: migration moved no rows")
        check(not run.stats["drops"]["exchange"],
              f"{name}: the exchange dropped ops")
        compare(f"{name} 4 chips vs 1 chip", run.outputs, run.final_values,
                ref_outs, ref_vals, tol=None)
        pct = run.latency_percentiles((50, 99))
        report(f"{name}_4chips", devices[0], dict(
            n_devices=4, migrations=len(place["migrations"]),
            moved_rows=place["moved_rows"], snapshots=len(run.snapshots),
            compile_s=clock.total - c0, wall_s_incl_compile=wall,
            p50_ms=pct["p50"] * 1e3, p99_ms=pct["p99"] * 1e3,
            bitwise_equal_1chip=True))


def main(argv=None):
    args = parse_args(argv)
    env = os.environ.get("JAX_PALLAS_INTERPRET", "").strip().lower()
    if env and env not in ("0", "false", "no", "off"):
        sys.exit(f"chip_smoke: JAX_PALLAS_INTERPRET={env} would run the "
                 f"kernels interpreted; unset it")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import setup_compile_cache
    cache = setup_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices: platform={dev.platform} "
          f"device_kind={dev.device_kind} count={len(devices)}; "
          f"compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (platform {dev.platform!r}); "
                 f"nothing was run")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: {need} chips needed, {len(devices)} found")

    clock = CompileClock()
    if args.four_chips:
        phase_four_chips(devices, clock, args.seed)
    else:
        gs_source, gs_run = phase_gs(dev, clock, args.seed)
        phase_sl(dev, clock, args.seed)
        phase_pallas(dev, clock, gs_source, gs_run)
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev.platform, kind=dev.device_kind, count=len(devices)))))


if __name__ == "__main__":
    main()
