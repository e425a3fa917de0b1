"""Sharded streaming benchmark worker (subprocess: 8 placeholder devices).

Measures end-to-end ``run_stream`` events/sec for the owner-routed fused
sharded driver against (a) the single-device fused driver and (b) the
replicate-everything per-batch ``evaluate_sharded`` loop — the path the
exchange replaces — across layouts and device counts, plus per-layout
collective bytes from the compiled HLO.  Prints JSON rows on the last
line; ``benchmarks/sharded_stream.py`` relays them into
``BENCH_sharded_stream.json``.
"""
import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=8"]))

import argparse
import dataclasses
import json
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import ALL_APPS                                # noqa: E402
from repro.core.blotter import build_opbatch                   # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig  # noqa: E402
from repro.core.sharded import evaluate_sharded                # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo              # noqa: E402


def _time(fn, iters):
    fn()  # warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts)), float(np.median(ts))


def stream_fused_sharded(app, store, stream, interval, mesh, layout, slack):
    eng = DualModeEngine(app, store, EngineConfig(), mesh=mesh,
                        layout=layout, exchange_slack=slack)

    def go():
        outs, vals = eng.run_stream(store.values, stream, interval)
        jax.block_until_ready(vals)
    return eng, go


def stream_per_batch(app, store, stream, interval, mesh, layout):
    """The replicate-everything baseline as a stream driver: one jitted
    build + one jitted evaluate_sharded dispatch per interval, state
    carried through the host loop (exactly the pre-exchange cost model:
    O(n_dev*N) replicated op bytes, a restructure sort and an ownership
    permutation per call)."""
    n = len(next(iter(stream.values())))
    n_intervals = n // interval
    batches = [{k: jnp.asarray(np.asarray(v)[i * interval:(i + 1) * interval])
                for k, v in stream.items()} for i in range(n_intervals)]

    @jax.jit
    def build(values, events, ts0):
        st = dataclasses.replace(store, values=values)
        ops, _ = build_opbatch(app, st, events, ts0)
        return ops

    def evl(values, ops):
        st = dataclasses.replace(store, values=values)
        out = evaluate_sharded(st, ops, app.funs, mesh, layout)
        return jnp.concatenate([out, jnp.zeros((1, app.width))])
    evl = jax.jit(evl)

    def go():
        values = store.values
        for i, ev in enumerate(batches):
            ops = build(values, ev, jnp.int32(i * interval))
            values = evl(values, ops)
        jax.block_until_ready(values)
    return go


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    if args.smoke:
        n_events, interval, iters = 256, 64, 2
        meshes = [(stream_mesh((8,), ("dev",)), 8, "1x8")]
    elif args.full:
        n_events, interval, iters = 8192, 512, 7
        meshes = [(stream_mesh((d,), ("dev",)), d, f"1x{d}")
                  for d in (2, 4, 8)]
    else:
        n_events, interval, iters = 2048, 512, 3
        meshes = [(stream_mesh((d,), ("dev",)), d, f"1x{d}")
                  for d in (2, 8)]
    mesh2 = stream_mesh((2, 4), ("socket", "core"))

    app = ALL_APPS["gs"]
    rng = np.random.default_rng(17)
    stream = app.gen_events(rng, n_events)
    store = app.make_store()
    rows = []

    # single-device fused reference (the bit-identity baseline)
    ref = DualModeEngine(app, store, EngineConfig())

    def ref_go():
        outs, vals = ref.run_stream(store.values, stream, interval,
                                    fused=True)
        jax.block_until_ready(vals)
    w_min, w_med = _time(ref_go, iters)
    rows.append(dict(fig="sharded_stream", app="gs", layout="single_device",
                     driver="fused", mesh="1x1", n_dev=1, interval=interval,
                     n_events=n_events, wall_s=w_min, median_wall_s=w_med,
                     events_per_s=n_events / w_min))

    cases = [("shared_nothing", mesh, n_dev, name)
             for mesh, n_dev, name in meshes]
    if not args.smoke:
        cases += [("shared_per_socket", mesh2, 8, "2x4"),
                  ("shared_everything", meshes[-1][0], meshes[-1][1],
                   meshes[-1][2])]

    for layout, mesh, n_dev, mesh_name in cases:
        eng, go = stream_fused_sharded(app, store, stream, interval, mesh,
                                       layout, slack=4.0)
        w_min, w_med = _time(go, iters)
        st = eng.last_exchange_stats
        # per-layout collective bytes from the compiled whole-stream HLO
        batched = {k: jnp.asarray(np.asarray(v)[: (n_events // interval)
                                                * interval].reshape(
            (n_events // interval, interval) + np.asarray(v).shape[1:]))
            for k, v in stream.items()}
        lowered = eng._sharded._impl.lower(
            eng.carry_in(store.values), batched, jnp.int32(0))
        hlo = analyze_hlo(lowered.compile().as_text(), mesh.size)
        rows.append(dict(
            fig="sharded_stream", app="gs", layout=layout,
            driver="fused_sharded", mesh=mesh_name, n_dev=n_dev,
            interval=interval, n_events=n_events, wall_s=w_min,
            median_wall_s=w_med, events_per_s=n_events / w_min,
            dropped=int(np.sum(st["dropped"])),
            exchange_capacity=int(st["capacity"]),
            exchanged_rows_per_device=int(st["exchanged_rows_per_device"]),
            coll_bytes=hlo["coll_bytes"],
            wire_bytes_per_device=hlo["wire_bytes_per_device"]))

        go_pb = stream_per_batch(app, store, stream, interval, mesh, layout)
        w_min, w_med = _time(go_pb, iters)
        rows.append(dict(
            fig="sharded_stream", app="gs", layout=layout,
            driver="per_batch", mesh=mesh_name, n_dev=n_dev,
            interval=interval, n_events=n_events, wall_s=w_min,
            median_wall_s=w_med, events_per_s=n_events / w_min))

    # acceptance summary: fused sharded vs per-batch on shared_nothing@8dev
    f8 = [r for r in rows if r["driver"] == "fused_sharded"
          and r["layout"] == "shared_nothing" and r["n_dev"] == 8]
    p8 = [r for r in rows if r["driver"] == "per_batch"
          and r["layout"] == "shared_nothing" and r["n_dev"] == 8]
    if f8 and p8:
        rows.append(dict(
            fig="sharded_stream", app="gs", layout="shared_nothing",
            driver="summary", mesh="1x8", n_dev=8, interval=interval,
            n_events=n_events,
            fused_sharded_speedup_vs_per_batch=(
                f8[0]["events_per_s"] / p8[0]["events_per_s"]),
            events_per_s=f8[0]["events_per_s"]))
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
