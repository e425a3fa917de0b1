import os
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=8"]))

"""Fig. 10 sharded worker (subprocess: 8 placeholder devices).

Multi-partition transactions on the fused sharded streaming path: GS
streams across mp_ratio / mp_len on a shared-nothing 8-device mesh, with
measured events/sec and exchange drop accounting.  Multi-partition
transactions are exactly the workload where owner routing fans one
transaction's ops out to several shards, so exchange padding pressure
rises with mp_ratio — the drop counters make that visible rather than
silent.  One engine is compiled once and reused across the grid (all
streams share shapes).  Prints JSON rows.
"""
import json
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps import GS                                       # noqa: E402
from repro.core.scheduler import DualModeEngine, EngineConfig   # noqa: E402
from repro.core.sharded_stream import stream_mesh               # noqa: E402


def main():
    quick = "--full" not in sys.argv
    n_events = 1024 if quick else 4096
    interval = 256
    n_partitions = 16
    mesh = stream_mesh((8,), ("dev",))
    store = GS.make_store()
    eng = DualModeEngine(GS, store, EngineConfig(), mesh=mesh,
                        layout="shared_nothing", exchange_slack=4.0)
    ref = DualModeEngine(GS, store, EngineConfig())

    rows = []

    def measure(tag, **gen_kw):
        rng = np.random.default_rng(10)
        stream = GS.gen_events(rng, n_events, n_partitions=n_partitions,
                               **gen_kw)
        _, vals_ref = ref.run_stream(store.values, stream, interval,
                                     fused=True)
        outs, vals = eng.run_stream(store.values, stream, interval)
        jax.block_until_ready(vals)
        t0 = time.perf_counter()
        for _ in range(3):
            outs, vals = eng.run_stream(store.values, stream, interval)
            jax.block_until_ready(vals)
        secs = (time.perf_counter() - t0) / 3
        st = eng.last_exchange_stats
        rows.append(dict(
            fig=tag, app="gs", scheme="tstream_sharded",
            layout="shared_nothing", mesh="1x8",
            events_per_s=n_events / secs, wall_s=secs,
            dropped=int(np.sum(st["dropped"])),
            exchange_capacity=int(st["capacity"]),
            bit_identical=bool(np.array_equal(np.asarray(vals),
                                              np.asarray(vals_ref))),
            **gen_kw))

    for mp_ratio in [0.0, 0.25, 0.5, 0.75, 1.0]:
        measure("fig10a", mp_ratio=mp_ratio, mp_len=6)
    for mp_len in [2, 4, 6, 8, 10]:
        measure("fig10b", mp_ratio=0.5, mp_len=mp_len)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
