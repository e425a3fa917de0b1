"""How many events per second the load generator makes, per
configuration, on this host (one thread, as in a run).

    python3 bench/tools/capacity.py [config ...]

Prints one JSON line per configuration: events/s over 8 blocks of the
backlog traffic's block size, after one block to warm up.
"""
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import spec  # noqa: E402
from benchlib.traffic import STREAM_EVENTS, block_rng  # noqa: E402


def main(argv):
    bench = spec.load_spec()
    block = json.load(open(os.path.join(BENCH, "traffic",
                                        "backlog.json")))["block_events"]
    names = argv or [c["name"] for c in bench["configs"]]
    for name in names:
        cell = next(w for w in bench["workloads"] if w["config"] == name)
        c = spec.Cell(bench, cell["name"])
        sampler = c.module("gen").Sampler(c.cfg)
        sampler.events(block_rng(1, STREAM_EVENTS, 0), block)
        t = time.perf_counter()
        for b in range(8):
            sampler.events(block_rng(1, STREAM_EVENTS, b + 1), block)
        dt = time.perf_counter() - t
        print(json.dumps(dict(config=name, events_per_s=8 * block / dt)),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
