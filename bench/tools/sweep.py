"""Find the highest open-loop rate a configuration sustains on this chip.

    python3 bench/tools/sweep.py --workload gs_paper.r80 --seed 5 \
        --seconds 10 --fractions 0.7,0.8,0.9,1.0,1.1

Sets the cell's configuration up once, measures what it serves under the
backlog traffic (``bench/traffic/backlog.json``), then serves the cell's
open-loop traffic at each fraction of that rate (or at each of
``--rates``) for ``--seconds``.  The backlog at time ``t`` is the events
due by ``t`` less those committed by ``t``; a rate is sustained when the
backlog grows by less than two chunks of events over the window's second
half.  Prints one JSON line per window.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from benchlib import spec  # noqa: E402


def backlog_growth(b, r):
    """(growth over the second half, backlog at the close) in events."""
    interval = b.cfg["punct_interval"]
    commit_s = np.sort([c["commit_s"] for c in r.commits])
    due = b.t0 + b.traffic.due_s
    ts = np.linspace(b.t0 + b.seconds / 2, b.t1, 11)
    log = (np.searchsorted(due, ts, side="right")
           - interval * np.searchsorted(commit_s, ts, side="right"))
    return int(log[-1] - log[0]), int(log[-1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fractions", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = spec.Cell(spec.load_spec(), args.workload)
    b = run.Bench(cell, args.seed, args.seconds)
    b.build()
    b.warm()
    chunk = b.cfg["punct_interval"] * b.cfg["chunk_intervals"]
    with open(os.path.join(BENCH, "traffic", "backlog.json")) as f:
        backlog = json.load(f)
    rates = [float(x) for x in args.rates.split(",") if x]
    if args.fractions:
        b.make_traffic(backlog, args.seed)
        r = b.window(trace=False)
        served = sum(1 for c in r.commits if c["commit_s"] < b.t1) * \
            b.cfg["punct_interval"] / b.seconds
        print(json.dumps(dict(mode="backlog", events_per_s=served)),
              flush=True)
        rates += [f * served for f in map(float, args.fractions.split(","))]
    for i, rate in enumerate(rates):
        b.make_traffic(dict(cell.traffic, rate_per_s=rate), args.seed + 1 + i)
        t = time.perf_counter()
        r = b.window(trace=False)
        growth, last = backlog_growth(b, r)
        due = b.traffic.due_s
        n = len(r.outputs) * b.cfg["punct_interval"]
        commit_s = np.asarray([c["commit_s"] for c in r.commits])
        iv = np.arange(n) // b.cfg["punct_interval"]
        lat = (commit_s[iv] - (b.t0 + due[:n]))[due[:n] < b.seconds]
        lag = np.asarray(b.traffic.lag_s).reshape(-1, 2)[:, 1]
        print(json.dumps(dict(
            mode="open", rate_per_s=rate, growth_events=growth,
            backlog_at_close=last, sustained=growth < 2 * chunk,
            p50_ms=float(np.percentile(lat, 50)) * 1e3,
            p99_ms=float(np.percentile(lat, 99)) * 1e3,
            gen_lag_p99_ms=float(np.percentile(lag, 99)) * 1e3,
            wall_s=time.perf_counter() - t)), flush=True)


if __name__ == "__main__":
    main()
