"""Readings of the control: the plain reference in the program's place,
held in bfloat16 (the precision below the float32 the configurations
state), compared with the float32 reference by the comparison that
decides ``correct``.

    python3 bench/tools/control.py --workload gs_paper.backlog \
        --events 5000000 --seeds 1,2,3

``--events`` is the number of events a window of the cell commits.  The
events and the table come from each seed exactly as a run draws them.
Prints one JSON line per seed with the numbers compared; the upper
reading of each limit is the smallest the control gives.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from benchlib import compare, snapshots, spec  # noqa: E402
from benchlib.traffic import STREAM_EVENTS, block_rng  # noqa: E402


def readings(cell, seed: int, n: int, block: int):
    import ml_dtypes
    cfg = cell.cfg
    gen, ref = cell.module("gen"), cell.module("reference")
    sampler = gen.Sampler(cfg)
    init = gen.initial_table(block_rng(seed, 3, 0), cfg)
    n -= n % (cfg["punct_interval"] * cfg["chunk_intervals"])
    parts = [sampler.events(block_rng(seed, STREAM_EVENTS, b), block)
             for b in range(-(-n // block))]
    events = {k: np.concatenate([p[k] for p in parts])[:n]
              for k in parts[0]}
    iv = cfg["punct_interval"]
    steps = snapshots.expected_steps(n // iv, cfg["snapshot_every"])
    at = [s * iv for s in compare.kept_expected(cfg, steps)]
    ref_out, ref_table, ref_at = ref.run(init, events, n, cfg, at=at)
    low_out, low_table, low_at = ref.run(init, events, n, cfg,
                                         dtype=ml_dtypes.bfloat16, at=at)
    outs = [{k: np.asarray(v[i:i + iv]) for k, v in low_out.items()}
            for i in range(0, n, iv)]
    low_table = np.asarray(low_table, np.float32).reshape(-1, 1)
    snaps = dict(expected=steps, recorded=steps,
                 kept={c // iv: np.asarray(low_at[c], np.float32)[:, None]
                       for c in at},
                 ref={c // iv: ref_at[c] for c in at})
    return compare.compare(cfg, outs, low_table, ref_out, ref_table, n,
                           snaps)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--events", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(spec.load_spec(), args.workload)
    block = int(cell.traffic.get("block_events", 1 << 15))
    for seed in map(int, args.seeds.split(",")):
        t = time.perf_counter()
        checks = readings(cell, seed, args.events, block)
        print(json.dumps(dict(
            workload=args.workload, seed=seed, events=args.events,
            **{k: v["value"] for k, v in checks.items()},
            seconds=time.perf_counter() - t)), flush=True)


if __name__ == "__main__":
    main()
