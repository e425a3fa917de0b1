"""Perf gate: fresh smoke bench vs the committed baseline (DESIGN.md §8.3).

Runs the same tiny smoke cells as CI's bench-smoke job (``fused_stream``
and ``restructure`` with ``smoke=True`` — seconds, not minutes) in
process, plus the elastic-resharding storm's smoke cells (peak-phase and
aggregate rows per plan, so a migration-path slowdown shows up in the
gate), matches rows against ``benchmarks/baselines/perf_gate_smoke.json``
by their identifying fields, and reports per-row deltas on the min-wall
estimator.

A row REGRESSES when it is both >``--tolerance`` (default 25%) slower
than baseline AND the absolute delta clears ``--abs-floor-us`` (default
200µs) — the smoke cells are sub-millisecond and jitter by tens of
percent under external load, so a relative threshold alone would cry
wolf.  New rows and rows missing from the fresh run are
reported, never failed.  A baseline recorded on a different
``device_kind`` downgrades every verdict to informational: cross-machine
deltas measure the machines, not the change.

Exit status is 0 unless ``--strict`` is passed AND comparable regressions
exist — CI wires this as a non-blocking report job
(``continue-on-error``), so a regression annotates the PR without
blocking it.  Refresh the committed baseline with ``--update-baseline``
after an intentional perf change (on the CI machine class).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

BASELINE = os.path.join(os.path.dirname(__file__), "baselines",
                        "perf_gate_smoke.json")

# identifying fields (everything measured — wall_s etc. — is excluded);
# together these are unique across the smoke modules' rows (``plan`` and
# ``phase`` identify the elastic-resharding storm cells)
KEY_FIELDS = ("fig", "kind", "app", "scheme", "layout", "interval",
              "n", "n_slots", "n_route", "shape", "fused", "plan",
              "phase")
METRIC = "wall_s"


def row_key(row: dict) -> str:
    return "/".join(f"{k}={row[k]}" for k in KEY_FIELDS if k in row)


def run_smoke(passes: int = 2) -> List[dict]:
    """The bench-smoke cells, in process (fresh side of the A/B).

    Runs the whole suite ``passes`` times and keeps the per-row minimum
    — the smoke cells are sub-millisecond, where a single min-of-3 still
    jitters by tens of percent under external load."""
    from . import fused_stream, reshard_storm, restructure_bench
    best: Dict[str, dict] = {}
    for _ in range(max(1, passes)):
        rows = []
        rows += fused_stream.run(quick=True, smoke=True)
        rows += restructure_bench.run(quick=True, smoke=True)
        for r in rows:
            if METRIC not in r:
                continue
            k = row_key(r)
            if k not in best or r[METRIC] < best[k][METRIC]:
                best[k] = r
    # elastic-resharding storm cells: seconds-scale service runs (one
    # pass — the min-of-N treatment is for the sub-millisecond cells);
    # keep the peak-phase and aggregate rows per plan
    for r in reshard_storm.run(quick=True, smoke=True):
        if METRIC in r and r.get("phase") in ("peak", "all") \
                and r[METRIC] > 0:
            best[row_key(r)] = r
    return list(best.values())


def device_kind() -> str:
    try:
        import jax
        return str(jax.devices()[0].device_kind)
    except Exception:
        return "unknown"


def compare(base: dict, fresh_rows: List[dict], *, tolerance: float,
            abs_floor_s: float) -> Tuple[List[dict], bool]:
    """Per-row verdicts + whether the comparison is device-comparable."""
    comparable = base.get("meta", {}).get("device_kind") == device_kind()
    base_by_key = {row_key(r): r for r in base.get("rows", [])}
    fresh_by_key = {row_key(r): r for r in fresh_rows}
    verdicts = []
    for key, fr in fresh_by_key.items():
        br = base_by_key.get(key)
        if br is None:
            verdicts.append(dict(key=key, verdict="new",
                                 fresh_s=fr[METRIC]))
            continue
        b, f = float(br[METRIC]), float(fr[METRIC])
        ratio = f / b if b > 0 else float("inf")
        regressed = (ratio > 1.0 + tolerance) and (f - b > abs_floor_s)
        improved = (ratio < 1.0 - tolerance) and (b - f > abs_floor_s)
        verdicts.append(dict(
            key=key, base_s=b, fresh_s=f, ratio=ratio,
            verdict=("regressed" if regressed else
                     "improved" if improved else "ok")))
    for key in base_by_key.keys() - fresh_by_key.keys():
        verdicts.append(dict(key=key, verdict="missing",
                             base_s=base_by_key[key][METRIC]))
    return verdicts, comparable


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--tolerance", type=float, default=0.25,
                   help="relative slowdown that counts as a regression")
    p.add_argument("--abs-floor-us", type=float, default=200.0,
                   help="absolute slowdown floor (noise guard)")
    p.add_argument("--strict", action="store_true",
                   help="exit 1 on comparable regressions")
    p.add_argument("--update-baseline", action="store_true",
                   help="record the fresh run as the new baseline")
    p.add_argument("--out", default=None,
                   help="write the verdict report JSON here")
    args = p.parse_args(argv)

    fresh = run_smoke()
    if args.update_baseline:
        payload = dict(meta=dict(device_kind=device_kind(),
                                 metric=METRIC, key_fields=KEY_FIELDS),
                       rows=fresh)
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"perf-gate: baseline updated ({len(fresh)} rows, "
              f"device_kind={payload['meta']['device_kind']!r}) -> "
              f"{args.baseline}")
        return 0

    if not os.path.exists(args.baseline):
        print(f"perf-gate: no baseline at {args.baseline} — run with "
              f"--update-baseline to record one (reporting fresh only)")
        for r in fresh:
            print(f"  {row_key(r)}: {r[METRIC] * 1e6:.1f}us")
        return 0

    with open(args.baseline) as f:
        base = json.load(f)
    verdicts, comparable = compare(
        base, fresh, tolerance=args.tolerance,
        abs_floor_s=args.abs_floor_us * 1e-6)
    n_reg = sum(v["verdict"] == "regressed" for v in verdicts)
    if not comparable:
        print(f"perf-gate: baseline device_kind="
              f"{base.get('meta', {}).get('device_kind')!r} != current "
              f"{device_kind()!r} — verdicts are informational only")
    for v in sorted(verdicts, key=lambda v: v["key"]):
        if v["verdict"] in ("new", "missing"):
            print(f"  [{v['verdict'].upper():9s}] {v['key']}")
        else:
            print(f"  [{v['verdict'].upper():9s}] {v['key']}: "
                  f"{v['base_s'] * 1e6:.1f}us -> {v['fresh_s'] * 1e6:.1f}us "
                  f"({v['ratio']:.2f}x)")
    summary = dict(
        comparable=comparable, regressed=n_reg,
        improved=sum(v["verdict"] == "improved" for v in verdicts),
        ok=sum(v["verdict"] == "ok" for v in verdicts),
        tolerance=args.tolerance, device_kind=device_kind())
    print(f"perf-gate: {json.dumps(summary)}")
    if args.out:
        report = dict(summary=summary, verdicts=verdicts)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    if args.strict and comparable and n_reg:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
