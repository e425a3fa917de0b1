"""The comparison that decides ``correct``.

The program's outputs of every committed interval and its final table
are compared with the plain reference run over the same events from the
same table.  Two numbers, each with the limit the configuration states
(``limits`` in ``bench/configs/<config>.json``; PERF.md gives the
readings each was set from):

* ``output_gap``: the largest ``|program - reference| / max(|reference|,
  1)`` over every output named in ``compare.outputs`` of every committed
  event, a boolean counting as 0 or 1 (so one flipped flag reads 1);
* ``table_gap``: the same over every row of the final table;
* ``snapshot_steps``: how many snapshot steps are off, counted both in
  the steps the run reports it published and in the steps left on disk:
  every ``snapshot_every`` intervals up to the last committed boundary is
  published, and the newest ``keep_last`` of them are kept (exact, limit
  0);
* ``snapshot_gap``: ``table_gap`` of each kept snapshot, read back from
  disk, against the reference's table at that step; a kept snapshot that
  is missing or damaged reads ``inf``.

A run that committed another number of events than the reference
covered, or whose outputs lack a key, reads ``inf`` on the first two.
"""
import numpy as np


def gap(prog, ref) -> float:
    p = np.asarray(prog, np.float64).reshape(-1)
    r = np.asarray(ref, np.float64).reshape(-1)
    if p.shape != r.shape:
        return float("inf")
    if not p.size:
        return 0.0
    d = np.abs(p - r) / np.maximum(np.abs(r), 1.0)
    d[np.isnan(d)] = np.inf
    return float(d.max())


def concat(outputs, key):
    return np.concatenate([np.asarray(o[key]).reshape(-1) for o in outputs])


def kept_expected(cfg, steps):
    """Of the steps due, those the retention keeps on disk."""
    keep = cfg.get("keep_last", 0)
    return list(steps[-keep:]) if keep else list(steps)


def snapshot_checks(cfg, snaps):
    """``snaps``: ``expected`` (steps due), ``recorded`` (steps the run
    reports), ``kept`` (``{step: values or None}`` read from disk) and
    ``ref`` (``{step: reference table}`` for the kept steps due)."""
    expected = list(snaps["expected"])
    due_kept = kept_expected(cfg, expected)
    off = (len(set(snaps["recorded"]) ^ set(expected))
           + len(set(snaps["kept"]) ^ set(due_kept)))
    gaps = []
    for s in due_kept:
        v = snaps["kept"].get(s)
        gaps.append(float("inf") if v is None
                    else gap(np.asarray(v)[:, 0], snaps["ref"][s]))
    return off, max(gaps, default=0.0)


def compare(cfg, outputs, table, ref_out, ref_table, n_events, snaps):
    """Returns ``{number: {"value": v, "limit": l}}`` in a fixed order."""
    keys, limits = cfg["compare"]["outputs"], cfg["limits"]
    if (len(outputs) * cfg["punct_interval"] != n_events
            or any(not set(keys) <= set(o) for o in outputs)):
        out_gap = table_gap = float("inf")
    else:
        out_gap = max([gap(concat(outputs, k), ref_out[k]) for k in keys],
                      default=0.0)
        table_gap = gap(np.asarray(table)[:, 0], ref_table)
    steps_off, snap_gap = snapshot_checks(cfg, snaps)
    return {"output_gap": dict(value=out_gap, limit=limits["output_gap"]),
            "table_gap": dict(value=table_gap, limit=limits["table_gap"]),
            "snapshot_steps": dict(value=steps_off,
                                   limit=limits["snapshot_steps"]),
            "snapshot_gap": dict(value=snap_gap,
                                 limit=limits["snapshot_gap"])}


def passed(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
