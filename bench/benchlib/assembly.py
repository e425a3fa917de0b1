"""Plain reference of how arrivals become punctuation intervals.

The service's stated watermark semantics: the watermark starts at minus
infinity and, after each arrival batch, advances to the largest event
time seen less ``allowed_lateness``.  An event whose time lies below the
watermark as it stood when its batch arrived is late; with
``late="reroute"`` it takes that watermark as its time, so it joins the
earliest interval still open.  Intervals are cut from the events ordered
by (that time, arrival position), ``interval`` at a time, and run in that
order: it is the serial order the results must equal.

This module imports nothing of the program.  Only ``reroute`` is covered:
a run whose configuration drops late events is refused before it starts.
"""
import numpy as np


def check_policy(watermark: dict):
    late = (watermark or {}).get("late", "reroute")
    if late != "reroute":
        raise ValueError(f"the reference covers late='reroute' only, not "
                         f"{late!r}")


def emission_order(times, batch_ends, allowed_lateness: int):
    """Arrival positions in the order the intervals run them.

    ``times``: event time of each arrival, in arrival order; ``batch_ends``:
    the stream position after each arrival batch the service took."""
    times = np.asarray(times, np.int64)
    n = times.size
    ends = np.asarray(batch_ends, np.int64)
    ends = ends[ends <= n]
    if not ends.size or ends[-1] != n:
        ends = np.append(ends, n)
    starts = np.concatenate([[0], ends[:-1]])
    batch_max = np.maximum.reduceat(times, starts)
    advanced = np.maximum.accumulate(batch_max - int(allowed_lateness))
    wm = np.concatenate([[np.iinfo(np.int64).min], advanced[:-1]])
    wm_at = np.repeat(wm, ends - starts)
    eff = np.where(times < wm_at, wm_at, times)
    return np.lexsort((np.arange(n), eff))
