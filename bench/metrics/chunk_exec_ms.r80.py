"""Median duration of the service's ``chunk.execute`` spans (dispatch to
commit of one chunk) that start inside the window (host clock)."""
import numpy as np


def read(rec):
    if rec.spans is None:
        return None
    d = rec.span_durations("chunk.execute")
    return float(np.median(d)) * 1e3 if d.size else None
