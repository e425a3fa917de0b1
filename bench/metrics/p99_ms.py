"""99th percentile of the latency from each event's due time to the
commit of its interval, over every event due inside the window (host
clock)."""
import numpy as np


def read(rec):
    if rec.latency_s is None or not rec.latency_s.size:
        return None
    return float(np.percentile(rec.latency_s, 99)) * 1e3
