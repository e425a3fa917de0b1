"""99th percentile of how late the load generator released each arrival
batch after its due time, over the batches due inside the window (host
clock)."""
import numpy as np


def read(rec):
    if rec.gen_lag_s is None or not rec.gen_lag_s.size:
        return None
    return float(np.percentile(rec.gen_lag_s, 99)) * 1e3
