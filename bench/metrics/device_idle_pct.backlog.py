"""Share of the window in which no op ran on the device (1 - the union
of the op intervals in the profiler's trace), averaged over the chips."""
from benchlib.devmetrics import idle_pct


def read(rec):
    return idle_pct(rec)
