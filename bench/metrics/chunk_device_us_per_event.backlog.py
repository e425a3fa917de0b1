"""Device time of the service's programs per committed event: every
program execution in the profiler's trace of the traced part (the chunk
program, and the output program at a few tenths of a percent of it),
averaged over the chips."""
from benchlib.devmetrics import chunk_device_s


def read(rec):
    s = chunk_device_s(rec)
    return None if s is None else rec.per_event_us(s)
