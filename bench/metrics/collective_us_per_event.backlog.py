"""Device time of the all-to-all exchange ops per committed event,
averaged over the chips (profiler trace).  Nothing on one chip."""
from benchlib.devmetrics import collective_s


def read(rec):
    s = collective_s(rec)
    return None if not s else rec.per_event_us(s)
