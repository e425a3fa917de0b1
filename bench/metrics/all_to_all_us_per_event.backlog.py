"""Device time in which the exchange's all-to-all ops ran, per committed
event, averaged over the chips (profiler trace): the ops whose own opcode
is an all-to-all, an asynchronous pair from start to done
(``benchlib/collectives.py``).  Nothing on one chip."""
from benchlib.collectives import all_to_all_s


def read(rec):
    s = all_to_all_s(rec)
    return None if not s else rec.per_event_us(s)
