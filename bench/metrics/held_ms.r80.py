"""Median, over the chunks the executor committed in the traced part, of
the time each chunk's result was held for its successor: the executor's
``executor.hold`` span, its wait for work while a dispatched chunk is
still uncommitted (the program's telemetry spans, host clock).  A chunk
committed without a hold (the one a snapshot boundary drains) counts 0."""
import numpy as np

EXECUTOR = "stream-service-executor"


def read(rec):
    if rec.spans is None:
        return None

    def inside(name):
        return [e - s for n, th, s, e in rec.spans
                if n == name and th == EXECUTOR and rec.t0 <= s < rec.t1]

    holds = inside("executor.hold")
    if not holds:
        return None
    held = holds + [0.0] * max(0, len(inside("chunk.commit")) - len(holds))
    return float(np.median(held)) * 1e3
