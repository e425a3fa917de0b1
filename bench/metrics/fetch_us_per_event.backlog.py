"""Time the service's executor thread spends in ``chunk.fetch``, per
committed event: the blocking part of ``chunk.commit``, its wait for the
device's result and the copy of outputs, successes and statistics to the
host (the program's telemetry spans, host clock)."""

EXECUTOR = "stream-service-executor"


def read(rec):
    if rec.spans is None or not any(n == "chunk.fetch"
                                    for n, *_ in rec.spans):
        return None
    return rec.per_event_us(rec.span_seconds(["chunk.fetch"], EXECUTOR))
