"""Time the service's executor thread spends in ``chunk.commit`` and
``snapshot.publish``, per committed event (the program's telemetry
spans, host clock).  It includes the wait for the device's result inside
``device_get``."""


def read(rec):
    if rec.spans is None:
        return None
    return rec.per_event_us(rec.span_seconds(
        ["chunk.commit", "snapshot.publish"], "stream-service-executor"))
