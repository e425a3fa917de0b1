"""Self time of the service's feed on the main thread, per committed
event: ``admission`` less its child ``source.pull`` (the wait for the
traffic source), plus ``assembly`` and ``chunk.submit`` (the program's
telemetry spans, host clock)."""


def read(rec):
    if rec.spans is None:
        return None
    main = "MainThread"
    feed = (rec.span_seconds(["admission", "assembly", "chunk.submit"], main)
            - rec.span_seconds(["source.pull"], main))
    return rec.per_event_us(feed)
