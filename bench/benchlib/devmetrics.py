"""Readings of the profiler's device trace that several metrics share."""

COLLECTIVES = ("all-to-all", "all_to_all", "alltoall")


def chunk_device_s(rec):
    """Device seconds of the programs the service ran inside the traced
    part, per chip: every ``XLA Modules`` execution.  The chunk program is
    nearly all of it; the output program and copies are the rest.  The
    programs are not told apart by name: a jitted ``functools.partial``
    is named ``jit__unknown`` on the device."""
    if rec.device is None:
        return None
    s = rec.device.seconds(rec.t0, rec.t1, lambda n: True, rows="modules")
    return s or None


def idle_pct(rec):
    if rec.device is None or not rec.device.ops:
        return None
    return (1.0 - rec.device.busy_s(rec.t0, rec.t1) / rec.seconds) * 100.0


def collective_s(rec):
    if rec.device is None:
        return None
    return rec.device.seconds(
        rec.t0, rec.t1, lambda n: any(c in n.lower() for c in COLLECTIVES))
