"""Share of the service programs' device time that the bytes the
committed intervals need would take at the chip's HBM bandwidth: the
least time (bytes from ``bench/work/<app>.py``, bandwidth from
``bench/peaks.json``) over the program executions' device time, per chip.
Bound by memory bandwidth: the work has no arithmetic worth counting."""
from benchlib.devmetrics import chunk_device_s


def read(rec):
    s = chunk_device_s(rec)
    if not s or not rec.committed or rec.peaks is None:
        return None
    bytes_per_chip = rec.committed * rec.work.event_bytes(rec.cfg) / rec.chips
    least = bytes_per_chip / rec.peaks["hbm_bytes_per_s"]
    return least / s * 100.0
