"""Share of the all-to-all ops' device time that the bytes the committed
events must move between chips would take at the chip's interconnect
bandwidth: the least time (bytes from ``bench/work/exchange.py``, one
chip's share, at ``bench/ici.json``'s bandwidth) over the device time of
the ops whose own opcode is an all-to-all (``benchlib/collectives.py``),
per chip.  Nothing on one chip."""
import os

from benchlib.collectives import all_to_all_s
from benchlib.spec import BENCH, load_module


def read(rec):
    s = all_to_all_s(rec)
    if not s or not rec.committed or rec.peaks is None or rec.chips < 2:
        return None
    work = load_module(os.path.join(BENCH, "work", "exchange.py"),
                       "bench_work_exchange")
    bytes_per_chip = (rec.committed * work.event_bytes(rec.cfg, rec.chips)
                      / rec.chips)
    least = bytes_per_chip / work.ici_bytes_per_s(rec.peaks)
    return least / s * 100.0
