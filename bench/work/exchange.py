"""Bytes the owner-routed exchange has to move between chips, from the
configuration's shapes alone, whatever implements it, and the chip's
interconnect bandwidth (``bench/ici.json``).

Events are spread over the chips by position and each key has one owner
chip, so of an event's ``txn_len`` accesses ``1 - 1/chips`` belong to
another chip, whatever share of the transactions spans partitions.  Each
such access carries its op out (int32 key, float32 value per lane) and
its result back (float32 per lane).
"""
import functools
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def event_bytes(cfg, chips: int) -> float:
    """Bytes that cross chips per committed event, over all the chips."""
    w = cfg["width"]
    per_access = (4 + 4 * w) + 4 * w
    return cfg["txn_len"] * (1.0 - 1.0 / chips) * per_access


@functools.lru_cache(maxsize=None)
def _tables():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        rows = json.load(f)["devices"]
    with open(os.path.join(BENCH, "ici.json")) as f:
        ici = json.load(f)["devices"]
    return rows, ici


def ici_bytes_per_s(peaks: dict) -> float:
    """The interconnect bandwidth of one chip of the device whose row of
    ``bench/peaks.json`` is ``peaks``.  The run record carries that row,
    not the device kind, so the kind is the one row equal to it; a row
    that two kinds share, or a kind missing from ``bench/ici.json``, is
    an error, never another device's figure."""
    rows, ici = _tables()
    kinds = [k for k, row in rows.items() if row == peaks]
    if len(kinds) != 1:
        raise KeyError(f"the peaks row {peaks!r} names {len(kinds)} device "
                       f"kinds in bench/peaks.json ({kinds}), not one")
    if kinds[0] not in ici:
        raise KeyError(f"no interconnect bandwidth for device kind "
                       f"{kinds[0]!r} in bench/ici.json (known: "
                       f"{sorted(ici)})")
    return ici[kinds[0]]["ici_bytes_per_s"]
