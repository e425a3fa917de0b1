"""Bytes one Grep-and-Sum event needs to move, from the configuration's
shapes alone, whatever implements it: its input columns in (``keys``
int32 and ``values`` float32 per access, ``is_read``), its outputs out
(``sum`` float32, ``ok``), and each touched table row read once and, for
a write event, written once (float32 per lane)."""


def event_bytes(cfg) -> float:
    m, w = cfg["txn_len"], cfg["width"]
    inputs = 4 * m + 4 * m + 1
    outputs = 4 + 1
    rows = 4 * w * m * (1 + (1 - cfg["read_ratio"]))
    return float(inputs + outputs + rows)
