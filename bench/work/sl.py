"""Bytes one Streaming Ledger event needs to move, from the
configuration's shapes alone, whatever implements it: its input columns
in (four keys int32, ``amount`` float32, ``is_transfer``), its outputs out
(``ok``, ``src_balance`` float32, ``rejected``), and each touched balance
row read and written once: two for a deposit, four for a transfer."""


def event_bytes(cfg) -> float:
    w = cfg["width"]
    inputs = 4 * 4 + 4 + 1
    outputs = 1 + 4 + 1
    tr = cfg["transfer_ratio"]
    rows = 2 * 4 * w * (2 * (1 - tr) + 4 * tr)
    return float(inputs + outputs + rows)
