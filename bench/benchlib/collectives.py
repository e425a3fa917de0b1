"""The all-to-all ops of a device trace, told by each op's own opcode.

A profiler's ``XLA Ops`` row is named by its HLO instruction,
``%name = <shape> <opcode>(<operands>), ...``; the operands name the ops
they come from, so matching the whole text would also take every op that
reads an all-to-all's result.  Here only the op itself counts: opcode
``all-to-all``, or an asynchronous pair, ``all-to-all-start`` and
``all-to-all-done`` (or ``async-start``/``async-done`` of an instruction
named for an all-to-all).  A pair's rows each last only their launch and
their wait, so a pair is timed from its start's beginning to its done's
end: the transfer runs between them.
"""
import re

import numpy as np

_NAME = re.compile(r"%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def opcode(event_name: str) -> str:
    """``%a = s32[4]{0} all-to-all(s32[4]{0} %b), ...`` -> ``all-to-all``."""
    return _split(event_name)[1]


def _split(event_name: str):
    """(instruction name, opcode, text from the opcode's operands on)."""
    j = event_name.find(" = ")
    if j < 0:
        return "", "", ""
    m = _NAME.match(event_name)
    rhs = event_name[j + 3:]
    if rhs.startswith("("):             # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rhs = rhs[i + 1:]
    else:                               # a shape holds no space
        k = rhs.find(" ")
        rhs = rhs[k + 1:] if k >= 0 else ""
    op, _, rest = rhs.lstrip().partition("(")
    return (m.group(1) if m else ""), op, rest


def kind(event_name: str) -> str:
    """``sync``, ``start`` or ``done`` for an all-to-all op, else ``""``."""
    name, op, _ = _split(event_name)
    if op == "all-to-all":
        return "sync"
    named = name.replace("_", "-").startswith("all-to-all")
    if op == "all-to-all-start" or (op == "async-start" and named):
        return "start"
    if op == "all-to-all-done" or (op == "async-done" and named):
        return "done"
    return ""


def intervals(rows, t0, t1):
    """``(start, end)`` of each all-to-all among one device's ``(name,
    start, end)`` rows, clipped to ``[t0, t1)``: a synchronous op's own
    row; a pair from its start's beginning to its done's end (found by
    the done's operand), open pairs closed at the clip's edges."""
    out, open_ = [], {}
    for n, s, e in sorted(rows, key=lambda r: r[1]):
        if e <= t0 or s >= t1:
            continue
        k = kind(n)
        if k == "sync":
            out.append((max(s, t0), min(e, t1)))
        elif k == "start":
            open_[_split(n)[0]] = max(s, t0)
        elif k == "done":
            m = _OPERAND.search(_split(n)[2])
            begin = open_.pop(m.group(1), t0) if m else t0
            out.append((begin, min(e, t1)))
    out.extend((s, t1) for s in open_.values())
    return out


def all_to_all_s(rec):
    """Device seconds in which an all-to-all ran in the traced part (the
    union of :func:`intervals`), averaged over the chips; ``None`` without
    a trace or without such ops."""
    if rec.device is None or not rec.device.ops:
        return None
    s = float(np.mean([
        sum(e - b for b, e in rec.device.union(
            [("", b, e) for b, e in intervals(r, rec.t0, rec.t1)]))
        for r in rec.device.ops.values()]))
    return s or None
