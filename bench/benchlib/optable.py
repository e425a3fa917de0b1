"""The chunk program's device ops by phase, from a traced run's files.

With a tracer attached, the program writes into its span file
(``bench/out/<cell>/spans.json``) one metadata event ``op_phases`` per
compiled chunk program: ``args.name`` is the program's module name (as a
profiler's ``XLA Modules`` rows show it, before the fingerprint in
brackets), ``args.phases`` maps each op the device runs to the named
scope it runs under, and ``args.containers`` lists the ``while``,
``conditional`` and ``call`` ops, whose time is their bodies'.  A program
without that event (or a span file without it) gives ``None`` here.
"""
import bisect
import json
import os

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out")
CHUNK_PROGRAM = "jit_tstream_chunk"


def read_events(path: str):
    """The events of a span file, or ``None`` if there is none; a file cut
    short by a crash gives the events written before it."""
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        body = f.read().strip()
    try:
        return json.loads(body)
    except ValueError:
        pass
    body = body[1:] if body.startswith("[") else body
    body = body[:-1] if body.endswith("]") else body
    events = []
    for line in body.splitlines():
        line = line.strip().rstrip(",")
        if line:
            try:
                events.append(json.loads(line))
            except ValueError:
                break
    return events


def op_phases(cell: str, out: str = None):
    """``{program: (phases, containers)}`` from the cell's span file, or
    ``None`` without an ``op_phases`` event."""
    events = read_events(os.path.join(out or OUT, cell, "spans.json"))
    tables = {}
    for e in events or ():
        if e.get("ph") == "M" and e.get("name") == "op_phases":
            a = e.get("args") or {}
            phases, containers = tables.setdefault(a.get("name", ""),
                                                   ({}, set()))
            phases.update(a.get("phases") or {})
            containers.update(a.get("containers") or ())
    return tables or None


def op_name(event_name: str) -> str:
    """``%fusion.13 = f32[...] fusion(...)`` -> ``fusion.13``."""
    j = event_name.find(" = ")
    name = event_name if j < 0 else event_name[:j]
    return name[1:] if name.startswith("%") else name


def program_name(module_name: str) -> str:
    """``jit_tstream_chunk(1234)`` -> ``jit_tstream_chunk``."""
    return module_name.split("(", 1)[0]


def phase_seconds(rec, tables, prefix: str = CHUNK_PROGRAM):
    """``{phase: seconds}`` of the leaf ops that ran inside executions of
    the programs whose name starts with ``prefix`` in the traced part,
    summed over the devices (``"unknown"``: an op its program's table
    lacks).  An op belongs to the program execution its start falls in,
    on its device's ``XLA Modules`` line.  A lockstep trace holds
    millions of ops, so this is one pass with nothing copied."""
    if rec.device is None or not tables:
        return None
    t0, t1 = rec.t0, rec.t1
    out = {}
    for dev, ops in rec.device.ops.items():
        runs = sorted((s, e, program_name(n))
                      for n, s, e in rec.device.modules.get(dev, ())
                      if program_name(n).startswith(prefix)
                      and program_name(n) in tables)
        starts = [r[0] for r in runs]
        for name, s, e in ops:
            if e <= t0 or s >= t1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
            phases, containers = tables[runs[i][2]]
            op = op_name(name)
            if op in containers:
                continue
            phase = phases.get(op, "unknown")
            out[phase] = out.get(phase, 0.0) + (min(e, t1) - max(s, t0))
    return out or None
