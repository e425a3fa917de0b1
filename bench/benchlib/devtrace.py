"""Reading the profiler's device trace into intervals on the host clock.

``jax.profiler`` writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``;
``ProfileData`` reads it with nothing but JAX.  Device planes are named
``/device:<KIND>:<n>``; on a TPU each has an ``XLA Ops`` line (one event
per executed HLO op, with the ``hlo_module`` it belongs to) and an
``XLA Modules`` line (one event per executed program).  The harness opens
the window with a host ``TraceAnnotation`` named :data:`MARK` whose
host-clock time it knows, which puts both clocks on one axis.
"""
import glob
import os

import numpy as np

MARK = "bench.window.open"


def xplane_path(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


class DeviceTrace:
    """Ops and program executions of each device, in host-clock seconds.

    ``ops[d]`` and ``modules[d]`` are lists of ``(name, start, end)`` for
    device ``d``; a program execution is named by its module."""

    def __init__(self, ops, modules, t_mark_offset, planes):
        self.ops, self.modules = ops, modules
        self.offset = t_mark_offset
        self.planes = planes

    @classmethod
    def load(cls, path: str, t_mark: float):
        from jax.profiler import ProfileData
        with open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
        mark_ns = None
        ops, modules, planes = {}, {}, []
        for plane in pd.planes:
            lines = list(plane.lines)
            planes.append((plane.name, [(ln.name, sum(1 for _ in ln.events))
                                        for ln in lines]))
            if plane.name.startswith("/host") and mark_ns is None:
                # the mark opens the trace: among the first events of its
                # thread's line
                for ln in lines:
                    for i, ev in enumerate(ln.events):
                        if ev.name == MARK:
                            mark_ns = ev.start_ns
                        if i >= 64 or mark_ns is not None:
                            break
            if not plane.name.startswith("/device:"):
                continue
            dev = plane.name
            for ln in lines:
                if ln.name not in ("XLA Ops", "XLA Modules"):
                    continue
                rows = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in ln.events]
                (ops if ln.name == "XLA Ops" else modules)[dev] = rows
        if mark_ns is None:
            raise ValueError(f"no {MARK!r} annotation in the trace")
        offset = t_mark - mark_ns * 1e-9
        conv = lambda rows: [(n, s * 1e-9 + offset, e * 1e-9 + offset)
                             for n, s, e in rows]
        return cls({d: conv(r) for d, r in ops.items()},
                   {d: conv(r) for d, r in modules.items()}, offset, planes)

    @property
    def devices(self):
        return sorted(self.ops)

    @staticmethod
    def clip(rows, t0, t1):
        return [(n, max(s, t0), min(e, t1)) for n, s, e in rows
                if e > t0 and s < t1]

    @staticmethod
    def union(rows) -> list:
        """Merged busy intervals ``[(start, end)]`` of a list of rows."""
        iv = sorted((s, e) for _, s, e in rows if e > s)
        out = []
        for s, e in iv:
            if out and s <= out[-1][1]:
                if e > out[-1][1]:
                    out[-1][1] = e
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    def busy_s(self, t0, t1) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return float(np.mean([
            sum(e - s for s, e in self.union(self.clip(r, t0, t1)))
            for r in self.ops.values()]))

    def seconds(self, t0, t1, match, rows="ops") -> float:
        """Summed durations of the ops (or, with ``rows="modules"``, the
        program executions) whose name ``match`` accepts, averaged over
        the devices."""
        table = self.ops if rows == "ops" else self.modules
        if not table:
            return 0.0
        return float(np.mean([
            sum(e - s for n, s, e in self.clip(r, t0, t1) if match(n))
            for r in table.values()]))

    def top_ops(self, t0, t1, k=10):
        tot = {}
        for r in self.ops.values():
            for n, s, e in self.clip(r, t0, t1):
                tot[n] = tot.get(n, 0.0) + (e - s)
        nd = max(len(self.ops), 1)
        return sorted(([n, v / nd] for n, v in tot.items()),
                      key=lambda x: -x[1])[:k]

    def top_modules(self, t0, t1, k=10):
        tot = {}
        for r in self.modules.values():
            for n, s, e in self.clip(r, t0, t1):
                tot[n] = tot.get(n, 0.0) + (e - s)
        return sorted(([n, v] for n, v in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, t0, t1, k=10):
        """The ``k`` longest gaps ``(start, end)`` of the first device in
        which no op ran."""
        if not self.ops:
            return []
        busy = self.union(self.clip(self.ops[self.devices[0]], t0, t1))
        gaps, prev = [], t0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if t1 > prev:
            gaps.append((prev, t1))
        return sorted(gaps, key=lambda g: g[0] - g[1])[:k]
