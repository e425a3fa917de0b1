"""Find every piece of a cell by the names in ``BENCHMARK.json``.

``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/gen/<app>.py``, ``bench/reference/<app>.py``,
``bench/work/<app>.py``, ``bench/metrics/<metric>.py`` and
``bench/peaks.json``.  A piece that is missing is an error.  A
configuration's optional ``service`` object sets further fields of the
program's ``ServiceConfig`` (a watermark policy, a controller) by
:func:`build`.
"""
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path (its name may hold
    dots, as a metric's does)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, spec: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.cfg = _json(root, self.config_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = _json(BENCH, "traffic", self.traffic_name + ".json")
        self.app = self.cfg["app"]

    def module(self, kind: str):
        """``bench/<kind>/<app>.py`` as a module."""
        gen_dir = os.path.join(BENCH, "gen")
        if gen_dir not in sys.path:     # the generators share gen/zipf.py
            sys.path.insert(0, gen_dir)
        return load_module(os.path.join(BENCH, kind, self.app + ".py"),
                           f"bench_{kind}_{self.app}")

    def metrics(self, section: str):
        """Names of the ``section`` metrics this cell reports, in the
        order of ``BENCHMARK.json``."""
        return [m for m in self.spec[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_spec(root: str = ROOT) -> dict:
    return _json(root, "BENCHMARK.json")


def reader(metric: str):
    """The reader of one metric: ``bench/metrics/<metric>.py``'s ``read``."""
    mod = load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                      "bench_metric_" + metric.replace(".", "_"))
    return mod.read


def peaks(device_kind: str) -> dict:
    """The row of ``bench/peaks.json`` for this device; an unknown kind is
    an error, never another row."""
    table = _json(BENCH, "peaks.json")
    rows = table["devices"]
    if device_kind not in rows:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(rows)})")
    return rows[device_kind]


def build(cls, fields: dict):
    """``cls(**fields)`` for a dataclass of the program, from JSON: a dict
    given for a dataclass field builds that dataclass, and a list becomes
    a tuple."""
    import dataclasses
    import typing
    hints = typing.get_type_hints(cls)
    kw = {}
    for k, v in fields.items():
        t = hints.get(k)
        if typing.get_origin(t) is typing.Union:      # Optional[...]
            t = next(a for a in typing.get_args(t) if a is not type(None))
        if isinstance(v, dict) and dataclasses.is_dataclass(t):
            v = build(t, v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[k] = v
    return cls(**kw)
