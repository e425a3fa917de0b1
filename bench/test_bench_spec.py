"""``BENCHMARK.json`` keeps to the benchmark's contract, and every piece a
cell names is where the harness looks for it."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_sizes(spec):
    assert set(spec) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(spec["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p.split("/") for p in
               spec["paths"])
    assert len(spec["command"]) <= 32 and all(line(w)
                                              for w in spec["command"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    for c in spec["configs"]:
        assert set(c) == KEYS["config"]
    for w in spec["workloads"]:
        assert set(w) == KEYS["workload"]
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == KEYS["end_to_end"]
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == KEYS["per_layer"]


def test_names_units_and_lines(spec):
    entries = (spec["configs"] + spec["workloads"] + spec["end_to_end"]
               + spec["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[section]]
        assert len(names) == len(set(names)), section
    for w in spec["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
    for c in spec["configs"]:
        assert line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"])


def test_every_piece_is_found(spec):
    used = {w["config"] for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    assert set(configs) == used
    for c in spec["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        for kind in ("gen", "reference", "work"):
            assert os.path.isfile(os.path.join(BENCH, kind,
                                               cfg["app"] + ".py"))
        assert set(cfg["limits"]) == {"output_gap", "table_gap",
                                      "snapshot_steps", "snapshot_gap"}
    for w in spec["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_every_cell_reports_what_it_must(spec):
    cells = {w["name"]: w for w in spec["workloads"]}

    def reports(m, cell):
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
    for cell in cells:
        assert sum(reports(m, cell) for m in e2e.values()) >= 2
        assert any(reports(m, cell) for m in spec["per_layer"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)


def test_every_data_file_is_read_by_the_harness():
    """Configuration and traffic files, in a cell or kept for one, are
    what the harness's general readers take."""
    import sys
    sys.path.insert(0, BENCH)
    from benchlib import spec as bspec
    from benchlib.assembly import check_policy
    from benchlib.traffic import phases_of
    from repro.runtime.service import ServiceConfig
    for name in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", name)) as f:
            cfg = json.load(f)
        assert name == cfg["name"] + ".json"
        assert line(cfg["source"]) and isinstance(cfg["reduced"], list)
        fields = {k: cfg[k] for k in ("punct_interval", "chunk_intervals",
                                      "queue_intervals", "admission",
                                      "snapshot_every", "keep_last")}
        fields.update(cfg.get("service", {}), ckpt_dir="unused")
        scfg = bspec.build(ServiceConfig, fields)
        assert scfg.snapshot_every == cfg["snapshot_every"]
        check_policy(cfg.get("service", {}).get("watermark", {}))
    for name in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            traffic = json.load(f)
        assert traffic["mode"] in ("backlog", "open") and line(traffic["why"])
        if traffic["mode"] == "open":
            assert phases_of(traffic)


def test_peaks_table_has_a_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert line(table["source"])
    assert table["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
