"""The exchange's readers and work model, for the 4-chip cell
``gs_mp4.backlog``: ``all_to_all_us_per_event.backlog``,
``exchange_device_pct.backlog`` and ``collective_roofline_pct.backlog``
on a synthetic four-chip trace, each ``None`` on a one-chip record, and
``bench/work/exchange.py`` against a count of the accesses that cross
chips in generated traffic."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from benchlib import collectives, optable, spec  # noqa: E402
from benchlib.devtrace import DeviceTrace  # noqa: E402
from benchlib.record import RunRecord  # noqa: E402
from benchlib.traffic import block_rng  # noqa: E402

CELL = "gs_mp4.backlog"
SHARDED = "jit_tstream_chunk_sharded"


def gs_mp4():
    with open(os.path.join(BENCH, "configs", "gs_mp4.json")) as f:
        return json.load(f)


def work():
    return spec.load_module(os.path.join(BENCH, "work", "exchange.py"),
                            "bench_work_exchange")


def record(device, cell=CELL, chips=4, committed=4000):
    return RunRecord(
        cell=cell, cfg=gs_mp4(), traffic={}, chips=chips, seconds=1.0,
        t0=10.0, t1=11.0, setup_s=1.0, committed=committed, interval=500,
        device=device, peaks=spec.peaks("TPU v5 lite"))


def four_chips():
    """Each chip runs one chunk program: owner bucketing, a synchronous
    all-to-all and the relayout copy of its result, an evaluate inside a
    while, an asynchronous all-to-all back and the result scatter; then
    the output program.  Chip d's forward all-to-all takes d ms more."""
    ops, modules = {}, {}
    for d in range(4):
        dev = f"/device:TPU:{d}"
        modules[dev] = [(f"{SHARDED}(9)", 10.10, 10.20),
                        ("jit_tstream_outputs(8)", 10.20, 10.21)]
        a2a = 0.01 + d * 0.001
        ops[dev] = [
            ("%fusion.1 = s32[4,4,8]{2,1,0} fusion(s32[32]{0} %p), "
             "calls=%f1", 10.10, 10.12),
            ("%all_to_all.3 = s32[4,4,8]{2,0,1} all-to-all(s32[4,4,8]"
             "{2,1,0} %fusion.1), channel_id=1, replica_groups={{0,1,2,3}}",
             10.12, 10.12 + a2a),
            ("%copy.4 = s32[4,4,8]{2,1,0} copy(s32[4,4,8]{2,0,1} "
             "%all_to_all.3)", 10.135, 10.145),
            ("%while.5 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
             "body=%b", 10.145, 10.175),
            ("%fusion.6 = f32[4,8]{1,0} fusion(s32[4,4,8]{2,1,0} %copy.4), "
             "calls=%f6", 10.145, 10.175),
            ("%all-to-all-start.7 = ((f32[4,8]{1,0}), f32[4,8]{1,0}) "
             "all-to-all-start(f32[4,8]{1,0} %fusion.6)", 10.175, 10.18),
            ("%all-to-all-done.7 = f32[4,8]{1,0} all-to-all-done(((f32[4,8]"
             "{1,0}), f32[4,8]{1,0}) %all-to-all-start.7)", 10.185, 10.19),
            ("%fusion.8 = f32[32]{0} fusion(f32[4,8]{1,0} "
             "%all-to-all-done.7), calls=%f8", 10.19, 10.20),
            ("%fusion.1 = f32[8] fusion(%p), calls=%f1", 10.20, 10.21),
        ]
    return DeviceTrace(ops, modules, 0.0, [])


TABLE = {SHARDED: ({"fusion.1": "exchange", "all_to_all.3": "exchange",
                    "copy.4": "exchange", "fusion.6": "evaluate",
                    "all-to-all-start.7": "exchange",
                    "all-to-all-done.7": "exchange",
                    "fusion.8": "exchange"}, ["while.5"])}


def one_chip():
    dev = "/device:TPU:0"
    return DeviceTrace(
        {dev: [("%fusion.1 = f32[8] fusion(%p), calls=%f1", 10.1, 10.2),
               ("%copy.3 = f32[8] copy(f32[8] %fusion.1)", 10.2, 10.3)]},
        {dev: [("jit_tstream_chunk(77)", 10.1, 10.3)]}, 0.0, [])


def write_spans(out, cell, tables):
    os.makedirs(os.path.join(out, cell))
    events = [dict(name="op_phases", ph="M", ts=0, pid=1, tid=0,
                   args=dict(name=p, phases=ph, containers=c))
              for p, (ph, c) in tables.items()]
    with open(os.path.join(out, cell, "spans.json"), "w") as f:
        json.dump(events, f)


@pytest.fixture
def out(tmp_path, monkeypatch):
    out = str(tmp_path / "out")
    monkeypatch.setattr(optable, "OUT", out)
    write_spans(out, CELL, TABLE)
    write_spans(out, "gs_paper.backlog", {"jit_tstream_chunk": (
        {"fusion.1": "restructure", "copy.3": "commit"}, [])})
    return out


def test_opcode_is_the_ops_own():
    assert collectives.opcode(
        "%all_to_all.3 = s32[4]{0:T(128)S(1)} all-to-all(s32[4]{0} %c), "
        "channel_id=1") == "all-to-all"
    assert collectives.opcode(
        "%copy.4 = s32[4]{0} copy(s32[4]{0} %all_to_all.3)") == "copy"
    assert collectives.opcode(
        "%all-to-all-start.7 = ((f32[4]{0}), f32[4]{0}) all-to-all-start("
        "f32[4]{0} %f)") == "all-to-all-start"
    assert collectives.opcode("jit_tstream_chunk(7)") == ""


def test_all_to_all_us_per_event_reads_the_ops_own_opcode(out):
    # the forward all-to-all (mean over chips 0.0115) and the pair back
    # from its start's beginning to its done's end (10.175 -> 10.19); not
    # the relayout copy nor the fusions that read their results
    rec = record(four_chips())
    assert collectives.all_to_all_s(rec) == pytest.approx(0.0265)
    got = spec.reader("all_to_all_us_per_event.backlog")(rec)
    assert got == pytest.approx(0.0265 / 4000 * 1e6)


@pytest.mark.parametrize("start,done", [
    ("all-to-all-start", "all-to-all-done"),
    ("async-start", "async-done")])
def test_async_all_to_all_is_timed_from_start_to_done(start, done):
    def row(name, op, operand, s, e):
        return (f"%{name} = ((f32[8]{{0}}), f32[8]{{0}}) {op}(f32[8]{{0}} "
                f"%{operand}), calls=%c", s, e)
    rows = [row("all-to-all-start.1", start, "fusion.0", 10.1, 10.11),
            row("fusion.2", "fusion", "fusion.0", 10.11, 10.2),
            row("all-to-all-start.3", start, "fusion.2", 10.2, 10.21),
            row("all-to-all-done.1", done, "all-to-all-start.1", 10.3, 10.31),
            row("all-to-all-done.3", done, "all-to-all-start.3", 10.4, 10.5),
            # begun before the traced part, and one not done inside it
            row("all-to-all-done.9", done, "all-to-all-start.9", 9.9, 10.05),
            row("all-to-all-start.4", start, "fusion.2", 10.9, 10.91)]
    got = collectives.intervals(rows, 10.0, 11.0)
    assert sorted(got) == pytest.approx(
        [(10.0, 10.05), (10.1, 10.31), (10.2, 10.5), (10.9, 11.0)])
    rec = record(DeviceTrace({"/device:TPU:0": rows}, {}, 0.0, []))
    # the two pairs in flight together count once
    assert collectives.all_to_all_s(rec) == pytest.approx(
        0.05 + 0.4 + 0.1)


def test_exchange_device_pct_is_the_exchange_phase_share(out):
    # summed over the 4 chips: exchange 0.02 + (0.01 + d ms) + 0.01 +
    # 0.005 + 0.005 + 0.01, evaluate 0.03; the while is its body's, the
    # output program's op is not the chunk program's
    rec = record(four_chips())
    by_phase = optable.phase_seconds(rec, optable.op_phases(CELL))
    assert by_phase == pytest.approx(dict(exchange=0.246, evaluate=0.12))
    got = spec.reader("exchange_device_pct.backlog")(rec)
    assert got == pytest.approx(0.246 / 0.366 * 100)


def test_collective_roofline_pct_is_least_time_over_all_to_all_time(out):
    rec = record(four_chips())
    # the ops' own opcodes: forward 0.0115 (mean), the pair back 0.015
    # from start to done; the relayout copy and the scatter are not
    # all-to-alls
    assert collectives.all_to_all_s(rec) == pytest.approx(0.0265)
    # 4,000 events x 10 accesses x 3/4 off-chip x 12 bytes, a quarter per
    # chip, at 200e9 bytes/s
    least = 4000 * 10 * 0.75 * 12 / 4 / 200e9
    got = spec.reader("collective_roofline_pct.backlog")(rec)
    assert got == pytest.approx(least / 0.0265 * 100)
    assert 0 < got < 100


@pytest.mark.parametrize("name", ["all_to_all_us_per_event.backlog",
                                  "exchange_device_pct.backlog",
                                  "collective_roofline_pct.backlog"])
def test_exchange_readers_read_nothing_on_one_chip(out, name):
    rec = record(one_chip(), cell="gs_paper.backlog", chips=1)
    assert optable.phase_seconds(rec, optable.op_phases(rec.cell))
    assert spec.reader(name)(rec) is None


def test_work_model_matches_a_count_of_crossing_accesses():
    cfg = gs_mp4()
    w = work()
    assert w.event_bytes(cfg, 4) == pytest.approx(10 * 0.75 * (8 + 4))
    assert w.event_bytes(cfg, 1) == 0.0
    assert w.ici_bytes_per_s(spec.peaks("TPU v5 lite")) == 200e9
    # count them in generated traffic: an interval's events are dealt to
    # the chips in equal runs, and a key's owner is its partition
    sys.path.insert(0, os.path.join(BENCH, "gen"))
    gen = spec.load_module(os.path.join(BENCH, "gen", "gs.py"), "bench_g")
    ev = gen.Sampler(cfg).events(block_rng(2 ** 31 + 5, 0, 0), 40 * 500)
    chips, interval = 4, cfg["punct_interval"]
    chip = (np.arange(len(ev["keys"])) % interval) // (interval // chips)
    owner = ev["keys"] % cfg["n_partitions"]
    crossing = np.sum(owner != chip[:, None])
    counted = crossing * 12 / len(ev["keys"])
    assert counted == pytest.approx(w.event_bytes(cfg, chips), rel=0.02)


def test_ici_lookup_refuses_a_peaks_row_two_kinds_share(monkeypatch):
    w = work()
    row = spec.peaks("TPU v5 lite")
    monkeypatch.setattr(w, "_tables", lambda: (
        {"TPU v5 lite": row, "TPU other": dict(row)},
        {"TPU v5 lite": {"ici_bytes_per_s": 200e9},
         "TPU other": {"ici_bytes_per_s": 1e9}}))
    with pytest.raises(KeyError, match="2 device kinds"):
        w.ici_bytes_per_s(row)
