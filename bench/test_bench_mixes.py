"""Cells that are data only run through the harness unchanged.

Each of these cells is a configuration file and a traffic file under
``bench/``, with no code of its own; the harness runs it on the CPU for a
short window and reads ``correct`` true:

* ``gs_late64.jitter64``: events arrive out of event-time order (each
  displaced by under 64 positions) into a service that allows a lateness
  of 64 and reroutes later rows; the reference cuts the intervals by the
  stated watermark semantics (``benchlib/assembly.py``);
* ``gs_paper.bursts``: on/off Poisson bursts from a list of phases;
* ``gs_storm4.backlog``: Zipf 2.5 aligned on one owner over four virtual
  devices, with the program's reshard controller on.
"""
import test_bench_faults as tf


def test_out_of_order_cell_is_correct(tmp_path):
    got = tf.run_child(tmp_path, "gs_late64.jitter64", ["none"], seconds=2)
    assert got["none"]["correct"], got["none"]["checks"]
    assert got["none"]["info"]["late_rerouted"] > 0


def test_burst_cell_is_correct(tmp_path):
    got = tf.run_child(tmp_path, "gs_paper.bursts", ["none"], seconds=2)
    assert got["none"]["correct"], got["none"]["checks"]
    assert got["none"]["info"]["generator"]["mode"] == "open"


def test_skew_storm_cell_is_correct(tmp_path):
    got = tf.run_child(tmp_path, "gs_storm4.backlog", ["none"], devices=4,
                       seconds=2)
    assert got["none"]["correct"], got["none"]["checks"]
