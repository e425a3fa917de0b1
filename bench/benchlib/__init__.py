"""The benchmark's own library: specification, traffic, trace reading,
the comparison that decides ``correct``, and the run record that the
metric readers under ``bench/metrics/`` take their numbers from."""
