"""The chip benchmark: one command runs one cell once (see ``run.py``).

Everything here is the yardstick: traffic generation, seeded weights, the plain
references, operation and byte counts, the table of peaks, the trace reduction
and the comparison that decides ``correct``. From ``maggy_tpu`` it takes only
the system under test and its spans, counters and kernel names.
"""
