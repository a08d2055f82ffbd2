"""The benchmark harness: cells from data files, the measured window, the
references that decide ``correct``, and the trace reduction."""
