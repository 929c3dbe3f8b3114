"""Chip benchmark of the hybrid query path (see ``BENCHMARK.json``).

Everything that decides a number lives here, apart from the system under
test: traffic generation, the data and weights drawn from the seed, the
plain references that decide ``correct``, the reduction from spans,
counters and the device trace to metrics, and the table of peaks.
"""
