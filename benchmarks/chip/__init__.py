"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

Everything that decides a number lives here, apart from the program: the
traffic generators, the reduction from traces to metrics, the table of
peaks, the operation and byte counts, the plain references and the
comparison that decides ``correct``.  See ``run.py``.
"""
