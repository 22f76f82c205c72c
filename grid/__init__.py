"""The benchmark grid: one command runs one cell once (``python -m grid.run``).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the clocks, the reduction from the
profiler's trace to metrics, the table of peaks, the arithmetic of
operations and bytes, a plain reference of each configuration and the
comparison that decides ``correct``. From the program the grid takes the
system under test, its counters and its kernel names.
"""
