"""The benchmark: one cell of BENCHMARK.json per run of ``benchmark/run.py``.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the reduction from traces and events
to metrics, the table of peaks, required FLOPs from shapes, the plain
reference forwards and the comparison that decides ``correct``. From the
program (``tpuic``, ``train.py``) it takes only the system under test and
its events, counters and kernel names.
"""
