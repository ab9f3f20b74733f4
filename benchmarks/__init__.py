"""The chip benchmark: one cell, one run, one JSON line.

``python -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a data file found by the name ``BENCHMARK.json`` gives
it: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.json`` (which names a reader in ``readers/``).  The
yardstick lives here (traffic generation, the reduction from records and
traces to metrics, the peaks, the shape functions, the float32 reference
and the comparison that decides ``correct``); from the program the
benchmark takes only the system under test and its annotations and
counters.
"""
