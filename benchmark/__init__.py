"""The benchmark of rocket-tpu: the yardstick later PRs are measured with.

Everything here is the benchmark's own: traffic generation, the plain
reference, the trace reduction, the table of peaks, the count functions and
the comparison that decides ``correct``. From ``rocket_tpu`` it takes only
the system under test. See ``benchmark/README.md``.
"""
