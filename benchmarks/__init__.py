"""The benchmark (BENCHMARK.json names this directory under ``paths``).

The yardstick lives here so that a PR which changes the program cannot
change what it is measured with: traffic generation, the reduction from
traces and spans to metrics, the peaks table, the shape functions for
FLOPs and bytes, a plain reference of every configuration and the
comparison that decides ``correct``.  From ``flexflow_tpu`` the benchmark
takes only the system under test.  See README.md for how a later PR adds
a configuration, a traffic mix, a per-layer metric or a driver as files
of its own.
"""
