"""The benchmark's yardstick: everything here is the benchmark's own.

From the program (`mosaic_tpu`) the benchmark takes the system under test,
its telemetry events, its counters and its kernel names; traffic generation,
the reduction from traces and events to metrics, the peaks table, the
bytes-per-row function, the plain reference and the comparison that decides
``correct`` live under the directories ``BENCHMARK.json`` lists in ``paths``.
"""
