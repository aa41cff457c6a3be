"""The chip benchmark of keystone_tpu: BENCHMARK.json's command and yardstick.

Everything here belongs to the benchmark and nothing to the program: the
traffic, the clocks, the reduction of the device trace, the table of
peaks, the plain references and the comparison that decides `correct`.
See PERF.md for what is measured and why.
"""
