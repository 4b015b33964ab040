"""The reference system's second metric: staleness against wall time.

``python -m distkeras_tpu.benchmarks.staleness_tradeoff`` sweeps strategy x
window x workers; it measures no chip. What measures the chip is ``perf/``
(``BENCHMARK.json``).
"""
