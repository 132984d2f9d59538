"""Chip benchmark of the SKVQ serving path (see ``BENCHMARK.json``).

Cells, model configurations, traffic mixes and per-layer metric readers are
data files and small readers found by name under ``bench/cells``,
``bench/configs``, ``bench/traffic`` and ``bench/metrics``; the rest of this
package is the fixed yardstick: traffic generation, the open and closed
request loops, end-to-end arithmetic, the trace reduction, the cost
functions, the table of peaks and the plain reference.
"""
