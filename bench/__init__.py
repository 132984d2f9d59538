"""Chip benchmark of the SKVQ serving path (see ``BENCHMARK.json``).

Cells, model configurations, traffic mixes, model families and per-layer
metric readers are data files and small modules found by name under
``bench/cells``, ``bench/configs``, ``bench/traffic``, ``bench/families``
and ``bench/metrics``; a family (named by its configurations) holds the
equations of one kind of model: its sizes, weight tree, reference block and
head, and cost counts.  The rest of this package is the fixed yardstick:
traffic generation, the open and closed request loops, end-to-end
arithmetic, the trace reduction, the sums of the cost counts, the table of
peaks and what every family's reference shares.
"""
