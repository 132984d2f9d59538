"""Support shared by the tests and the benchmarks.

:func:`count_compiles` counts XLA compilations (jit cache misses) inside a
``with`` block: ``with count_compiles() as n: ...; assert n() == 0``.  The
zero-compiles-after-warmup gates of the serving tests and
``benchmarks/serving_bench.py`` read it.
"""
from __future__ import annotations

from jax._src import test_util as _jtu

count_compiles = _jtu.count_jit_compilation_cache_miss
