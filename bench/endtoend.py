"""End-to-end arithmetic: what a user of the server sees, on the host clock.

Adapted from ``serving/metrics.py`` (``RequestRecord.ttft_ms``/``tpot_ms``,
``percentiles``), with TTFT taken from the moment a request was due.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def p95(xs) -> Optional[float]:
    xs = [x for x in xs if x is not None]
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if xs else None


def due(records) -> List:
    return [r for r in records if r.due]


def ttft_ms(r, end_s: float) -> float:
    """First token delivered minus due time; a request that never got one
    counts as waiting until the loop ended."""
    first = r.first_s if r.first_s is not None else end_s
    return (first - r.due_s) * 1e3


def tpot_ms(r) -> Optional[float]:
    if r.finish_s is None or r.first_s is None or r.n_tokens < 2:
        return None
    return (r.finish_s - r.first_s) * 1e3 / (r.n_tokens - 1)


def compute(name: str, ctx: Dict) -> Optional[float]:
    w = ctx["window"]
    recs = due(w.records)
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "ttft_p95_ms":
        return p95(ttft_ms(r, ctx["end_s"]) for r in recs)
    if name == "tpot_p95_ms":
        return p95(tpot_ms(r) for r in recs)
    if name == "output_tok_s":
        return w.tokens / (w.t1 - w.t0)
    raise KeyError(f"no end-to-end arithmetic for {name!r}")
