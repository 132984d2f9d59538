"""Decides ``correct``: the served tokens against the plain reference.

After the window a sample of requests, drawn from the seed and holding the
one with the most served tokens, is scored by :func:`bench.reference.gaps`:
for each served token, how far its reference logit lies below the
reference's best at that position.  The widest and the mean gap are held to
the cell's ``limits`` (PERF.md gives the readings they were set from).
"""
from __future__ import annotations

from typing import List

import numpy as np


def sample(records, k: int, seed: int) -> List:
    """Up to ``k`` due requests with served tokens: the one with the most
    tokens, then others drawn from the seed.  Open loops sample finished
    requests; closed-loop sessions are taken as far as they got."""
    pool = [r for r in records if r.due and r.n_tokens > 0
            and (r.finish_s is not None or r.reason is None)]
    if not pool:
        return []
    pool.sort(key=lambda r: r.index)
    first = max(pool, key=lambda r: (r.prompt_len + r.n_tokens, r.n_tokens))
    rest = [r for r in pool if r is not first]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [first] + [rest[i] for i in sorted(pick)]


def numbers(per_pos) -> dict:
    """The numbers compared, from each sampled request's per-position gaps:
    the widest gap, and the mean gap over every served token."""
    if not per_pos:
        return {"widest_logit_gap": None, "mean_logit_gap": None}
    allg = np.concatenate(per_pos)
    return {"widest_logit_gap": float(allg.max()),
            "mean_logit_gap": float(allg.mean())}


def line(name: str, value, limit) -> str:
    return f"check {name} {value!r} limit {limit!r}"
