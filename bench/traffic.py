"""One general generator for every traffic mix in ``bench/traffic``.

A mix is data: the loop kind (``closed``: a fixed set of sessions submitted
at set-up; ``open``: Poisson arrivals at the cell's fixed rate), the length
distributions, a shared prefix and the engine knobs it implies.  The same
``--seed`` gives byte-identical arrivals.

For open loops every seed gets the same multiset of prompt lengths, output
lengths and gaps between arrivals (drawn from the mix's ``shape_seed``), in
an order drawn from ``--seed``: the seed changes the order and the token ids,
not the amount of work.  Arrivals due inside the measured window form the
sample; arrivals after it keep the load on while the sample finishes.

Adapted from ``serving/loadgen.py``'s ``poisson_trace``, whose lengths are
drawn uniformly from a few values.
"""
from __future__ import annotations

import copy
import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    t: float              # seconds after the window opens (0: set-up)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    due: bool             # inside the measured window: part of the sample
    index: int


def merged(traffic: dict, smoke: bool = False) -> dict:
    """The mix, with its ``smoke`` block applied for CPU tests."""
    t = copy.deepcopy(traffic)
    if smoke:
        for k, v in t.get("smoke", {}).items():
            if isinstance(v, dict) and isinstance(t.get(k), dict):
                t[k].update(v)
            else:
                t[k] = v
    return t


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    if kind == "choice":
        return rng.choice(np.asarray(dist["values"], np.int64), size=n)
    raise ValueError(f"unknown length distribution {kind!r}")


def longest(traffic: dict) -> tuple:
    """(longest prompt, largest max_new) the mix can draw."""
    def top(dist):
        return int(dist.get("value", dist.get("max",
                                              max(dist.get("values", [0])))))
    return (traffic.get("shared_prefix", 0) + top(traffic["prompt_len"]),
            top(traffic["max_new"]))


def generate(traffic: dict, seed: int, seconds: float, rate, vocab: int
             ) -> List[Arrival]:
    """The arrivals of one run (``traffic`` already :func:`merged`)."""
    rng = np.random.default_rng(int(seed))
    if traffic["loop"] == "closed":
        n = int(traffic["sessions"])
        base = np.random.default_rng(traffic.get("shape_seed", 0))
        plen = draw_lengths(traffic["prompt_len"], n, base)
        mnew = draw_lengths(traffic["max_new"], n, base)
        return [Arrival(0.0, rng.integers(0, vocab, int(p)).astype(np.int32),
                        int(m), True, i)
                for i, (p, m) in enumerate(zip(plen, mnew))]
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop kind {traffic['loop']!r}")
    if not rate or rate <= 0:
        raise ValueError("an open-loop cell needs a fixed rate > 0")
    n = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(traffic["shape_seed"])
    user = draw_lengths(traffic["prompt_len"], n, base)
    mnew = draw_lengths(traffic["max_new"], n, base)
    gaps = base.exponential(1.0, n)
    user, mnew, gaps = (x[rng.permutation(n)] for x in (user, mnew, gaps))
    times = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    prefix = rng.integers(0, vocab, int(traffic.get("shared_prefix", 0)))
    # arrivals after the window: the same sizes and gaps again, new tokens
    n_after = int(math.ceil(rate * traffic.get("drain_limit_s", 60)))
    out = []
    for i in range(n + n_after):
        k, lap = i % n, i // n
        body = rng.integers(0, vocab, int(user[k]))
        prompt = np.concatenate([prefix, body]).astype(np.int32)
        out.append(Arrival(float(times[k] + lap * seconds), prompt,
                           int(mnew[k]), lap == 0, i))
    return out
