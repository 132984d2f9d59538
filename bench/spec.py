"""Finds a cell, its configuration, its traffic mix, its model family and
its metrics by name.

Everything that belongs to one cell, configuration, mix, family or
per-layer metric lives in a file of its own; this module is the only place
that knows where:

    bench/cells/<cell>.json      config, traffic, chips, rate, why, limits
    bench/configs/<config>.json  model sizes as run, family, source, cuts, policy
    bench/traffic/<mix>.json     loop kind, lengths, prefix, engine knobs
    bench/families/<family>.py   a family's sizes, weights, reference and costs
    bench/metrics/<metric>.py    ``read(ctx) -> float | None``
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _check_name(name: str) -> str:
    if not _NAME.match(name) or ".." in name:
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load(kind: str, name: str, root: Path = BENCH) -> dict:
    """The JSON file ``<root>/<kind>/<name>.json`` with ``name`` added."""
    path = root / kind / f"{_check_name(name)}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} named {name!r} ({path} is missing)")
    out = json.loads(path.read_text())
    out["name"] = name
    return out


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = BENCH) -> dict:
    """A cell with its configuration and traffic mix resolved."""
    c = load("cells", name, root)
    c["config"] = load("configs", c["config"], root)
    c["traffic"] = load("traffic", c["traffic"], root)
    return c


def metrics_for(cell_name: str, bm: dict, trace: bool) -> List[dict]:
    """The entries of ``BENCHMARK.json`` this cell reports in a run: its
    end-to-end metrics with ``--trace 0``, its per-layer ones with 1."""
    e2e = [m for m in bm["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in names]


def _module(prefix: str, name: str, path: Path):
    sp = importlib.util.spec_from_file_location(
        prefix + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = BENCH) -> Callable[[dict], Optional[float]]:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = root / "metrics" / f"{_check_name(metric)}.py"
    if not path.is_file():
        raise KeyError(f"no reader for per-layer metric {metric!r} ({path})")
    return _module("bench_metric_", metric, path).read


def family(name: str, root: Path = BENCH):
    """The module ``bench/families/<name>.py``: a model family's sizes as
    run (``dims``), the program's config (``arch``), its weight tree
    (``shapes``), the reference's position tables, block and head
    (``tables``, ``layer``, ``head``) and its cost counts
    (``weight_flops_per_token``, ``attn_flops``, ``attended_lengths``).
    Loaded once a process, so the reference's compiled programs, keyed by
    the module, are found again."""
    path = root / "families" / f"{_check_name(name)}.py"
    if not path.is_file():
        raise KeyError(f"no model family named {name!r} ({path} is missing)")
    return _family(path.resolve())


@functools.cache
def _family(path: Path):
    return _module("bench_family_", path.stem, path)


def workload(bm: dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{sorted(w['name'] for w in bm['workloads'])}")
