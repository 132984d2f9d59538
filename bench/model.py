"""The system under test, built from a cell: configuration, policy, engine.

This module, and each family's ``arch`` (``bench/families``), are the only
code of the benchmark that imports the serving program (``src/repro``); the
reference (:mod:`bench.reference`) does not.
"""
from __future__ import annotations

import copy
import sys

from . import spec
from .spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def dims(config: dict, smoke: bool = False) -> dict:
    """The model sizes as run (the smoke block overrides them in tests),
    by the family the configuration names (``bench/families``)."""
    return spec.family(config["family"]).dims(config, smoke)


def arch(d: dict):
    """The program's ``ArchConfig`` for these sizes, by their family."""
    return spec.family(d["family"]).arch(d)


def policy(config: dict, d: dict):
    from repro.core.policy import QuantPolicy
    p = config["cache_policy"]
    return QuantPolicy(bits_k=p["bits_k"], bits_v=p["bits_v"],
                       group_size=min(p["group_size"], d["head_dim"]),
                       window=p["window"], n_sink=p["n_sink"],
                       fp8_meta=p["fp8_meta"])


def engine_knobs(traffic: dict, smoke: bool = False) -> dict:
    knobs = copy.deepcopy(traffic["engine"])
    if smoke:
        knobs.update(traffic.get("smoke", {}).get("engine", {}))
    return knobs


def capacity(pol, knobs: dict, longest_prompt: int, largest_new: int) -> int:
    """Per-slot capacity: the longest request plus one sync, rounded up so
    the packed region tiles into whole pool blocks."""
    bt = knobs["pool_block_tokens"]
    need = longest_prompt + largest_new + knobs["steps_per_sync"]
    packed = need - pol.n_sink - pol.window
    return pol.n_sink + pol.window + -(-packed // bt) * bt


def build_engine(params, cfg, pol, knobs: dict, max_len: int):
    """``Engine`` as the cell's traffic needs it: pallas backend, chunked
    prefill, the paged pool sized for every slot at capacity, async host,
    computing in the weights' bf16."""
    from repro.serving import Engine
    slots, bt = knobs["slots"], knobs["pool_block_tokens"]
    blocks_per_slot = (max_len - pol.n_sink - pol.window) // bt
    return Engine(
        params, cfg, pol, batch_slots=slots, max_len=max_len,
        backend="pallas", steps_per_sync=knobs["steps_per_sync"],
        prefill_chunk=knobs["prefill_chunk"],
        chunk_buckets=knobs["chunk_buckets"], pool_block_tokens=bt,
        pool_blocks=slots * blocks_per_slot,
        async_host=knobs["async_host"])


def request(prompt, max_new: int, seed: int, temperature: float = 0.0):
    from repro.serving import Request
    return Request(prompt=prompt, max_new=int(max_new), seed=int(seed),
                   temperature=float(temperature))


def count_compiles():
    from repro.testing import count_compiles as cc
    return cc()


def enable_compile_cache() -> str:
    from repro.launch.compile_cache import enable_compile_cache as ecc
    return ecc()


def interpret_mode() -> dict:
    from repro.kernels._compat import interpret_mode_info
    return interpret_mode_info()
