"""``correct`` at the cell's smoke sizes on the CPU: the program passes, its
control (the reference computed in fp8, one precision below the bf16 the
configuration states) fails, and a run with the timed path broken
underneath fails.

Each test drives a whole run (``bench.run.run_cell``) past the harness's
look for a chip, at the configuration's and the mix's smoke sizes, with the
Pallas kernels in interpret mode, and holds the logit gaps to the cell's
``smoke_limits``.
"""
import time

import jax.numpy as jnp
import pytest

from bench import run

SEED = 2 ** 31 + 101
CELLS = [("qwen2-7b.longctx-decode", 2.0)]


def _run(cell, seconds, **kw):
    return run.run_cell(cell, SEED, seconds, False, smoke=True,
                        t_start=time.monotonic(), **kw)


@pytest.mark.parametrize("cell,seconds", CELLS)
def test_program_correct_and_control_not(cell, seconds):
    r = _run(cell, seconds, control=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert any(v["limit"] is not None and v["value"] > v["limit"]
               for v in r["control_checks"].values()), r["control_checks"]


def _wrong_token(orig):
    def sample(logits, temps, keys):
        return (orig(logits, temps, keys) + 1) % logits.shape[-1]
    return sample


def _half_batch(orig):
    def sample(logits, temps, keys):
        tok = orig(logits, temps, keys)
        rows = jnp.arange(tok.shape[0])
        return jnp.where(rows % 2 == 1, jnp.zeros_like(tok), tok)
    return sample


def _state_unchanged(orig):
    def decode_step(params, cfg, token, caches, policy, **kw):
        logits, _ = orig(params, cfg, token, caches, policy, **kw)
        return logits, caches
    return decode_step


@pytest.mark.parametrize("fault", ["token", "half_batch", "state"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.models import transformer as T
    from repro.serving import engine as E
    if fault == "token":
        monkeypatch.setattr(E, "sample_per_slot", _wrong_token(E.sample_per_slot))
    elif fault == "half_batch":
        monkeypatch.setattr(E, "sample_per_slot", _half_batch(E.sample_per_slot))
    else:
        monkeypatch.setattr(T, "decode_step", _state_unchanged(T.decode_step))
    r = _run(CELLS[0][0], CELLS[0][1])
    assert not r["correct"]
    assert any(v["value"] > v["limit"] for k, v in r["checks"].items()
               if k.endswith("logit_gap") and v["limit"] is not None)
