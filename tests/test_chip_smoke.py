"""chip_smoke.py at smoke size on the CPU (kernels in interpret mode).

The script's phases run here end to end on the 2-layer smoke config, so a
broken path, argument or check shows up before any chip time is spent; and
without a TPU ``main`` must refuse to run and print no result line.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("llama3p2_1b")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_phase_a_smoke(smoke, model):
    failures = []
    smoke.phase_a(*model, prompt_len=96, seed=0, failures=failures)
    assert failures == []


def test_phase_b_smoke(smoke, model, monkeypatch):
    monkeypatch.setattr(smoke, "PROMPT_LENS_B", (24, 40))
    monkeypatch.setattr(smoke, "MAX_NEW", 8)
    monkeypatch.setattr(smoke, "PREFILL_CHUNK", 16)
    failures = []
    info = smoke.phase_b(*model, seed=0, failures=failures)
    assert failures == []
    assert info["name"] == "pallas" and info["pooled"]
