"""Interpret-mode policy for the fused Pallas kernels (DESIGN.md §4).

:func:`resolve_interpret` is the single policy for whether a kernel runs
compiled or in the Pallas interpreter; ``decode_attn.py`` and
``kv_quant.py`` both resolve through it.

Interpret-mode resolution (most-specific wins):

1. an explicit ``interpret=True/False`` argument is always honored;
2. the ``REPRO_PALLAS_INTERPRET`` env var ("1"/"true"/"on" or
   "0"/"false"/"off") overrides the auto default — e.g. force-interpret on
   a TPU host to debug a kernel, or assert-compiled in a TPU CI job;
3. otherwise auto: compiled on TPU hosts, interpreter everywhere else (the
   interpreter is a correctness tool, not a fast CPU path).

:func:`interpret_mode_info` reports the resolved mode + its source so the
serving engine and the benchmark JSON can record which mode produced a
number (a compiled-TPU latency and an interpreted-CPU latency are not
comparable).
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV_VAR = "REPRO_PALLAS_INTERPRET"

_TRUE = {"1", "true", "yes", "on"}
_FALSE = {"0", "false", "no", "off"}


def _env_interpret() -> Optional[bool]:
    raw = os.environ.get(ENV_VAR, "").strip().lower()
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    return None  # unset / "auto" / unrecognized -> auto-detect


def interpret_mode_info(interpret: Optional[bool] = None) -> dict:
    """{"interpret": bool, "source": "explicit" | "env" | "auto"} — the one
    resolution of the precedence ladder above, recorded in
    ``Engine.backend_info`` and the benchmark JSON artifact."""
    if interpret is not None:
        return {"interpret": bool(interpret), "source": "explicit"}
    env = _env_interpret()
    if env is not None:
        return {"interpret": env, "source": f"env:{ENV_VAR}"}
    return {"interpret": jax.default_backend() != "tpu", "source": "auto"}


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the Pallas interpret flag (explicit > env var > auto)."""
    return interpret_mode_info(interpret)["interpret"]
