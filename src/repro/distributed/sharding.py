"""Logical-axis sharding: model code names axes, a rule table maps them to mesh axes.

Model code calls ``logical(x, "batch", "seq", "ff")``; outside a sharding
context this is the identity (CPU unit tests), inside it becomes a
``with_sharding_constraint`` so GSPMD propagates the intended layout.  The rule
tables below encode the production strategy (DESIGN.md §5):

  * TRAIN_RULES — DP over (pod, data), Megatron TP over model
    (heads/ff/vocab/experts), optional sequence parallelism.
  * SERVE_RULES — batch over (pod, data), heads over model; long-context
    (batch=1) cells switch ``kv_seq`` to data (context parallelism).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

TRAIN_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
    "d": None,
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "vocab": "model",
    "experts": "model",
    "cap": None,
    "state": None,
}

SERVE_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
    "d": None,
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "vocab": "model",
    "experts": "model",
    "cap": None,
    "state": None,
}

# long-context decode, batch=1: shard the KV sequence over (pod, data)
LONG_SERVE_RULES = dict(SERVE_RULES, batch=None, kv_seq=("pod", "data"))

# batch=1 with the packed cache replicated (SKVQ makes that affordable):
# nothing batch/seq-sharded; TP only
REPL_SERVE_RULES = dict(SERVE_RULES, batch=None, kv_seq=None)

# sequence-parallel training (hillclimb lever): norms/elementwise run
# seq-sharded over the model axis, cutting TP all-gather volume
SEQ_PARALLEL_TRAIN_RULES = dict(TRAIN_RULES, seq="model")


@dataclasses.dataclass
class ShardingCtx:
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, Axis]] = None


_TLS = threading.local()


def _ctx() -> ShardingCtx:
    if not hasattr(_TLS, "ctx"):
        _TLS.ctx = ShardingCtx()
    return _TLS.ctx


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Dict[str, Axis]):
    prev = _ctx().mesh, _ctx().rules
    _TLS.ctx = ShardingCtx(mesh, dict(rules))
    try:
        yield
    finally:
        _TLS.ctx = ShardingCtx(*prev)


def current_rules() -> Optional[Dict[str, Axis]]:
    return _ctx().rules


def _axes_in_mesh(axis: Axis, mesh: Mesh) -> Axis:
    """Drop mesh axes that don't exist (single-pod mesh has no 'pod')."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if axis in mesh.axis_names else None
    kept = tuple(a for a in axis if a in mesh.axis_names)
    return kept if kept else None


def spec_for(*names: Optional[str]) -> P:
    ctx = _ctx()
    assert ctx.rules is not None
    parts = []
    for n in names:
        a = None if n is None else ctx.rules.get(n)
        parts.append(_axes_in_mesh(a, ctx.mesh))
    return P(*parts)


def logical(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (identity w/o context).

    Axes whose size doesn't divide the mesh extent are dropped: forcing e.g.
    4 kv-heads onto a 16-way model axis makes GSPMD pad-and-reduce (measured
    as a 17 GB/step all-reduce on gemma3 long-context decode — §Perf)."""
    ctx = _ctx()
    if ctx.mesh is None or ctx.rules is None:
        return x
    if x.ndim != len(names):
        raise ValueError(f"rank {x.ndim} vs {names}")
    spec = spec_for(*names)
    dims = []
    for i, ax in enumerate(spec):
        if ax is not None:
            size = 1
            for a in ((ax,) if isinstance(ax, str) else ax):
                size *= ctx.mesh.shape[a]
            if x.shape[i] % size != 0:
                ax = None
        dims.append(ax)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*dims)))


# ------------------------------------------------------------ param specs

# parameter partition rules by key-path suffix (Megatron TP + EP); tried in
# order, first match wins. ZeRO-1 additionally shards optimizer state along
# 'data' (see training.optim).
_PARAM_RULES = (
    ("wq", P(None, None, "model")),
    ("wk", P(None, None, "model")),
    ("wv", P(None, None, "model")),
    ("wo_attn", P(None, "model", None)),
    ("bq", P(None, "model")),
    ("bk", P(None, "model")),
    ("bv", P(None, "model")),
    ("wi_gate", P(None, None, "model")),
    ("wi_up", P(None, None, "model")),
    ("wo", P(None, "model", None)),
    ("experts_gate", P(None, "model", None, None)),   # (L, E, D, f)
    ("experts_up", P(None, "model", None, None)),
    ("experts_down", P(None, "model", None, None)),   # (L, E, f, D)
    ("router", P(None, None, None)),
    ("embed", P("model", None)),
    ("lm_head", P(None, "model")),
    # rwkv6 / mamba big projections
    ("w_rkvg", P(None, None, "model")),
    ("w_out", P(None, "model", None)),
    ("in_proj", P(None, None, "model")),
    ("out_proj", P(None, "model", None)),
)


def param_partition_specs(params, mesh: Mesh):
    """PartitionSpec pytree for a param tree, by key-name rules."""

    def spec_of(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        for suffix, spec in _PARAM_RULES:
            if name == suffix:
                ok = len(spec) == leaf.ndim and all(
                    a is None or a in mesh.axis_names for a in spec)
                if ok:
                    return spec
                # specs above assume a leading stacked-layer dim; tolerate
                # unstacked variants by trimming the leading None
                if len(spec) == leaf.ndim + 1 and spec[0] is None:
                    trimmed = P(*spec[1:])
                    if all(a is None or a in mesh.axis_names for a in trimmed):
                        return trimmed
        return P()  # replicate

    return jax.tree_util.tree_map_with_path(spec_of, params)


def named_shardings(params, mesh: Mesh):
    specs = param_partition_specs(params, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
