"""Gradient compression for the slow cross-pod link (DESIGN.md §5).

Two pieces, composable:

* ``ef_int8_compress`` — error-feedback int8 rounding of the gradient tree.
  This is the *numerics* of compressed data-parallel sync: quantize (g + e) to
  per-tensor int8, carry the residual e forward.  Convergence-tested on CPU.

* ``int8_allreduce_pod`` — the *wire* path: an explicit shard_map over the
  ``pod`` axis whose all-gather moves int8 (4× fewer collective bytes than
  fp32, 2× fewer than bf16).  Inner data/model axes stay under GSPMD (partial
  shard_map via ``axis_names={"pod"}``).  Used in the §Perf collective
  hillclimb; the HLO shows ``s8[...] all-gather`` on the pod groups.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant_int8(g32):
    scale = jnp.max(jnp.abs(g32)) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127)
    return q.astype(jnp.int8), scale


def ef_int8_compress(grads, ef, mesh=None) -> Tuple[Dict, Dict]:
    """Error-feedback int8 rounding of every gradient leaf."""

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = _quant_int8(g32)
        gq = q.astype(jnp.float32) * scale
        return gq.astype(g.dtype), g32 - gq

    out = jax.tree.map(one, grads, ef)
    gq = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    ef_new = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return gq, ef_new


def int8_allreduce_pod(x: jnp.ndarray, mesh) -> jnp.ndarray:
    """Mean over the pod axis with int8 on the wire (all-gather + local sum)."""
    if "pod" not in mesh.axis_names or mesh.shape["pod"] == 1:
        return x

    def inner(g):
        q, scale = _quant_int8(g.astype(jnp.float32))
        qs = jax.lax.all_gather(q, "pod")            # s8 on the wire
        ss = jax.lax.all_gather(scale, "pod")
        brd = ss.reshape((ss.shape[0],) + (1,) * g.ndim)
        return (qs.astype(jnp.float32) * brd).mean(0).astype(x.dtype)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names={"pod"}, check_vma=False)(x)


def int8_allreduce_tree(tree, mesh):
    return jax.tree.map(lambda x: int8_allreduce_pod(x, mesh), tree)
