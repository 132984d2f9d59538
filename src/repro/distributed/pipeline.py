"""GPipe-style pipeline parallelism over the ``pod`` axis (DESIGN.md §5).

At 512 chips the assigned models fit comfortably under TP×DP, so PP is OFF by
default; this module is the >4-pod scaling path.  The schedule is the
collective-permute ladder: stage s holds layers [s·L/S, (s+1)·L/S); a
microbatch scan pushes activations stage-to-stage with
``jax.lax.ppermute``; bubbles = (S-1)/(M+S-1).

Implementation notes:
  * runs inside ``jax.shard_map`` over the pipeline axis with the remaining
    mesh axes left to GSPMD (``axis_names={axis}`` partial shard_map — same
    mechanism as the int8 cross-pod all-reduce in compression.py);
  * stage-local params are the layer-stacked pytree sliced on the leading
    axis, so the same scan-over-layers block function is reused;
  * correctness is asserted against the unpipelined forward in
    tests/test_pipeline.py on 4 fake devices.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(block_fn: Callable, params_stacked, x, *, mesh,
                     axis: str = "pod", microbatches: int = 4):
    """Run ``block_fn`` over layer-stacked params, pipelined over ``axis``.

    block_fn(h, layer_params) -> h        (one transformer block)
    params_stacked: pytree with leading layer dim L (L % n_stages == 0)
    x: (B, ...) activations (B % microbatches == 0)

    Returns the same value as sequentially applying all L layers.
    """
    n_stages = mesh.shape[axis]
    l_total = jax.tree.leaves(params_stacked)[0].shape[0]
    assert l_total % n_stages == 0, (l_total, n_stages)
    per_stage = l_total // n_stages
    b = x.shape[0]
    assert b % microbatches == 0, (b, microbatches)

    def stage_fn(params_local, x_local):
        """Runs on one pipeline stage. params_local: (per_stage, ...) slice;
        x_local: full activations (replicated input), consumed stage 0 only."""
        sid = jax.lax.axis_index(axis)
        mb = x_local.reshape(microbatches, b // microbatches, *x_local.shape[1:])
        n_ticks = microbatches + n_stages - 1

        def run_stage(h):
            def body(c, p):
                return block_fn(c, p), None
            out, _ = jax.lax.scan(body, h, params_local)
            return out

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (if any), others take the relayed
            # activations from the previous stage
            inject = mb[jnp.clip(t, 0, microbatches - 1)]
            h_in = jnp.where(sid == 0, inject, buf)
            h_out = run_stage(h_in)
            # last stage harvests microbatch (t - n_stages + 1)
            slot = t - (n_stages - 1)
            do_write = (slot >= 0) & (sid == n_stages - 1)
            idx = jnp.clip(slot, 0, microbatches - 1)
            old = jax.lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
            new = jnp.where(do_write, h_out, old)
            out = jax.lax.dynamic_update_index_in_dim(out, new, idx, 0)
            buf = jax.lax.ppermute(h_out, axis, perm)
            return (buf, out), None

        buf0 = jnp.zeros_like(mb[0])
        out0 = jnp.zeros_like(mb)
        (buf, out), _ = jax.lax.scan(tick, (buf0, out0),
                                     jnp.arange(n_ticks))
        # only the last stage's `out` is real; broadcast it to all stages
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(b, *x_local.shape[1:])

    # params: stage s gets layers [s*per_stage, (s+1)*per_stage)
    in_specs = (jax.tree.map(lambda _: P(axis), params_stacked), P())
    f = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
                      axis_names={axis}, check_vma=False)
    stage_view = jax.tree.map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]), params_stacked)
    # shard_map with P(axis) expects the leading dim == n_stages blocks
    stage_flat = jax.tree.map(
        lambda a: a.reshape(n_stages * per_stage, *a.shape[2:]), stage_view)
    return f(stage_flat, x)
