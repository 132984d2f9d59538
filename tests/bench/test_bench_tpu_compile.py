"""Compile the decode kernel for a described TPU v5e at the cell's shape.

The pooled ``decode_attn_pallas`` is lowered with ``interpret=False`` at
``qwen2-7b.longctx-decode``'s shape (4 slots, 4 KV heads, 7 query heads per
KV head, a 24.6k-token capacity in 16-token blocks) and at a short-context
chat shape (16 slots, a 2.3k-token capacity).  Nothing executes; the topology is described inside a fixture.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.policy import QuantPolicy
from repro.core.quant import plane_layout
from repro.kernels.decode_attn import decode_attn_pallas

POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=64, window=32, n_sink=5)
BT = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _planes(sharding, lead, d, bits):
    out = {}
    for name, (_, w, b, gs) in zip(("hi", "lo"), plane_layout(d, bits, 64)):
        out[f"codes_{name}"] = jax.ShapeDtypeStruct((*lead, w * b // 8),
                                                    jnp.uint8, sharding=sharding)
        for part in ("scale", "zero"):
            out[f"{part}_{name}"] = jax.ShapeDtypeStruct(
                (*lead, w // gs), jnp.uint8, sharding=sharding)
    return out


@pytest.mark.parametrize("slots,hkv,gq,capacity", [
    (4, 4, 7, 24592),      # qwen2-7b.longctx-decode
    (16, 4, 7, 2304),      # chat: 16 slots, short contexts
])
def test_pooled_decode_kernel_compiles(one_chip, slots, hkv, gq, capacity):
    d = 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n_phys = slots * capacity // BT
    lead = (n_phys, BT, hkv)

    def fn(q, k, v, mask, bounds, table):
        return decode_attn_pallas(q, k, v, mask, POL, d, d ** -0.5,
                                  interpret=False, block_s=BT,
                                  block_bounds=bounds, block_table=table)
    hlo = jax.jit(fn).lower(
        sds((slots, hkv, gq, d), jnp.float32),
        _planes(one_chip, lead, d, POL.bits_k),
        _planes(one_chip, lead, d, POL.bits_v),
        sds((slots, capacity), jnp.float32), sds((slots, 2), jnp.int32),
        sds((slots, capacity // BT), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo
