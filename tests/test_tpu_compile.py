"""Compile the fused Pallas kernels for a TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what Mosaic refuses —
unsupported casts, lane reshapes, unaligned tiles — so these tests lower
``decode_attn_pallas`` (striped and pooled) and ``kv_quant_pallas`` with
``interpret=False`` for a *described* ``v5e:2x2`` topology at the widths
the serving path runs (head_dim 64 and 128, K2/V1.5, fp8 metadata).
Nothing executes.  The topology is described inside a fixture, never at
import, so every pytest-xdist worker collects the same tests; where the
TPU compiler cannot be loaded the tests skip from that fixture.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.policy import QuantPolicy
from repro.core.quant import n_meta_groups, plane_layout
from repro.kernels.decode_attn import decode_attn_pallas
from repro.kernels.kv_quant import kv_quant_pallas

# the serving defaults (launch/serve.py, chip_smoke.py): K2/V1.5, group 64
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=64, window=32, n_sink=5)
B, HKV, GQ = 4, 8, 4            # llama3p2_1b: 32 query / 8 kv heads
S_LEN = 2048
POOL_BLOCK_TOKENS = 16          # chip_smoke.py's pool tile (serve.py default)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these tests
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


def _compile(fn, *avatars):
    hlo = jax.jit(fn).lower(*avatars).compile().as_text()
    assert "tpu_custom_call" in hlo      # the Mosaic kernel is in the program


@pytest.mark.parametrize("d", [64, 128])
def test_decode_attn_striped_compiles(one_chip, d):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lead = (B, S_LEN, HKV)

    def fn(q, k, v, mask, bounds):
        return decode_attn_pallas(q, k, v, mask, POL, d, d ** -0.5,
                                  interpret=False, block_s=256,
                                  block_bounds=bounds)
    _compile(fn, sds((B, HKV, GQ, d), jnp.float32),
             _planes(one_chip, lead, d, POL.bits_k),
             _planes(one_chip, lead, d, POL.bits_v),
             sds((B, S_LEN), jnp.float32), sds((B, 2), jnp.int32))


@pytest.mark.parametrize("d,hkv,gq,n_pages,n_phys", [
    pytest.param(64, HKV, GQ, S_LEN // POOL_BLOCK_TOKENS, 512, id="64"),
    pytest.param(128, HKV, GQ, S_LEN // POOL_BLOCK_TOKENS, 512, id="128"),
    # qwen2-7b.longctx-decode: 4 KV heads of 7 query heads, 1,535 pages a
    # slot, every slot's pages in the pool plus the null page
    pytest.param(128, 4, 7, 1535, 4 * 1535 + 1, id="longctx-cell"),
])
def test_decode_attn_pooled_compiles(one_chip, d, hkv, gq, n_pages, n_phys):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bt = POOL_BLOCK_TOKENS
    lead = (n_phys, bt, hkv)

    def fn(q, k, v, mask, bounds, table):
        return decode_attn_pallas(q, k, v, mask, POL, d, d ** -0.5,
                                  interpret=False, block_s=bt,
                                  block_bounds=bounds, block_table=table)
    _compile(fn, sds((B, hkv, gq, d), jnp.float32),
             _planes(one_chip, lead, d, POL.bits_k),
             _planes(one_chip, lead, d, POL.bits_v),
             sds((B, n_pages * bt), jnp.float32), sds((B, 2), jnp.int32),
             sds((B, n_pages), jnp.int32))


@pytest.mark.parametrize("bits", [2.0, 1.5])
@pytest.mark.parametrize("d", [64, 128])
def test_kv_quant_compiles(one_chip, d, bits):
    n = 1024
    g = n_meta_groups(d, bits, 64)

    def fn(x, alpha):
        return kv_quant_pallas(x, bits, 64, alpha=alpha, fp8_meta=True,
                               interpret=False, block_t=128)
    _compile(fn, jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=one_chip),
             jax.ShapeDtypeStruct((n, g), jnp.float32, sharding=one_chip))
