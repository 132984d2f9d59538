"""The benchmark's byte counts against the program's own accounting."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs

from repro.core import kv_cache as kvc
from repro.core.policy import QuantPolicy
from repro.kernels.ops import decode_block_report

POLS = [QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=64, window=32, n_sink=5),
        QuantPolicy(bits_k=4.0, bits_v=2.0, group_size=32, window=16, n_sink=4)]


def _pd(p):
    return {"bits_k": p.bits_k, "bits_v": p.bits_v, "group_size": p.group_size,
            "window": p.window, "n_sink": p.n_sink, "fp8_meta": p.fp8_meta}


@pytest.mark.parametrize("hkv,hd", [(8, 128), (4, 128), (2, 64)])
@pytest.mark.parametrize("pol", POLS)
def test_bytes_per_token_match_pool_blocks(pol, hkv, hd):
    dims = {"num_key_value_heads": hkv, "head_dim": hd}
    bt = 16
    assert costs.kv_bytes_per_token_layer(dims, _pd(pol)) * bt == \
        kvc.pool_block_nbytes(hkv, hd, pol, bt)


def test_bytes_per_token_at_8_and_4_kv_heads():
    pol = _pd(POLS[0])
    assert costs.kv_bytes_per_token_layer(
        {"num_key_value_heads": 8, "head_dim": 128}, pol) == 512
    assert costs.kv_bytes_per_token_layer(
        {"num_key_value_heads": 4, "head_dim": 128}, pol) == 256


def test_live_bytes_within_the_blocks_the_kernel_visits():
    pol = POLS[0]
    hkv, hd, b, cap = 2, 128, 3, 32 + 5 + 1024
    lens = np.asarray([40, 500, 1000], np.int32)
    cache = kvc.init_cache(b, cap, hkv, hd, pol, jnp.bfloat16)
    cache["length"] = jnp.asarray(lens)
    rep = decode_block_report(cache, pol, hd, block_s=256)
    per_tok = costs.kv_bytes_per_token_layer(
        {"num_key_value_heads": hkv, "head_dim": hd}, _pd(pol))
    assert rep["bytes_per_block"] == 256 * per_tok
    visited = np.asarray(rep["visited"])
    for n, v in zip(lens, visited):
        live = costs.live_packed_tokens(int(n), _pd(pol)) * per_tok
        assert live <= v * rep["bytes_per_block"]
        assert live > (v - 1) * rep["bytes_per_block"] or live == 0


def test_decode_steps_lengths():
    assert list(costs.decode_steps([(10, 1, 4)])) == [11, 12, 13]
