"""Pooled decode kernel: compute blocks of pages fetched by the kernel.

The pooled ``decode_attn_pallas`` attends over compute blocks of
``P = pages_per_block(BT, NB)`` pages, copying each live page of the block
table itself.  Its flash triple must equal the striped kernel's at
``block_s == P * BT`` bit for bit — same tiles, same merge order — on
scrambled tables, a ragged last compute block, a windowed lower bound off
the block grid, an empty slot, ``P == 1``, and fp16 metadata.  The
metadata decodes the kernel does from integer bits are checked against the
reference conversions on every code.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fp8 import decode_fp8
from repro.core.policy import QuantPolicy
from repro.core.quant import quantize_groups
from repro.core import kv_cache as kvc
from repro.core import segments as seg
from repro.kernels.decode_attn import (decode_attn_pallas, pages_per_block,
                                       _fp8_to_f32, _f16_to_f32)
from repro.kernels.ops import decode_block_report

POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
HKV, GQ, D = 2, 3, 32


def _planes(rng, b, s, pol):
    k = jnp.asarray(rng.normal(size=(b, s, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, HKV, D)), jnp.float32)
    gs = min(pol.group_size, D)
    return (quantize_groups(k, pol.bits_k, gs, fp8_meta=pol.fp8_meta),
            quantize_groups(v, pol.bits_v, gs, fp8_meta=pol.fp8_meta))


def _pool(qt, tbl, bt):
    """Scatter striped planes (B, NB*BT, H, W) into pool pages (NP, BT, H, W)
    at ``tbl``; physical page 0 is the null page, left zero."""
    b, nb = tbl.shape
    n_phys = int(tbl.max()) + 1
    out = {}
    for key, a in qt.items():
        pages = a.reshape(b * nb, bt, *a.shape[2:])
        pool = jnp.zeros((n_phys, bt, *a.shape[2:]), a.dtype)
        out[key] = pool.at[tbl.reshape(-1)].set(pages)
    return out


def _pad(qt, s_to):
    return {k: jnp.pad(a, ((0, 0), (0, s_to - a.shape[1]), (0, 0), (0, 0)))
            for k, a in qt.items()}


def _mask(b, s, live):
    """(B, S) f32: slot ``i`` attends logical tokens [lo, hi) of live[i]."""
    j = np.arange(s)
    return jnp.asarray(np.stack([(j >= lo) & (j < hi) for lo, hi in live]),
                       jnp.float32)


def _both(rng, *, bt, nb, live, pol=POL, scramble=True):
    b = len(live)
    s = nb * bt
    k_qt, v_qt = _planes(rng, b, s, pol)
    ids = np.arange(1, b * nb + 1)
    if scramble:
        ids = rng.permutation(ids)
    tbl = jnp.asarray(ids.reshape(b, nb), jnp.int32)
    mask = _mask(b, s, live)
    q = jnp.asarray(rng.normal(size=(b, HKV, GQ, D)), jnp.float32)
    scale = D ** -0.5

    ppb = pages_per_block(bt, nb)
    pooled = decode_attn_pallas(
        q, _pool(k_qt, tbl, bt), _pool(v_qt, tbl, bt), mask, pol, D, scale,
        interpret=True, block_s=bt,
        block_bounds=seg.packed_block_bounds(mask, bt), block_table=tbl)

    tile = ppb * bt
    s_pad = -(-s // tile) * tile
    mask_p = jnp.pad(mask, ((0, 0), (0, s_pad - s)))
    striped = decode_attn_pallas(
        q, _pad(k_qt, s_pad), _pad(v_qt, s_pad), mask_p, pol, D, scale,
        interpret=True, block_s=tile,
        block_bounds=seg.packed_block_bounds(mask_p, tile))
    return pooled, striped, ppb


def _assert_bits(pooled, striped):
    for name, a, b in zip(("num", "m", "l"), pooled, striped):
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("case,bt,nb,live", [
    # 8-token pages: P = 32 capped at the table width
    ("scrambled", 8, 12, [(0, 96), (5, 61), (17, 18)]),
    # 8-token pages: P = 32, three compute blocks, the last one ragged
    ("multi_block", 8, 80, [(0, 640), (3, 513), (250, 260)]),
    # ragged last compute block: 20 pages of 16 tokens, P = 16
    ("ragged_last", 16, 20, [(0, 320), (0, 260), (1, 300)]),
    # windowed lower bound off the block grid (page 21 of P = 16)
    ("window_lo", 16, 40, [(341, 620), (300, 640), (17, 33)]),
    # a slot with no live page
    ("empty_slot", 16, 20, [(0, 200), (0, 0), (100, 101)]),
    # 256-token pages: P = 1
    ("p1", 256, 3, [(0, 768), (300, 700), (0, 1)]),
])
def test_pooled_matches_striped_bits(rng, case, bt, nb, live):
    pooled, striped, ppb = _both(rng, bt=bt, nb=nb, live=live)
    assert ppb == max(1, min(256 // bt, nb))
    _assert_bits(pooled, striped)
    if case == "empty_slot":
        assert float(np.abs(np.asarray(pooled[2][1])).max()) == 0.0


def test_pooled_matches_striped_fp16_meta(rng):
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8,
                      n_sink=4, fp8_meta=False)
    pooled, striped, _ = _both(rng, bt=16, nb=20, pol=pol,
                               live=[(3, 300), (0, 17)])
    _assert_bits(pooled, striped)


def test_pooled_unscrambled_and_unbounded(rng):
    """Identity table and no bounds (the unpruned walk) give the same bits."""
    b, bt, nb = 2, 16, 20
    k_qt, v_qt = _planes(rng, b, nb * bt, POL)
    tbl = jnp.asarray(np.arange(1, b * nb + 1).reshape(b, nb), jnp.int32)
    mask = _mask(b, nb * bt, [(7, 290), (0, 64)])
    q = jnp.asarray(rng.normal(size=(b, HKV, GQ, D)), jnp.float32)
    kp, vp = _pool(k_qt, tbl, bt), _pool(v_qt, tbl, bt)
    run = lambda bounds: decode_attn_pallas(
        q, kp, vp, mask, POL, D, D ** -0.5, interpret=True, block_s=bt,
        block_bounds=bounds, block_table=tbl)
    _assert_bits(run(seg.packed_block_bounds(mask, bt)), run(None))


def test_fp8_bits_decode_exact():
    codes = jnp.arange(256, dtype=jnp.int32)
    want = np.asarray(decode_fp8(codes.astype(jnp.uint8)))
    got = np.asarray(_fp8_to_f32(codes))
    np.testing.assert_array_equal(got.view(np.uint32)[~np.isnan(want)],
                                  want.view(np.uint32)[~np.isnan(want)])
    assert np.isnan(got[np.isnan(want)]).all()


def test_f16_bits_decode_exact():
    codes = jnp.arange(65536, dtype=jnp.int32)
    want = np.asarray(jax.lax.bitcast_convert_type(
        codes.astype(jnp.uint16), jnp.float16).astype(jnp.float32))
    got = np.asarray(_f16_to_f32(codes))
    fin = np.isfinite(want)
    np.testing.assert_array_equal(got.view(np.uint32)[fin],
                                  want.view(np.uint32)[fin])
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_array_equal(got[np.isinf(want)], want[np.isinf(want)])


def test_block_report_counts_compute_blocks():
    """At the long-context cell's numbers: 16-token pages, 1,535 pages per
    slot, ~1,040 live pages -> 66 compute blocks of 16 pages, of 96."""
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=64, window=32,
                      n_sink=5)
    bt, nb = 16, 1535
    max_len = nb * bt + pol.n_sink + pol.window
    cache = kvc.init_pooled_cache(4, max_len, 4, 128, pol, pool_blocks=8,
                                  block_tokens=bt)
    lengths = [16_700, 16_420, 16_900, 16_400]
    cache["length"] = jnp.asarray(lengths, jnp.int32)
    rep = decode_block_report(cache, pol, 128)
    assert rep["pages_per_block"] == 16
    assert -(-rep["total"] // rep["pages_per_block"]) == 96
    live = [-(-(n - pol.n_sink - pol.window) // bt) for n in lengths]
    assert np.asarray(rep["visited"]).tolist() == live
    assert min(live) >= 1023 and max(live) <= 1060
    assert (np.asarray(rep["compute_blocks_visited"]).tolist()
            == [-(-n // 16) for n in live])
    assert np.asarray(rep["compute_blocks_visited"]).tolist()[0] == 66
