"""Length-aware block pruning in the decode path (DESIGN.md §4).

The fused kernel must do work proportional to *live* tokens, not capacity:
per-slot ``[lo, hi)`` block bounds (``segments.packed_block_bounds``) ride
in via scalar prefetch, out-of-range grid steps re-request the previous
block (DMA elided) and skip the math.  A skipped block is exactly a no-op,
so pruning is bit-identical — asserted here at block_s edges, for empty
slots, for windowed layers with ``lo > 0``, and for mixed-occupancy ragged
batches, on both backends; plus the blocks-visited regression guard
(``<= ceil(live / block_s) + 1`` per slot).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.core import kv_cache as kvc
from repro.core import segments as seg
from repro.core.quant import quantize_groups
from repro.models.config import ArchConfig
from repro.models import backends as B
from repro.models.attention import decode_attention_skvq
from repro.kernels.decode_attn import decode_attn_pallas
from repro.kernels.ops import decode_block_report
from repro.testing import count_compiles

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=32, d_ff=32, vocab_size=64)
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
BS = 8                                 # small block_s so edges are reachable

REF = B.get_backend("reference")
PAL = B.PallasBackend(block_s=BS)
PAL_OFF = B.PallasBackend(block_s=BS, prune_blocks=False)


def _ragged_cache(rng, lengths, max_len=96, h=2, d=32):
    """Cache whose packed planes are written to the longest slot's frontier,
    then clamped to per-slot ``lengths`` — exactly the ragged serving state
    (stale rows past each frontier exist and must be pruned/masked)."""
    b = len(lengths)
    s = max(lengths)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    cache = kvc.prefill(k, v, max_len, POL)
    return dict(cache, length=jnp.asarray(lengths, jnp.int32))


def _q(rng, b, hq=4, d=32):
    return jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.float32)


def _attend_all(q, cache, **kw):
    ref = REF.attend(q, cache, CFG, POL, dtype=jnp.float32, **kw)
    pruned = PAL.attend(q, cache, CFG, POL, dtype=jnp.float32, **kw)
    unpruned = PAL_OFF.attend(q, cache, CFG, POL, dtype=jnp.float32, **kw)
    return ref, pruned, unpruned


def _check(q, cache, **kw):
    ref, pruned, unpruned = _attend_all(q, cache, **kw)
    np.testing.assert_array_equal(
        np.asarray(pruned), np.asarray(unpruned),
        err_msg="pruned kernel must be bit-identical to the unpruned walk")
    np.testing.assert_allclose(np.asarray(pruned), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------- parity cases

@pytest.mark.parametrize("delta", [-1, 0, 1])
@pytest.mark.parametrize("edge_blocks", [1, 3])
def test_parity_at_block_edges(delta, edge_blocks, rng):
    """Packed counts exactly on / one off a block_s edge (the clamp math's
    fencepost regime)."""
    qc = edge_blocks * BS + delta
    length = qc + POL.n_sink + POL.window
    cache = _ragged_cache(rng, [length, length])
    _check(_q(rng, 2), cache)
    rep = decode_block_report(cache, POL, CFG.head_dim, block_s=BS)
    np.testing.assert_array_equal(np.asarray(rep["bounds"][:, 0]), 0)
    np.testing.assert_array_equal(np.asarray(rep["bounds"][:, 1]),
                                  -(-qc // BS))


def test_zero_packed_slot_all_window(rng):
    """A slot whose whole history fits in sinks + window has zero packed
    tokens: its bounds are empty and the kernel touches (at most) one
    clamped block for it."""
    lengths = [POL.n_sink + POL.window, 60]   # slot 0: nothing packed
    cache = _ragged_cache(rng, lengths)
    _check(_q(rng, 2), cache)
    rep = decode_block_report(cache, POL, CFG.head_dim, block_s=BS)
    lo, hi = np.asarray(rep["bounds"])[0]
    assert lo == hi == 0
    assert int(np.asarray(rep["visited"])[0]) == 1
    # striped: each block is its own compute block
    assert rep["pages_per_block"] == 1
    np.testing.assert_array_equal(np.asarray(rep["compute_blocks_visited"]),
                                  np.asarray(rep["visited"]))


def test_windowed_layer_lower_bound(rng):
    """A local-attention layer (traced window) never attends below
    ``t_now - w_eff`` — the pruning lower bound must rise above 0 and the
    outputs must stay bit-identical to the unpruned kernel."""
    cache = _ragged_cache(rng, [80, 80], max_len=96)
    w = jnp.int32(12)
    _check(_q(rng, 2), cache, window=w)
    rep = decode_block_report(cache, POL, CFG.head_dim, window=w, block_s=BS)
    bounds = np.asarray(rep["bounds"])
    assert (bounds[:, 0] > 0).all(), bounds
    # global layer on the same cache reaches back to block 0
    rep_g = decode_block_report(cache, POL, CFG.head_dim, block_s=BS)
    assert (np.asarray(rep_g["bounds"])[:, 0] == 0).all()
    assert (bounds[:, 1] - bounds[:, 0]
            < np.asarray(rep_g["visited"])).all(), "window must prune blocks"


def test_mixed_occupancy_ragged_batch(rng):
    """Slots at ~1% / ~50% / 100% of the packed capacity in one batch."""
    cache = _ragged_cache(rng, [POL.n_sink + POL.window + 1, 48, 96],
                          max_len=96)
    _check(_q(rng, 3), cache)
    rep = decode_block_report(cache, POL, CFG.head_dim, block_s=BS)
    vis = np.asarray(rep["visited"])
    assert vis[0] < vis[1] < vis[2], vis


def test_parity_under_jit_traced_lengths(rng):
    """The serving path: lengths are traced, the grid stays capacity-sized,
    and pruning rides on the remap + skip — same numbers as eager, and
    growing lengths never recompile (the bounds are traced too)."""
    cache = _ragged_cache(rng, [20, 60])
    q = _q(rng, 2)

    @jax.jit
    def attend(q, cache):
        return PAL.attend(q, cache, CFG, POL, dtype=jnp.float32)

    np.testing.assert_allclose(
        np.asarray(attend(q, cache)),
        np.asarray(PAL.attend(q, cache, CFG, POL, dtype=jnp.float32)),
        atol=1e-6, rtol=1e-6)
    with count_compiles() as n_compiles:
        for lens in ([21, 61], [40, 96], [12, 13]):
            out = attend(q, dict(cache, length=jnp.asarray(lens, jnp.int32)))
            out.block_until_ready()
    assert n_compiles() == 0, (
        f"block pruning recompiled {n_compiles()}x as slot lengths moved")


# ------------------------------------------------- kernel-level bitwise gate

def test_flash_triple_bit_identical(rng):
    """The raw flash triple (num, m, l) — not just the merged output — must
    be bitwise unchanged by pruning."""
    b, s, hkv, gq, d = 2, 64, 2, 4, 32
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=0,
                      n_sink=0)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, hkv, gq, d)), jnp.float32)
    k_qt = quantize_groups(k, pol.bits_k, 16, fp8_meta=pol.fp8_meta)
    v_qt = quantize_groups(v, pol.bits_v, 16, fp8_meta=pol.fp8_meta)
    lens = jnp.asarray([9, 40])
    ok = (jnp.arange(s)[None, :] < lens[:, None])
    bounds = seg.packed_block_bounds(ok, BS)
    base = decode_attn_pallas(q, k_qt, v_qt, ok.astype(jnp.float32), pol, d,
                              d ** -0.5, block_s=BS)
    pruned = decode_attn_pallas(q, k_qt, v_qt, ok.astype(jnp.float32), pol, d,
                                d ** -0.5, block_s=BS, block_bounds=bounds)
    for name, a, b_ in zip(("num", "m", "l"), base, pruned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=name)


# ------------------------------------------------------- regression guards

def test_blocks_visited_bound(rng):
    """Pruned kernel visits <= ceil(live / block_s) + 1 blocks per slot
    (the +1 is the single clamped fetch of an empty slot)."""
    lengths = [POL.n_sink + POL.window, 13, 29, 48, 96]
    cache = _ragged_cache(rng, lengths, max_len=96)
    for w in (None, jnp.int32(12)):
        rep = decode_block_report(cache, POL, CFG.head_dim, window=w,
                                  block_s=BS)
        lens = np.asarray(lengths)
        live = np.maximum(lens - POL.n_sink - POL.window, 0)
        if w is not None:
            live = np.minimum(live, int(w))  # window caps reachable history
        bound = -(-live // BS) + 1
        vis = np.asarray(rep["visited"])
        assert (vis <= bound).all(), (vis, bound, w)


def test_bounds_match_mask_exactly(rng):
    """packed_block_bounds is tight: every attendable token is inside
    [lo, hi) and the boundary blocks actually contain one."""
    ok = jnp.asarray(rng.random((4, 40)) < 0.15)
    bounds = np.asarray(seg.packed_block_bounds(ok, 8))
    blk = np.asarray(seg.block_live(ok, 8))
    for r in range(4):
        lo, hi = bounds[r]
        assert not blk[r, :lo].any() and not blk[r, hi:].any()
        if blk[r].any():
            assert blk[r, lo] and blk[r, hi - 1]
        else:
            assert lo == hi == 0


# ------------------------------------------- reference backend chunk mirror

def test_reference_chunk_scan_prunes_and_matches(rng):
    """The reference backend's chunk-tiled scan mirrors the bounds via
    lax.cond; outputs match the unchunked and unpruned paths."""
    cache = _ragged_cache(rng, [20, 88], max_len=96)
    q = _q(rng, 2)
    dense = decode_attention_skvq(q, cache, CFG, POL, dtype=jnp.float32)
    for prune in (True, False):
        tiled = decode_attention_skvq(q, cache, CFG, POL, dtype=jnp.float32,
                                      chunk=14, prune_blocks=prune)
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(dense),
                                   atol=2e-5, rtol=1e-4)


# ------------------------------------------------------ interpret resolution

def test_interpret_env_override(monkeypatch):
    from repro.kernels import _compat as CC
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    auto = jax.default_backend() != "tpu"
    assert CC.resolve_interpret(None) is auto
    assert CC.interpret_mode_info(None)["source"] == "auto"
    monkeypatch.setenv(CC.ENV_VAR, "0")
    assert CC.resolve_interpret(None) is False
    assert CC.interpret_mode_info(None)["source"].startswith("env:")
    monkeypatch.setenv(CC.ENV_VAR, "true")
    assert CC.resolve_interpret(None) is True
    # explicit argument always wins
    assert CC.resolve_interpret(False) is False
    assert CC.interpret_mode_info(False) == {"interpret": False,
                                             "source": "explicit"}


def test_backend_info_reports_resolved_mode():
    info = B.PallasBackend().info()
    assert set(info) >= {"name", "interpret", "source", "prune_blocks"}
    assert info["interpret"] == (jax.default_backend() != "tpu")
    ref = B.get_backend("reference").info()
    assert ref["name"] == "reference" and ref["interpret"] is None


def test_engine_backend_info(rng):
    from repro.models import transformer as T
    from repro.serving import Engine
    params = T.init_params(CFG, jax.random.PRNGKey(0))
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=32,
                 backend=B.PallasBackend(block_s=BS))
    info = eng.backend_info
    assert info["name"] == "pallas" and info["block_s"] == BS
    assert isinstance(info["interpret"], bool)
