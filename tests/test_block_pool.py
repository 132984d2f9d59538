"""Paged KV block pool: per-slot block tables, content-addressed prefix
sharing, and pooled-vs-striped decode parity (DESIGN.md §9).

Acceptance for the pool redesign:
  (a) pooled decode is bit-identical to the striped layout on BOTH
      backends, for uniform and mixed PolicySchedules, whole-prompt and
      chunked prefill — the pallas striped baseline runs at the pooled
      kernel's compute block, ``block_s == P * pool_block_tokens``, so the
      tile grid and flash merge order match exactly;
  (b) block tables are *data*: ragged traffic through the pooled engine
      never recompiles the decode executable;
  (c) identical prompt prefixes quantize once and share blocks
      copy-on-write; admission accounts in free blocks and drains FIFO
      under a tight pool without deadlock or stream changes;
  (d) multi-band (``L###``) cache groups survive reset_slot / insert_slot
      round-trips, striped and pooled.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy, PolicySchedule
from repro.core import kv_cache as kvc
from repro.core import segments as seg
from repro.core.block_pool import BlockPool, prefix_block_keys
from repro.kernels.decode_attn import pages_per_block
from repro.models.config import ArchConfig
from repro.models import backends as bk
from repro.models import transformer as T
from repro.serving import Engine, Request

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=16, d_ff=32, vocab_size=64)
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=16, n_sink=4)
FP16 = QuantPolicy(bits_k=16, bits_v=16, group_size=16, window=0, n_sink=0)
BT = 8
MAX_LEN = 68          # packed = 68 - 4 - 16 = 48 tokens = 6 BT-blocks


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(2))


def _prompts(rng, lens):
    return [np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)
            for n in lens]


def _run(params, policy, prompts, *, pool_blocks=None, backend="reference",
         prefill_chunk=None, max_new=8, slots=3, return_engine=False):
    eng = Engine(params, CFG, policy, batch_slots=slots, max_len=MAX_LEN,
                 backend=backend, steps_per_sync=4, pool_blocks=pool_blocks,
                 pool_block_tokens=BT, prefill_chunk=prefill_chunk)
    hs = [eng.submit(Request(prompt=p, max_new=max_new, temperature=0.0,
                             seed=i)) for i, p in enumerate(prompts)]
    eng.run(hs)
    streams = [h.result().tolist() for h in hs]
    return (streams, eng) if return_engine else streams


# --------------------------------------------------------- block index math

def test_block_index_math():
    assert seg.n_table_blocks(48, 8) == 6
    with pytest.raises(ValueError):
        seg.n_table_blocks(50, 8)          # ragged packed region
    tbl = jnp.asarray([[3, 1, 4], [2, 0, 5]], jnp.int32)
    lb = jnp.asarray([2, 0], jnp.int32)
    assert seg.physical_block(tbl, lb).tolist() == [4, 2]
    u = jnp.asarray([0, 7, 8, 17])
    assert seg.logical_block(u, 8).tolist() == [0, 0, 1, 2]
    assert seg.block_offset(u, 8).tolist() == [0, 7, 0, 1]
    # host-side span helper clips into the table like the device math
    assert list(seg.blocks_spanned(0, 8, 8, 6)) == [0]
    assert list(seg.blocks_spanned(7, 17, 8, 6)) == [0, 1, 2]
    assert list(seg.blocks_spanned(-5, 3, 8, 6)) == [0]
    assert list(seg.blocks_spanned(-9, -1, 8, 6)) == []
    assert list(seg.blocks_spanned(100, 108, 8, 6)) == [5]   # overshoot clip


# ------------------------------------------------------------ BlockPool unit

def test_block_pool_alloc_ref_cow():
    pool = BlockPool(4, n_slots=2, n_table=3, block_nbytes=100)
    a = pool.alloc(0)
    pool.assign(0, 0, a)
    pool.register("k0", a)
    assert pool.lookup("k0") == a and pool.used() == 1
    # second slot hits the registered block and refs it
    pool.ref(a)
    pool.assign(1, 0, a)
    # writer with refcount 2 -> copy-on-write to a fresh block
    kind, src, dst = pool.ensure_writable(0, 0)
    assert kind == "copy" and src == a and dst != a
    assert pool.tables[0, 0] == dst and pool.tables[1, 0] == a
    assert pool.cow_copies == 1 and pool.used() == 2
    # exclusive writer just drops the content hash
    assert pool.ensure_writable(1, 0) is None
    assert pool.lookup("k0") is None
    # unallocated table entry -> fresh alloc consuming the reservation
    pool.set_reservation(0, 1)
    avail = pool.available()
    kind2, fresh, _ = pool.ensure_writable(0, 2)
    assert kind2 == "alloc" and pool.tables[0, 2] == fresh
    assert pool.available() == avail      # reservation paid for the block
    pool.release_slot(0)
    pool.release_slot(1)
    assert pool.used() == 0 and pool.available() == 4
    assert (pool.tables == 0).all()


def test_block_pool_exhaustion_and_stats():
    pool = BlockPool(2, n_slots=1, n_table=4, block_nbytes=10)
    pool.assign(0, 0, pool.alloc(0))
    pool.assign(0, 1, pool.alloc(0))
    with pytest.raises(RuntimeError):
        pool.alloc(0)
    st = pool.stats()
    assert st["used"] == 2 and st["free"] == 0
    assert st["resident_bytes"] == 20 and st["peak_used"] == 2


def test_prefix_block_keys():
    prompt = list(range(40))
    full, tail = prefix_block_keys(prompt, n_sink=4, window=16,
                                   block_tokens=8, seed="s")
    # packed prompt span = 40 - 4 - 16 = 20 -> 2 full blocks + 4-token tail
    assert len(full) == 2 and tail.startswith("P4:")
    again, tail2 = prefix_block_keys(prompt, 4, 16, 8, seed="s")
    assert full == again and tail == tail2
    # sink tokens are part of every block's content chain
    flip = [99] + prompt[1:]
    alt, _ = prefix_block_keys(flip, 4, 16, 8, seed="s")
    assert alt[0] != full[0]
    # a different band/policy seed must not collide
    other, _ = prefix_block_keys(prompt, 4, 16, 8, seed="t")
    assert other[0] != full[0]
    # fully-windowed prompt: nothing packed, nothing to share
    assert prefix_block_keys(prompt[:20], 4, 16, 8) == ([], None)


# -------------------------------------------------- pooled cache primitives

def test_pooled_cache_reset_insert_roundtrip(rng):
    """reset_slot zeroes a pooled slot's table row but never the shared
    planes; insert_slot grafts striped fp leaves without needing a
    block_tbl on the source."""
    pooled = kvc.init_pooled_cache(2, MAX_LEN, CFG.n_kv_heads, CFG.head_dim,
                                   POL, pool_blocks=8, block_tokens=BT)
    pooled["block_tbl"] = pooled["block_tbl"].at[0].set(
        jnp.arange(1, 7, dtype=jnp.int32))
    planes = jax.random.randint(jax.random.PRNGKey(0),
                                pooled["qk_scale_hi"].shape, 0, 255,
                                jnp.int32).astype(jnp.uint8)
    pooled["qk_scale_hi"] = planes
    out = kvc.reset_slot(pooled, 0)
    assert (np.asarray(out["block_tbl"][0]) == 0).all()
    assert (np.asarray(out["block_tbl"][1]) ==
            np.asarray(pooled["block_tbl"][1])).all()
    np.testing.assert_array_equal(np.asarray(out["qk_scale_hi"]),
                                  np.asarray(planes))   # planes untouched
    striped_src = {k: jnp.ones(s, d) if k != "length"
                   else jnp.full(s, 5, d)
                   for k, (s, d) in kvc.cache_shapes(
                       1, MAX_LEN, CFG.n_kv_heads, CFG.head_dim, POL).items()}
    ins = kvc.insert_slot(out, 1, striped_src, src_slot=0)
    assert int(ins["length"][1]) == 5
    assert (np.asarray(ins["block_tbl"][1]) ==
            np.asarray(pooled["block_tbl"][1])).all()   # table preserved
    np.testing.assert_array_equal(np.asarray(ins["qk_scale_hi"]),
                                  np.asarray(planes))


def test_pooled_decode_append_and_gather_parity(rng):
    """Appending through a scrambled block table then gathering back is
    bit-identical to the striped cache."""
    b, n_kv, d = 2, CFG.n_kv_heads, CFG.head_dim
    striped = kvc.init_cache(b, MAX_LEN, n_kv, d, POL)
    pooled = kvc.init_pooled_cache(b, MAX_LEN, n_kv, d, POL,
                                   pool_blocks=2 * 6, block_tokens=BT)
    # slot tables deliberately non-contiguous and interleaved
    tbl = np.asarray([[3, 1, 7, 2, 9, 5], [4, 8, 12, 6, 10, 11]], np.int32)
    pooled["block_tbl"] = jnp.asarray(tbl)
    start = POL.n_sink + POL.window + BT * 2   # appends straddle blocks
    lens = jnp.asarray([start, start - 3])
    striped["length"] = lens
    pooled["length"] = lens
    for t in range(2 * BT):
        k = jax.random.normal(jax.random.PRNGKey(t), (b, 1, n_kv, d),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(100 + t), (b, 1, n_kv, d),
                              jnp.bfloat16)
        striped = kvc.decode_append(striped, k, v, POL)
        pooled = kvc.decode_append(pooled, k, v, POL)
    got = kvc.unpool_cache(pooled)
    for key in striped:
        np.testing.assert_array_equal(
            np.asarray(striped[key]).view(np.uint8),
            np.asarray(got[key]).view(np.uint8), err_msg=key)
    sk, sv, sp, sm = kvc.gather_attention_inputs(striped, CFG.head_dim, POL)
    pk, pv, pp, pm = kvc.gather_attention_inputs(pooled, CFG.head_dim, POL)
    np.testing.assert_array_equal(np.asarray(sk).view(np.uint8),
                                  np.asarray(pk).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(sv).view(np.uint8),
                                  np.asarray(pv).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(pp))
    np.testing.assert_array_equal(np.asarray(sm), np.asarray(pm))


def test_pool_copy_and_insert_blocks():
    pooled = kvc.init_pooled_cache(1, MAX_LEN, CFG.n_kv_heads, CFG.head_dim,
                                   POL, pool_blocks=8, block_tokens=BT)
    def _noise(key, like):
        return jax.random.randint(jax.random.PRNGKey(key), like.shape,
                                  0, 255, jnp.int32).astype(jnp.uint8)
    val = _noise(3, pooled["qk_scale_hi"])
    pooled["qk_scale_hi"] = val
    out = kvc.pool_copy_block(pooled, jnp.asarray([[2, 5], [0, 0]],
                                                  jnp.int32))
    np.testing.assert_array_equal(np.asarray(out["qk_scale_hi"][5]),
                                  np.asarray(val[2]))
    np.testing.assert_array_equal(np.asarray(out["qk_scale_hi"][0]),
                                  np.asarray(val[0]))   # null row is a no-op
    striped = kvc.init_cache(1, MAX_LEN, CFG.n_kv_heads, CFG.head_dim, POL)
    striped["qk_scale_hi"] = _noise(4, striped["qk_scale_hi"])
    ins = kvc.pool_insert_blocks(pooled, striped,
                                 jnp.asarray([[1, 3], [0, 0]], jnp.int32))
    want = np.asarray(striped["qk_scale_hi"][0]).reshape(6, BT, -1)[1]
    got = np.asarray(ins["qk_scale_hi"][3]).reshape(BT, -1)
    np.testing.assert_array_equal(got, want)


def test_pool_block_nbytes_vs_stripe():
    per_block = kvc.pool_block_nbytes(CFG.n_kv_heads, CFG.head_dim, POL, BT)
    sq = MAX_LEN - POL.n_sink - POL.window
    shapes = kvc.cache_shapes(1, MAX_LEN, CFG.n_kv_heads, CFG.head_dim, POL)
    stripe = sum(int(np.prod(s)) * np.dtype(d).itemsize
                 for k, (s, d) in shapes.items() if kvc.is_plane_key(k))
    assert per_block * (sq // BT) == stripe
    with pytest.raises(ValueError):
        kvc.pool_block_nbytes(CFG.n_kv_heads, CFG.head_dim, FP16, BT)


# ----------------------------------------------- engine parity (tentpole a)

MIXED = PolicySchedule(layers=(FP16, POL))
BANDED = PolicySchedule(layers=(
    QuantPolicy(bits_k=4.0, bits_v=4.0, group_size=16, window=16, n_sink=4),
    POL))


@pytest.mark.parametrize("backend_name", ["reference", "pallas"])
@pytest.mark.parametrize("policy", [POL, MIXED, BANDED],
                         ids=["uniform", "fp16_guard", "two_band"])
def test_pooled_engine_bit_parity(params, rng, backend_name, policy):
    tile = pages_per_block(BT, (MAX_LEN - POL.n_sink - POL.window) // BT) * BT
    backend = (bk.PallasBackend(block_s=tile) if backend_name == "pallas"
               else "reference")
    prompts = _prompts(rng, [40, 40, 33, 50, 27])
    striped = _run(params, policy, prompts, backend=backend)
    pooled, eng = _run(params, policy, prompts, pool_blocks=20,
                       backend=backend, return_engine=True)
    assert striped == pooled
    st = eng.stats()
    assert st["pooled"] and st["used"] == 0     # everything released
    assert st["peak_used"] > 0


def test_pooled_chunked_prefill_parity(params, rng):
    prompts = _prompts(rng, [40, 33, 50, 27])
    whole = _run(params, POL, prompts)
    striped = _run(params, POL, prompts, prefill_chunk=16)
    pooled = _run(params, POL, prompts, pool_blocks=20, prefill_chunk=16)
    assert whole == striped == pooled


# ------------------------------------------- tables are data (tentpole b)

def test_ragged_traffic_never_recompiles_decode(params, rng):
    prompts = _prompts(rng, [40, 33, 50, 27, 45, 29])
    _, eng = _run(params, POL, prompts, pool_blocks=20, return_engine=True)
    # six ragged requests over two admission waves permuted the block
    # tables many times; the scanned decode step must have ONE executable
    assert eng._multi is not None
    assert eng._multi._cache_size() == 1


# ------------------------------------- prefix sharing + CoW (tentpole c)

def test_shared_prefix_quantizes_once_and_cows(params, rng):
    prefix = np.asarray(rng.integers(0, CFG.vocab_size, (44,)), np.int32)
    prompts = [np.concatenate([prefix, np.asarray([i], np.int32)])
               for i in range(3)]
    striped = _run(params, POL, prompts, max_new=6)
    pooled, eng = _run(params, POL, prompts, max_new=6, pool_blocks=20,
                       return_engine=True)
    assert striped == pooled
    st = eng.stats()
    # packed span of the shared 44 tokens: (45-20)//8 = 3 full blocks, all
    # identical across the three requests -> requests 2..3 hit every full
    # block request 1 registered
    assert st["prefix_hits"] > 0 and st["cow_copies"] > 0
    assert st["prefix_hit_rate"] > 0.5
    assert st["peak_used"] < 3 * eng._pool_bands[0][5]  # beat the stripes


def test_tight_pool_stalls_then_drains_fifo(params, rng):
    prompts = _prompts(rng, [50, 50, 50, 50])
    roomy = _run(params, POL, prompts, slots=4, pool_blocks=30)
    eng = Engine(params, CFG, POL, batch_slots=4, max_len=MAX_LEN,
                 backend="reference", steps_per_sync=4, pool_blocks=13,
                 pool_block_tokens=BT)
    hs = [eng.submit(Request(prompt=p, max_new=8, temperature=0.0, seed=i))
          for i, p in enumerate(prompts)]
    stalled = False
    for _ in range(300):
        if all(h.finished for h in hs):
            break
        eng.step()
        stalled = stalled or "admission_stall" in eng.stats()
    assert all(h.finished for h in hs), "tight pool deadlocked"
    assert stalled, "13 blocks cannot admit four 6-block requests at once"
    assert [h.result().tolist() for h in hs] == roomy


def test_pool_validation_and_rejection(params):
    with pytest.raises(ValueError, match="not a multiple"):
        Engine(params, CFG, POL, batch_slots=2, max_len=MAX_LEN + 1,
               pool_blocks=8, pool_block_tokens=BT)
    with pytest.raises(ValueError, match="pool_block_tokens"):
        Engine(params, CFG, POL, batch_slots=2, max_len=MAX_LEN,
               pool_blocks=8, pool_block_tokens=4)
    with pytest.raises(ValueError, match="no band has a packed region"):
        Engine(params, CFG, FP16, batch_slots=2, max_len=MAX_LEN,
               pool_blocks=8, pool_block_tokens=BT)
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=MAX_LEN,
                 pool_blocks=3, pool_block_tokens=BT)
    with pytest.raises(ValueError, match="pool blocks"):
        eng.submit(Request(prompt=np.arange(50, dtype=np.int32), max_new=8))
    info = eng.backend_info
    assert info["pooled"] and info["pool_blocks"] == 3


# ------------------------------------- multi-band L### groups (satellite 3)

def test_multiband_reset_insert_roundtrip(params, rng):
    """A two-band schedule's band-keyed (L###) cache group survives slot
    reset + re-insert with no cross-band or cross-slot leakage — the
    engine-level slot lifecycle the pool's release path depends on."""
    prompts = _prompts(rng, [40, 40])
    for pool_blocks in (None, 20):
        streams, eng = _run(params, BANDED, prompts, slots=2,
                            pool_blocks=pool_blocks, return_engine=True)
        group = eng._caches["scan"]
        assert set(group) >= {"L000", "L001"}   # band-keyed layout held
        # slots were retired: every per-slot leaf is zero again
        for bkey in ("L000", "L001"):
            assert int(group[bkey]["length"].sum()) == 0
            if pool_blocks and "block_tbl" in group[bkey]:
                assert int(jnp.abs(group[bkey]["block_tbl"]).sum()) == 0
        # re-admitting through the same engine reproduces the streams
        hs = [eng.submit(Request(prompt=p, max_new=8, temperature=0.0,
                                 seed=i)) for i, p in enumerate(prompts)]
        eng.run(hs)
        assert [h.result().tolist() for h in hs] == streams
