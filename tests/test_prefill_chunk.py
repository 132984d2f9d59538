"""Chunked prefill (DESIGN.md §7): bit-parity with whole-prompt prefill,
bounded compile shapes, and no decode stalls.

Acceptance:
  (a) the chunk-grown SKVQ cache and the final-token logits are bit-identical
      to whole-prompt ``prefill_model`` — ragged lengths, prompts spanning
      the window+packed boundary, both decode backends;
  (b) greedy Engine streams with ``prefill_chunk`` set exactly equal the
      whole-prompt engine's streams;
  (c) ragged traffic (>= 6 distinct prompt lengths) compiles at most
      ``len(chunk_buckets)`` prefill executables — new lengths hit the jit
      cache (asserted with jax's compilation counters);
  (d) a long prompt prefilling in chunks never stalls the decode lanes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.core import kv_cache as kvc
from repro.models.config import ArchConfig
from repro.models import transformer as T
from repro.serving import Engine, Request, default_chunk_buckets
from repro.testing import count_compiles

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=32, d_ff=32, vocab_size=64)
# window 8 + 4 sinks: prompts longer than 12 span all three segments
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
BACKENDS = ["reference", "pallas"]


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(2))


def _prompt(rng, n):
    return np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)


def _run_chunked(params, prompt, max_len, buckets, chunk):
    """Drive T.prefill_chunk by hand; returns (logits, caches)."""
    state = T.prefill_chunk_init(CFG, POL, max_len, max_len + max(buckets))
    fn = jax.jit(lambda p, tk, st, a, b: T.prefill_chunk(
        p, CFG, tk, st, POL, a, b))
    pos, logits = 0, None
    while pos < len(prompt):
        n = min(chunk, len(prompt) - pos)
        bucket = next(b for b in buckets if b >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        logits, state = fn(params, jnp.asarray(toks), state,
                           jnp.int32(pos), jnp.int32(n))
        pos += n
    return logits, state["caches"]


# ------------------------------------------------- (a) cache/logits bit-parity

@pytest.mark.parametrize("plen", [3, 7, 11, 13, 23, 31])
def test_chunk_grown_cache_bitmatches_whole_prompt(params, rng, plen):
    """Every cache leaf and the last-token logits must be bit-identical,
    from shorter-than-one-bucket prompts up to prompts whose tail crossed
    the window+packed boundary mid-prefill."""
    prompt = _prompt(rng, plen)
    max_len = 40
    ref_logits, ref_caches = jax.jit(
        lambda p, t: T.prefill_model(p, CFG, {"tokens": t}, POL,
                                     max_len=max_len))(
        params, jnp.asarray(prompt[None]))
    logits, caches = _run_chunked(params, prompt, max_len, (4, 8), chunk=8)
    np.testing.assert_array_equal(np.asarray(ref_logits), np.asarray(logits))
    for name in ref_caches["scan"]:
        np.testing.assert_array_equal(
            np.asarray(ref_caches["scan"][name]),
            np.asarray(caches["scan"][name]), err_msg=name)


def test_no_headroom_workspace_is_safe(params, rng):
    """cap == max_len (zero bucket headroom) must stay bit-exact: bucket
    padding rows are scatter-dropped, never clamped into real workspace
    rows (regression: dynamic_update_slice clamping corrupted the tail)."""
    prompt = _prompt(rng, 30)
    ref_logits, ref_caches = jax.jit(
        lambda p, t: T.prefill_model(p, CFG, {"tokens": t}, POL,
                                     max_len=30))(
        params, jnp.asarray(prompt[None]))
    state = T.prefill_chunk_init(CFG, POL, 30, 30)
    fn = jax.jit(lambda p, tk, st, a, b: T.prefill_chunk(
        p, CFG, tk, st, POL, a, b))
    pos, logits = 0, None
    while pos < 30:
        n = min(8, 30 - pos)
        toks = np.zeros((1, 8), np.int32)
        toks[0, :n] = prompt[pos:pos + n]
        logits, state = fn(params, jnp.asarray(toks), state,
                           jnp.int32(pos), jnp.int32(n))
        pos += n
    np.testing.assert_array_equal(np.asarray(ref_logits), np.asarray(logits))
    for name in ref_caches["scan"]:
        np.testing.assert_array_equal(
            np.asarray(ref_caches["scan"][name]),
            np.asarray(state["caches"]["scan"][name]), err_msg=name)


def test_chunk_size_does_not_change_bits(params, rng):
    """Different chunkings of the same prompt agree bit-for-bit with each
    other (transitively via the whole-prompt reference)."""
    prompt = _prompt(rng, 29)
    l4, c4 = _run_chunked(params, prompt, 48, (4,), chunk=4)
    l16, c16 = _run_chunked(params, prompt, 48, (8, 16), chunk=16)
    np.testing.assert_array_equal(np.asarray(l4), np.asarray(l16))
    for name in c4["scan"]:
        np.testing.assert_array_equal(np.asarray(c4["scan"][name]),
                                      np.asarray(c16["scan"][name]),
                                      err_msg=name)


# ------------------------------------------------ (b) engine stream bit-parity

@pytest.mark.parametrize("backend", BACKENDS)
def test_chunked_engine_streams_bitmatch_whole_prompt(params, rng, backend):
    """Greedy streams through a chunked-prefill Engine == the whole-prompt
    Engine, over ragged lengths spanning the window+packed boundary, with
    slot reuse across admission waves — on both decode backends.  The long
    prompt comes FIRST so later short prompts prefill through a recycled
    dirty workspace (stale rows must be unreachable behind the causal
    mask)."""
    lens = [31, 9, 23, 17, 5, 13]
    reqs = [(_prompt(rng, n), 2 + (i % 4)) for i, n in enumerate(lens)]

    def serve(chunk):
        eng = Engine(params, CFG, POL, batch_slots=2, max_len=48,
                     steps_per_sync=4, backend=backend, prefill_chunk=chunk)
        hs = [eng.submit(Request(prompt=p, max_new=m)) for p, m in reqs]
        eng.run(hs)
        return eng, [h.result() for h in hs]

    eng, chunked = serve(8)
    _, whole = serve(None)
    for a, b in zip(chunked, whole):
        np.testing.assert_array_equal(a, b)
    assert set(eng.prefill_shapes) <= set(eng.chunk_buckets)


# ----------------------------------------------- (c) bounded compile shapes

def test_ragged_traffic_bounded_prefill_compiles(params, rng):
    """>= 6 distinct prompt lengths compile <= len(chunk_buckets) prefill
    executables, and once the buckets are warm, arbitrarily new prompt
    lengths trigger ZERO further jit compilations (jax counter-asserted)."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=64,
                 steps_per_sync=4, prefill_chunk=8)
    wave1 = [eng.submit(Request(prompt=_prompt(rng, n), max_new=2))
             for n in (5, 9, 14, 22, 27, 33)]
    eng.run(wave1)
    assert len(eng.prefill_shapes) <= len(eng.chunk_buckets)
    assert set(eng.prefill_shapes) <= set(eng.chunk_buckets)

    # six MORE distinct, previously-unseen lengths: everything is warm
    with count_compiles() as n_compiles:
        wave2 = [eng.submit(Request(prompt=_prompt(rng, n), max_new=2))
                 for n in (6, 11, 18, 25, 30, 38)]
        eng.run(wave2)
    assert n_compiles() == 0, (
        f"chunked prefill recompiled {n_compiles()}x on new prompt lengths")
    assert all(h.finished for h in wave2)

    # contrast: whole-prompt admission compiles per new length
    whole = Engine(params, CFG, POL, batch_slots=2, max_len=64,
                   steps_per_sync=4)
    eng_warm = [whole.submit(Request(prompt=_prompt(rng, 9), max_new=2))]
    whole.run(eng_warm)
    with count_compiles() as n_compiles:
        h = whole.submit(Request(prompt=_prompt(rng, 10), max_new=2))
        whole.run([h])
    assert n_compiles() > 0


def test_default_chunk_buckets_ladder():
    assert default_chunk_buckets(64) == (8, 16, 32, 64)
    assert default_chunk_buckets(8) == (8,)
    assert default_chunk_buckets(4) == (4,)


# --------------------------------------------------- (d) no decode stalls

def test_prefill_does_not_stall_decode(params, rng):
    """While a long prompt prefills chunk-by-chunk, the already-active slot
    keeps receiving a full decode chunk every step."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=128,
                 steps_per_sync=2, prefill_chunk=8)
    active = eng.submit(Request(prompt=_prompt(rng, 6), max_new=40))
    eng.step()                                  # admit + first decode chunk
    assert len(active.tokens) > 0
    long_h = eng.submit(Request(prompt=_prompt(rng, 80), max_new=4))

    stalled = False
    while long_h.first_token_time is None:
        before = len(active.tokens)
        eng.step()                              # one prefill chunk + decode
        if not active.finished and len(active.tokens) == before:
            stalled = True
    assert not stalled, "decode lane starved during chunked prefill"
    assert len(active.tokens) >= 80 // 8        # prefill took >= 10 steps
    eng.run()
    assert long_h.finished and active.finished


def test_prefill_job_reserves_slot_without_decoding_it(params, rng):
    """The reserved slot must not emit tokens until its prefill lands."""
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=64,
                 steps_per_sync=2, prefill_chunk=8)
    h = eng.submit(Request(prompt=_prompt(rng, 20), max_new=3))
    eng.step()                                  # chunk 1 of 3 — no tokens yet
    assert len(h.tokens) == 0 and h.first_token_time is None
    eng.run([h])
    assert h.finished and len(h.tokens) == 3


# ----------------------------------------------------- kv-level chunk append

def test_prefill_chunk_append_matches_sequential_appends(rng):
    """prefill_chunk_append == a loop of decode_append over the valid tokens;
    bucket-padding rows beyond n_valid leave every leaf untouched."""
    k = jnp.asarray(rng.normal(size=(2, 20, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 20, 2, 32)), jnp.float32)
    cache = kvc.prefill(k[:, :14], v[:, :14], 40, POL)

    chunk_k = jnp.asarray(rng.normal(size=(2, 8, 2, 32)), jnp.float32)
    chunk_v = jnp.asarray(rng.normal(size=(2, 8, 2, 32)), jnp.float32)
    got = kvc.prefill_chunk_append(cache, chunk_k, chunk_v, POL, n_valid=5)

    want = cache
    for i in range(5):
        want = kvc.decode_append(want, chunk_k[:, i:i + 1],
                                 chunk_v[:, i:i + 1], POL)
    for name in want:
        np.testing.assert_array_equal(np.asarray(want[name]),
                                      np.asarray(got[name]), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got["length"]), [19, 19])


def test_decode_append_valid_false_is_noop(rng):
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, 2, 32)), jnp.float32)
    cache = kvc.prefill(k, v, 40, POL)
    tok_k = jnp.asarray(rng.normal(size=(2, 1, 2, 32)), jnp.float32)
    tok_v = jnp.asarray(rng.normal(size=(2, 1, 2, 32)), jnp.float32)
    out = kvc.decode_append(cache, tok_k, tok_v, POL,
                            valid=jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(out["length"]), [17, 16])
    ref = kvc.decode_append(cache, tok_k, tok_v, POL)
    for name in cache:
        if name == "length":
            continue
        # row 0 took the append, row 1 kept its pre-append bits
        np.testing.assert_array_equal(np.asarray(out[name][0]),
                                      np.asarray(ref[name][0]), err_msg=name)
        np.testing.assert_array_equal(np.asarray(out[name][1]),
                                      np.asarray(cache[name][1]),
                                      err_msg=name)


# --------------------------------------------------------------- validation

def test_engine_chunk_validation(params):
    with pytest.raises(ValueError, match="prefill_chunk"):
        Engine(params, CFG, POL, batch_slots=1, max_len=32, prefill_chunk=0)
    with pytest.raises(ValueError, match="chunk_buckets"):
        Engine(params, CFG, POL, batch_slots=1, max_len=32,
               prefill_chunk=8, chunk_buckets=(4,))
    with pytest.raises(ValueError, match="chunk_buckets"):
        Engine(params, CFG, POL, batch_slots=1, max_len=32, chunk_buckets=(8,))
    ssm = ArchConfig(name="s", family="ssm", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=32, d_ff=32,
                     vocab_size=64)
    with pytest.raises(NotImplementedError, match="dense"):
        Engine(params, ssm, POL, batch_slots=1, max_len=32, prefill_chunk=8)


def test_submit_validation_names_fields(params):
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=32)
    with pytest.raises(ValueError, match=r"Request\.prompt length \(30\)"):
        eng.submit(Request(prompt=np.zeros(30, np.int32), max_new=8))
    with pytest.raises(ValueError, match=r"max_len=32"):
        eng.submit(Request(prompt=np.zeros(30, np.int32), max_new=8))
