"""Per-layer policy schedules (DESIGN.md §8).

Acceptance:
  (a) a UNIFORM schedule is bit-identical to the bare policy it wraps —
      prefill caches (leaf-for-leaf, same pytree structure), logits, decode
      steps, and greedy Engine streams, on BOTH decode backends;
  (b) mixed schedules run end-to-end: ``first_last_fp16`` keeps guard-layer
      caches as raw fp K/V leaves (dtype-checked) while interior layers pack
      planes, and the Engine serves it with per-layer avg-bits in
      ``backend_info``;
  (c) schedules stay jit-static: a schedule with <= 2 distinct policies
      compiles exactly one decode executable (jax counter-asserted, no
      extra compiles vs uniform);
  (d) the policy-validation bugfixes: ``reorder`` vs the baseline switches
      are mutually exclusive, and fp16 policies reject window/sink buffers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.policy import (QuantPolicy, PolicySchedule, SchedulePreset,
                               as_schedule, as_layer_policy, fp16_guard,
                               FP16_POLICY, PAPER_POLICY)
from repro.models.config import ArchConfig
from repro.models import transformer as T
from repro.serving import Engine, Request
from repro.testing import count_compiles

CFG = ArchConfig(name="t", family="dense", n_layers=4, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=32, d_ff=32, vocab_size=64)
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
BACKENDS = ["reference", "pallas"]


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(2))


def _prompt(rng, n):
    return np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- (d) policy validation

def test_reorder_excludes_baseline_switches():
    with pytest.raises(ValueError, match="mutually exclusive"):
        QuantPolicy(reorder=True, smooth=True)
    with pytest.raises(ValueError, match="mutually exclusive"):
        QuantPolicy(reorder=True, per_channel_key=True)
    # baselines set reorder=False — still expressible
    QuantPolicy(reorder=False, smooth=True)
    QuantPolicy(reorder=False, per_channel_key=True)


def test_fp16_rejects_window_and_sinks():
    with pytest.raises(ValueError, match="fp16"):
        QuantPolicy(bits_k=16.0, bits_v=16.0, clip=False, reorder=False,
                    window=8, n_sink=0)
    with pytest.raises(ValueError, match="fp16"):
        QuantPolicy(bits_k=16.0, bits_v=16.0, clip=False, reorder=False,
                    window=0, n_sink=2)
    assert FP16_POLICY.is_fp16  # the canonical fp16 policy stays valid


# --------------------------------------------------- presets, coercion, hash

def test_uniform_coercion_and_hashability():
    s = as_schedule(POL, 4)
    assert isinstance(s, PolicySchedule) and len(s) == 4 and s.is_uniform
    assert s[0] == POL and s[-1] == POL
    assert s == PolicySchedule.uniform(POL, 4)
    assert hash(s) == hash(PolicySchedule.uniform(POL, 4))
    assert {s: 1}[as_schedule(POL, 4)] == 1  # usable as a jit-static key
    assert as_schedule(s, 4) is s
    with pytest.raises(ValueError, match="covers 4 layers"):
        as_schedule(s, 6)


def test_unbound_presets_materialize():
    pre = PolicySchedule.first_last_fp16(PAPER_POLICY, 2)
    assert isinstance(pre, SchedulePreset)
    s = as_schedule(pre, 6)
    assert [p.is_fp16 for p in s] == [True, True, False, False, True, True]
    assert s[2] == PAPER_POLICY
    lad = as_schedule(PolicySchedule.bits_ladder(POL), 6)
    assert (lad[0].bits_k, lad[0].bits_v) == (4.0, 4.0)
    assert (lad[-1].bits_k, lad[-1].bits_v) == (2.0, 1.5)
    # guards must leave at least one quantized layer — no silent fp16 runs
    with pytest.raises(ValueError, match="NO quantized layers"):
        as_schedule(PolicySchedule.first_last_fp16(POL, 2), 4)


def test_bands_and_distinct():
    s = PolicySchedule.first_last_fp16(POL, 1, 4)
    bands = s.bands()
    assert [(a, b) for a, b, _ in bands] == [(0, 1), (1, 3), (3, 4)]
    assert bands[0][2].is_fp16 and not bands[1][2].is_fp16
    assert len(s.distinct()) == 2
    assert as_layer_policy(PolicySchedule.uniform(POL, 3)) == POL
    with pytest.raises(TypeError, match="per-layer"):
        as_layer_policy(s)


def test_stacked_calib_rejects_mixed_bit_layouts(params, rng):
    """A single stacked calibration table carries no plane-layout metadata,
    so mixed-bits schedules must refuse it instead of silently misaligning
    clip alphas (fp16 guard layers are exempt — alphas unused)."""
    toks = jnp.asarray(np.stack([_prompt(rng, 10)]))
    calib = T.identity_calib(CFG, POL)
    mixed = PolicySchedule.bits_ladder(POL, ((4.0, 4.0), (2.0, 1.5)),
                                       CFG.n_layers)
    with pytest.raises(ValueError, match="quantization layouts"):
        T.prefill_model(params, CFG, {"tokens": toks}, mixed, calib=calib,
                        max_len=32)
    # one quantized layout + fp16 guards: allowed
    guard = PolicySchedule.first_last_fp16(POL, 1, CFG.n_layers)
    T.prefill_model(params, CFG, {"tokens": toks}, guard, calib=calib,
                    max_len=32)


def test_for_arch_caps_local_windows():
    cfg = CFG.scaled(local_window=4, local_pattern=(1, 0))
    s = PolicySchedule.for_arch(POL, cfg)
    assert [p.window for p in s] == [4, 8, 4, 8]
    assert s[1] == POL


def test_schedule_accounting():
    s = PolicySchedule.first_last_fp16(POL, 1, 4)
    per = s.layer_avg_bits(32)
    assert per[0] == per[3] == 16.0
    assert per[1] == pytest.approx(POL.avg_bits(32))
    assert s.avg_bits(32) == pytest.approx(sum(per) / 4)
    assert as_schedule(POL, 4).avg_bits(32) == pytest.approx(POL.avg_bits(32))
    nb = s.layer_kv_bytes(32, n_kv=2)
    assert nb[0] == 2 * 2 * 32 * 2          # fp16: 2 bytes * D * H_kv * {K,V}
    assert nb[1] < nb[0]                    # packed layers are smaller
    table = s.layer_table(32, n_kv=2)
    assert len(table) == 4 and table[2]["bits_v"] == 1.5


# ----------------------------------------- (a) uniform-schedule bit-parity

@pytest.mark.parametrize("backend", BACKENDS)
def test_uniform_schedule_bitmatches_bare_policy(params, rng, backend):
    """Caches (structure + every leaf), prefill logits and a decode step are
    bit-identical between QuantPolicy and PolicySchedule.uniform."""
    toks = jnp.asarray(np.stack([_prompt(rng, 14) for _ in range(2)]))
    lg0, c0 = T.prefill_model(params, CFG, {"tokens": toks}, POL, max_len=40,
                              backend=backend)
    lg1, c1 = T.prefill_model(params, CFG, {"tokens": toks},
                              PolicySchedule.uniform(POL, CFG.n_layers),
                              max_len=40, backend=backend)
    np.testing.assert_array_equal(np.asarray(lg0), np.asarray(lg1))
    _assert_trees_equal(c0, c1)
    tok = jnp.argmax(lg0[:, -1:], -1).astype(jnp.int32)
    l0, d0 = T.decode_step(params, CFG, tok, c0, POL, backend=backend)
    l1, d1 = T.decode_step(params, CFG, tok, c1,
                           as_schedule(POL, CFG.n_layers), backend=backend)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    _assert_trees_equal(d0, d1)


@pytest.mark.parametrize("backend", BACKENDS)
def test_uniform_schedule_engine_stream_parity(params, rng, backend):
    """Greedy Engine streams under a uniform schedule exactly equal the
    bare-policy engine's streams (ragged prompts, 2 admission waves)."""
    prompts = [_prompt(rng, n) for n in (9, 12, 9)]

    def streams(policy):
        eng = Engine(params, CFG, policy, batch_slots=2, max_len=48,
                     backend=backend, steps_per_sync=4)
        hs = [eng.submit(Request(prompt=p, max_new=6)) for p in prompts]
        eng.run(hs)
        return [h.result().tolist() for h in hs]

    assert streams(POL) == streams(PolicySchedule.uniform(POL, CFG.n_layers))


# ------------------------------------------------- (b) mixed schedules e2e

def test_guard_layer_cache_dtypes(params, rng):
    """first_last_fp16 guard layers store raw fp K/V; interior layers store
    packed planes — checked on the band-keyed prefill caches."""
    toks = jnp.asarray(np.stack([_prompt(rng, 14)]))
    sched = PolicySchedule.first_last_fp16(POL, 1, CFG.n_layers)
    _, caches = T.prefill_model(params, CFG, {"tokens": toks}, sched,
                                max_len=40)
    group = caches["scan"]
    assert sorted(group) == ["L000", "L001", "L003"]  # 3 bands
    for key in ("L000", "L003"):                      # fp16 guard bands
        leaves = group[key]
        assert sorted(leaves) == ["k", "length", "v"]
        assert leaves["k"].dtype == toks_dtype(params)
        assert leaves["v"].dtype == toks_dtype(params)
    mid = group["L001"]                               # packed interior band
    assert "qk_codes_hi" in mid and mid["qk_codes_hi"].dtype == jnp.uint8
    assert "win_k" in mid and "sink_k" in mid
    assert mid["qk_codes_hi"].shape[0] == 2           # 2 stacked layers


def toks_dtype(params):
    return params["embed"].dtype


@pytest.mark.parametrize("backend", BACKENDS)
def test_first_last_fp16_engine_end_to_end(params, rng, backend):
    """The acceptance scenario: an UNBOUND first_last_fp16 preset serves
    end-to-end through the Engine; backend_info reports per-layer avg-bits."""
    sched = PolicySchedule.first_last_fp16(POL, 1)   # materializes in Engine
    eng = Engine(params, CFG, sched, batch_slots=2, max_len=48,
                 backend=backend, steps_per_sync=4)
    hs = [eng.submit(Request(prompt=_prompt(rng, n), max_new=5))
          for n in (9, 13, 11)]
    eng.run(hs)
    assert all(h.finished and len(h.tokens) == 5 for h in hs)
    info = eng.backend_info
    assert info["n_policies"] == 2 and not info["schedule_uniform"]
    assert len(info["layer_avg_bits"]) == CFG.n_layers
    assert info["layer_avg_bits"][0] == 16.0
    assert info["layer_avg_bits"][1] == pytest.approx(POL.avg_bits(32))
    assert info["avg_bits"] == pytest.approx(
        sum(info["layer_avg_bits"]) / CFG.n_layers)
    assert info["cache_bytes_per_slot"] == sum(info["layer_cache_bytes"])


def test_mixed_schedule_chunked_prefill_matches_whole_prompt(params, rng):
    """Chunked prefill under a mixed schedule produces the same greedy
    streams as whole-prompt admission (the §7 invariant holds per band)."""
    sched = PolicySchedule.first_last_fp16(POL, 1, CFG.n_layers)
    prompts = [_prompt(rng, n) for n in (9, 17, 12)]

    def streams(chunk):
        eng = Engine(params, CFG, sched, batch_slots=2, max_len=64,
                     backend="reference", steps_per_sync=4,
                     prefill_chunk=chunk)
        hs = [eng.submit(Request(prompt=p, max_new=6)) for p in prompts]
        eng.run(hs)
        return [h.result().tolist() for h in hs]

    assert streams(None) == streams(8)


def test_backend_parity_under_mixed_schedule(params, rng):
    """Both backends agree on a mixed schedule's decode output (guard bands
    take the dense fp16 path, interior bands the packed path)."""
    toks = jnp.asarray(np.stack([_prompt(rng, 14) for _ in range(2)]))
    sched = PolicySchedule.first_last_fp16(POL, 1, CFG.n_layers)
    lg, caches = T.prefill_model(params, CFG, {"tokens": toks}, sched,
                                 max_len=40)
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    l_ref, _ = T.decode_step(params, CFG, tok, caches, sched,
                             backend="reference")
    l_pal, _ = T.decode_step(params, CFG, tok, caches, sched,
                             backend="pallas")
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_pal),
                               rtol=2e-2, atol=2e-2)
    assert (np.asarray(l_ref[:, -1].argmax(-1))
            == np.asarray(l_pal[:, -1].argmax(-1))).all()


# --------------------------------------------- (c) no-extra-compiles static

def test_two_policy_schedule_compiles_once(params, rng):
    """A schedule with 2 distinct policies compiles exactly ONE decode
    executable — bands live inside the jitted step, and repeated steps at
    new cache lengths hit the jit cache (zero further compilations)."""
    toks = jnp.asarray(np.stack([_prompt(rng, 12) for _ in range(2)]))
    sched = PolicySchedule.first_last_fp16(POL, 1, CFG.n_layers)
    _, caches = T.prefill_model(params, CFG, {"tokens": toks}, sched,
                                max_len=48)
    fn = jax.jit(lambda p, t, c: T.decode_step(p, CFG, t, c, sched,
                                               backend="reference"))
    tok = jnp.zeros((2, 1), jnp.int32)
    with count_compiles() as n:
        _, caches = fn(params, tok, caches)
    assert n() == 1                      # warmup: exactly one executable
    with count_compiles() as n:
        for _ in range(3):                # lengths advance -> traced, cached
            _, caches = fn(params, tok, caches)
    assert n() == 0, f"schedule decode recompiled {n()}x"
