"""Throughput-mode serving harness (DESIGN.md §10): warmup cache, async
host loop, open-loop load generator, and SLA accounting.

Acceptance:
  (a) ``poisson_trace`` is a pure function of its ``WorkloadSpec`` — same
      seed, same trace, byte for byte (times, lengths, token ids);
  (b) after ``Engine.warmup()`` a mixed ragged workload (chunked prefill +
      decode, pool enabled) triggers ZERO new XLA compiles — asserted with
      jax's compile counter AND the engine's own post-warmup counter;
  (c) the async host loop is bit-identical to the synchronous path — same
      tokens, same finish reasons — on both decode backends;
  (d) an engine shut down mid-stream drains gracefully: no deadlock, and
      every token the host loop delivered is a prefix of the sync stream;
  (e) ``pool_memory_bytes`` sizes the block pool from a byte budget
      (round-down warns, explicit ``pool_blocks`` overrides with a warning,
      a budget below one block raises);
  (f) ``Engine.stats()`` exposes cumulative scheduler counters (admissions,
      pool-exhausted stalls, CoW copies, decode syncs).
"""
import threading
import time

import numpy as np
import pytest

import jax

from repro.core.policy import QuantPolicy
from repro.models.config import ArchConfig
from repro.models import transformer as T
from repro.serving import (Engine, Request, WorkloadSpec, poisson_trace,
                           run_open_loop, HostLoop, TokenDelivery,
                           MetricsRecorder, RequestRecord, percentiles,
                           goodput, find_saturation)
from repro.testing import count_compiles

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=32, d_ff=32, vocab_size=64)
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
BACKENDS = ["reference", "pallas"]
# pool tiling: packed = max_len - (window + n_sink) must divide into
# pool_block_tokens blocks -> 44 - 12 = 32 = 4 x 8
POOL_LEN, POOL_BT = 44, 8


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(2))


def _prompt(rng, n):
    return np.asarray(rng.integers(0, CFG.vocab_size, (n,)), np.int32)


# ------------------------------------------------ (a) loadgen determinism

def test_poisson_trace_deterministic():
    spec = WorkloadSpec(n_requests=12, arrival_rate=5.0,
                        prompt_lens=(8, 12, 16), max_news=(2, 4),
                        shared_prefix_ratio=0.5, shared_prefix_len=4,
                        vocab=CFG.vocab_size, seed=7)
    a, b = poisson_trace(spec), poisson_trace(spec)
    assert [x.t for x in a] == [x.t for x in b]
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa.request.prompt, xb.request.prompt)
        assert xa.request.max_new == xb.request.max_new
        assert xa.request.seed == xb.request.seed
    # a different seed must actually change the trace
    c = poisson_trace(WorkloadSpec(n_requests=12, arrival_rate=5.0,
                                   prompt_lens=(8, 12, 16), max_news=(2, 4),
                                   shared_prefix_ratio=0.5,
                                   shared_prefix_len=4,
                                   vocab=CFG.vocab_size, seed=8))
    assert [x.t for x in a] != [x.t for x in c]
    # arrival times are strictly increasing (Poisson gaps are > 0 a.s.)
    assert all(a[i].t < a[i + 1].t for i in range(len(a) - 1))


def test_poisson_trace_shared_prefix():
    spec = WorkloadSpec(n_requests=32, arrival_rate=10.0,
                        prompt_lens=(12, 16), max_news=(2,),
                        shared_prefix_ratio=0.5, shared_prefix_len=6,
                        vocab=CFG.vocab_size, seed=0)
    trace = poisson_trace(spec)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, CFG.vocab_size, size=6)
    shared = [a for a in trace
              if np.array_equal(a.request.prompt[:6], prefix)]
    # ratio=0.5 over 32 draws: both populations must be represented
    assert 0 < len(shared) < len(trace)
    # every prompt still hits its drawn mix length exactly
    assert all(len(a.request.prompt) in (12, 16) for a in trace)


def test_workload_spec_validation():
    with pytest.raises(ValueError, match="arrival_rate"):
        WorkloadSpec(arrival_rate=0.0)
    with pytest.raises(ValueError, match="n_requests"):
        WorkloadSpec(n_requests=0)
    with pytest.raises(ValueError, match="shared_prefix_ratio"):
        WorkloadSpec(shared_prefix_ratio=1.5)
    with pytest.raises(ValueError, match="shared_prefix_len"):
        WorkloadSpec(shared_prefix_ratio=0.5, shared_prefix_len=0)
    with pytest.raises(ValueError, match="shorter than"):
        WorkloadSpec(shared_prefix_ratio=0.5, shared_prefix_len=24,
                     prompt_lens=(24, 40))


# --------------------------------------- (b) zero compiles after warmup

def test_zero_compiles_after_warmup(params, rng):
    """The tentpole acceptance: AOT warmup + host-path rehearsal, then a
    mixed ragged open-loop workload (chunked prefill + decode, pool on)
    completes with ZERO new XLA compiles."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4, prefill_chunk=8,
                 pool_blocks=24, pool_block_tokens=POOL_BT, async_host=True)
    rep = eng.warmup()
    assert rep["warmed"] and rep["n_executables"] > 0
    assert rep["post_warmup_compiles"] == 0

    spec = WorkloadSpec(n_requests=6, arrival_rate=50.0,
                        prompt_lens=(9, 14, 21), max_news=(2, 3),
                        shared_prefix_ratio=0.5, shared_prefix_len=5,
                        vocab=CFG.vocab_size, seed=3)
    with count_compiles() as n_compiles:
        handles, _ = run_open_loop(eng, poisson_trace(spec),
                                   time_scale=0.01)
    assert n_compiles() == 0, (
        f"{n_compiles()} XLA compiles leaked past warmup "
        f"(cold: {eng.warmup_report()['cold_names']})")
    assert eng.warmup_report()["post_warmup_compiles"] == 0
    assert all(h.finished for h in handles)
    eng.close()


def test_warmup_is_bit_transparent(params, rng):
    """Dispatching through AOT executables must not change a single token
    relative to a never-warmed engine."""
    reqs = [Request(prompt=_prompt(rng, n), max_new=3, seed=i)
            for i, n in enumerate((9, 14, 21, 11))]

    def serve(warm):
        eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                     steps_per_sync=4, prefill_chunk=8,
                     pool_blocks=24, pool_block_tokens=POOL_BT)
        if warm:
            eng.warmup()
        hs = [eng.submit(Request(prompt=r.prompt, max_new=r.max_new,
                                 seed=r.seed)) for r in reqs]
        eng.run(hs)
        return [(h.result().tolist(), h.finish_reason) for h in hs]

    assert serve(True) == serve(False)


# ------------------------------------------- (c) async/sync bit-parity

@pytest.mark.parametrize("backend", BACKENDS)
def test_async_host_loop_bit_parity(params, rng, backend):
    """Async delivery must be pure plumbing: same tokens, same finish
    reasons as the synchronous path — mixed temperatures, an EOS id in
    range, ragged lengths, chunked prefill + pool."""
    reqs = [Request(prompt=_prompt(rng, n), max_new=m, seed=i,
                    temperature=t, eos_id=7)
            for i, (n, m, t) in enumerate(
                [(9, 6, 0.0), (14, 4, 0.5), (21, 5, 0.0),
                 (11, 6, 0.7), (16, 3, 0.0)])]

    def serve(async_host):
        eng = Engine(params, CFG, POL, batch_slots=3, max_len=POOL_LEN,
                     steps_per_sync=4, backend=backend, prefill_chunk=8,
                     pool_blocks=24, pool_block_tokens=POOL_BT,
                     async_host=async_host)
        hs = [eng.submit(Request(prompt=r.prompt, max_new=r.max_new,
                                 seed=r.seed, temperature=r.temperature,
                                 eos_id=r.eos_id)) for r in reqs]
        eng.run(hs)
        out = [(h.result().tolist(), h.finish_reason) for h in hs]
        eng.close()
        return out

    got_async, got_sync = serve(True), serve(False)
    assert got_async == got_sync


def test_async_first_token_time_set_on_delivery(params, rng):
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=40,
                 steps_per_sync=4, async_host=True)
    h = eng.submit(Request(prompt=_prompt(rng, 8), max_new=3))
    eng.run([h])
    assert h.first_token_time is not None
    assert h.finish_time is not None
    assert h.first_token_time >= h.submit_time
    eng.close()


# ----------------------------------------------- (d) graceful shutdown

def test_host_loop_drain_and_close():
    """Standalone HostLoop: everything enqueued before close(drain=True)
    is delivered; a second close is a no-op; post-close stats are sane."""
    done = []

    class H:
        def __init__(self):
            self.tokens, self.text = [], ""
            self.first_token_time = None

    hs = [H() for _ in range(4)]
    loop = HostLoop(lambda h, reason: done.append((h, reason)),
                    detokenize=lambda toks: "".join(chr(65 + t % 26)
                                                    for t in toks),
                    max_queue=2)
    for i, h in enumerate(hs):
        loop.put(TokenDelivery(handles=[h], rows=[0], counts=[2],
                               reasons=["length" if i % 2 else None],
                               tokens=np.full((1, 2), i, np.int32)))
    loop.close(drain=True)
    st = loop.stats()
    assert st["enqueued"] == 4
    assert st["delivered"] == 8            # 4 items x 2 tokens each
    assert st["queue_depth"] == 0
    assert [h.tokens for h in hs] == [[i, i] for i in range(4)]
    assert all(h.text for h in hs)
    assert [r for _, r in done] == ["length", "length"]
    loop.close(drain=True)  # idempotent


def test_engine_close_mid_stream(params, rng):
    """Shutting down with requests still decoding must not deadlock, and
    every delivered token must be a prefix of the full sync stream."""
    ref = Engine(params, CFG, POL, batch_slots=1, max_len=64,
                 steps_per_sync=2)
    prompt = _prompt(rng, 10)
    rh = ref.submit(Request(prompt=prompt, max_new=12, seed=0))
    ref.run([rh])

    eng = Engine(params, CFG, POL, batch_slots=1, max_len=64,
                 steps_per_sync=2, async_host=True)
    h = eng.submit(Request(prompt=prompt, max_new=12, seed=0))
    eng.step()
    eng.step()
    eng.close(drain=True)          # early shutdown: drain, then stop
    got = h.result().tolist()
    assert got == rh.result().tolist()[:len(got)]
    # the loop can be closed again without error
    eng.close()


def test_host_loop_backpressure_counted():
    """A slow consumer behind a tiny queue forces the producer to block;
    the stall is accounted, not silent."""
    release = threading.Event()

    class H:
        def __init__(self):
            self.tokens, self.text = [], ""
            self.first_token_time = None

    def slow_finish(h, reason):
        release.wait(timeout=5.0)

    def delivery():
        return TokenDelivery(handles=[H()], rows=[0], counts=[1],
                             reasons=["length"],
                             tokens=np.zeros((1, 1), np.int32))

    loop = HostLoop(slow_finish, max_queue=1)
    t0 = time.time()
    loop.put(delivery())               # consumer takes it, parks in finish
    deadline = time.time() + 5.0
    while loop.queue_depth > 0 and time.time() < deadline:
        time.sleep(0.005)
    loop.put(delivery())               # fills the 1-slot queue
    threading.Timer(0.2, release.set).start()
    loop.put(delivery())               # queue full -> accounted blocking put
    loop.close(drain=True)
    st = loop.stats()
    assert st["delivered"] == 3
    assert st["backpressure_waits"] >= 1
    assert st["backpressure_s"] > 0
    assert time.time() - t0 < 10


# ------------------------------------------ (e) pool sizing from bytes

def _per_block_bytes(params):
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
                 steps_per_sync=4, pool_blocks=4, pool_block_tokens=POOL_BT)
    return sum(r[6] for r in eng._enumerate_pool_bands())


def test_pool_memory_bytes_sizes_pool(params):
    per = _per_block_bytes(params)
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
                 steps_per_sync=4, pool_block_tokens=POOL_BT,
                 pool_memory_bytes=per * 6)
    assert eng.pool_blocks == 6
    assert eng._pools  # the pool actually materialized


def test_pool_memory_bytes_round_down_warns(params):
    per = _per_block_bytes(params)
    with pytest.warns(UserWarning, match="rounds down"):
        eng = Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
                     steps_per_sync=4, pool_block_tokens=POOL_BT,
                     pool_memory_bytes=per * 5 + per // 2)
    assert eng.pool_blocks == 5


def test_pool_blocks_overrides_budget_with_warning(params):
    per = _per_block_bytes(params)
    with pytest.warns(UserWarning, match="overrides"):
        eng = Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
                     steps_per_sync=4, pool_blocks=4,
                     pool_block_tokens=POOL_BT, pool_memory_bytes=per * 9)
    assert eng.pool_blocks == 4


def test_pool_memory_bytes_too_small_raises(params):
    with pytest.raises(ValueError, match="cannot fit a single pool block"):
        Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
               steps_per_sync=4, pool_block_tokens=POOL_BT,
               pool_memory_bytes=8)


# --------------------------------------------- (f) stats() counters

def test_stats_counters(params, rng):
    """More requests than slots: the later requests wait in the queue
    (admitted after they were submitted); every admission is counted; the
    counters block is present for pooled engines too."""
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=POOL_LEN,
                 steps_per_sync=4, prefill_chunk=8,
                 pool_blocks=12, pool_block_tokens=POOL_BT)
    hs = [eng.submit(Request(prompt=_prompt(rng, 9), max_new=2, seed=i))
          for i in range(3)]
    eng.run(hs)
    st = eng.stats()
    c = st["counters"]
    assert c["admitted"] == 3
    # two requests waited behind slot 0: each was admitted after it was
    # submitted, once the request before it had finished
    for prev, h in zip(hs, hs[1:]):
        assert h.admit_time >= prev.finish_time > h.submit_time
    assert c["pool_exhausted_stalls"] >= 0
    assert "cow_copies" in c
    assert st["queue_depth"] == 0 and st["active_slots"] == 0


def test_stats_host_block_present_when_async(params, rng):
    eng = Engine(params, CFG, POL, batch_slots=1, max_len=40,
                 steps_per_sync=4, async_host=True)
    h = eng.submit(Request(prompt=_prompt(rng, 8), max_new=2))
    eng.run([h])
    st = eng.stats()
    assert st["host"]["delivered"] >= 1
    assert st["host"]["queue_depth"] == 0
    eng.close()


# ------------------------------------------------- metrics unit tests

def test_percentiles_empty_safe():
    assert percentiles([]) == {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    p = percentiles([1.0, 2.0, 3.0])
    assert p["p50"] == 2.0 and p["p99"] <= 3.0


def test_request_record_sla():
    r = RequestRecord(rid=0, arrival_s=0.0, submit_s=0.0, prompt_len=8,
                      max_new=4, first_token_s=0.1, finish_s=0.4, n_tokens=4)
    assert r.ttft_ms == pytest.approx(100.0)
    assert r.tpot_ms == pytest.approx(100.0)
    assert r.meets_sla(150.0, 150.0)
    assert not r.meets_sla(50.0, None)       # TTFT bound violated
    assert not r.meets_sla(None, 50.0)       # TPOT bound violated
    assert r.meets_sla(None, None)           # finished, unconstrained
    unfinished = RequestRecord(rid=1, arrival_s=0.0, submit_s=0.0,
                               prompt_len=8, max_new=4)
    assert not unfinished.meets_sla(None, None)
    g = goodput([r, unfinished], makespan_s=1.0,
                sla_ttft_ms=150.0, sla_tpot_ms=150.0)
    assert g["n_ok"] == 1 and g["attainment"] == 0.5
    assert g["goodput_rps"] == pytest.approx(1.0)
    assert g["goodput_tok_s"] == pytest.approx(4.0)


def test_find_saturation_early_stop():
    calls = []

    def eval_at_rate(rate):
        calls.append(rate)
        att = 1.0 if rate <= 8 else 0.2
        return {"goodput": {"attainment": att, "goodput_rps": rate * att},
                "ttft_ms": {"p90": 1.0}, "tpot_ms": {"p90": 1.0}}

    out = find_saturation(eval_at_rate, [4, 8, 16, 32],
                          attainment_target=0.9)
    assert out["saturation_rps"] == 8
    assert calls == [4, 8, 16]               # 32 never evaluated
    assert len(out["table"]) == 3


def test_open_loop_recorder_end_to_end(params, rng):
    """run_open_loop + MetricsRecorder on a real engine: every request is
    recorded, finished, and the summary's goodput block is populated."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=40,
                 steps_per_sync=4, async_host=True)
    spec = WorkloadSpec(n_requests=5, arrival_rate=40.0,
                        prompt_lens=(8, 12), max_news=(2, 3),
                        vocab=CFG.vocab_size, seed=1)
    rec = MetricsRecorder()
    handles, makespan = run_open_loop(eng, poisson_trace(spec), rec,
                                      time_scale=0.01)
    assert all(h.finished for h in handles)
    summ = rec.summary(sla_ttft_ms=60_000.0, sla_tpot_ms=60_000.0)
    assert summ["n_requests"] == summ["n_finished"] == 5
    assert summ["goodput"]["attainment"] == 1.0
    assert summ["goodput"]["goodput_rps"] > 0
    assert summ["ttft_ms"]["p50"] > 0
    assert makespan > 0
    eng.close()
