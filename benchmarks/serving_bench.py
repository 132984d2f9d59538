"""Serving-path benchmark: ragged traffic, TTFT, and prefill compile counts.

Chunked prefill (DESIGN.md §7) exists for two serving symptoms that the
aggregate tok/s number hides:

* **unbounded recompiles** — whole-prompt admission jits one prefill
  executable per distinct prompt length, so ragged real-world traffic keeps
  paying compile latency; chunked admission compiles at most
  ``len(chunk_buckets)`` shapes ever;
* **head-of-line blocking** — a long whole-prompt prefill stalls every
  decode lane for that tick, which shows up as decode-stall time for the
  co-scheduled request.

This suite serves the same ragged request mix through both admission modes
and emits TTFT percentiles plus the *measured* prefill-shape counts, so the
bounded-compile-shape contract is tracked in the benchmarks JSON artifact
across PRs.

Rows are labeled by loop discipline so they stay comparable across PRs:
``mode=closed`` rows submit everything up front and run to completion
(offered load is unbounded — the engine sets the pace), while the
``mode=open`` rows of :func:`_open_loop_suite` (DESIGN.md §10) submit on a
seeded Poisson clock and report offered vs achieved req/s, TTFT/TPOT
percentiles, goodput under an SLA, and a saturation sweep — all after
``Engine.warmup()``, with the jax compile counter gating that ZERO XLA
compiles hit the open-loop traffic.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax

from repro import configs
from repro.core.policy import QuantPolicy
from repro.data import SyntheticCorpus
from repro.models import transformer as T
from repro.serving import (Engine, Request, WorkloadSpec, poisson_trace,
                           run_open_loop, MetricsRecorder, find_saturation,
                           FinishReason, ChaosEvent, FaultInjector,
                           TickClock)
from repro.testing import count_compiles


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def _serve(params, cfg, pol, reqs, max_len, prefill_chunk):
    eng = Engine(params, cfg, pol, batch_slots=2, max_len=max_len,
                 steps_per_sync=4, prefill_chunk=prefill_chunk)
    t0 = time.time()
    handles = [eng.submit(Request(prompt=r.prompt, max_new=r.max_new,
                                  seed=r.seed)) for r in reqs]
    eng.run(handles)
    wall = time.time() - t0
    toks = sum(len(h.tokens) for h in handles)
    ttft = [(h.first_token_time - h.submit_time) * 1e3 for h in handles]
    if prefill_chunk:
        shapes = len(eng.prefill_shapes)
    else:
        shapes = len({len(r.prompt) for r in reqs})  # one jit per length
    # per-request final occupancy (live tokens / per-slot capacity): the
    # regime block pruning targets — BENCH deltas are only interpretable
    # next to the occupancy that produced them
    occ = [(len(h.request.prompt) + len(h.tokens)) / max_len for h in handles]
    return {"wall_s": wall, "tok_s": toks / max(wall, 1e-9),
            "ttft_p50_ms": _pct(ttft, 50), "ttft_max_ms": max(ttft),
            "prefill_shapes": shapes,
            "occ_mean": float(np.mean(occ)), "occ_max": float(np.max(occ)),
            "backend_info": eng.backend_info}


def _serve_pool(params, cfg, pol, reqs, max_len, pool_blocks, bt, slots):
    """Serve a wave through the paged block pool (DESIGN.md §9), stepping
    manually so peak occupancy and admitted concurrency are sampled live."""
    eng = Engine(params, cfg, pol, batch_slots=slots, max_len=max_len,
                 steps_per_sync=4, pool_blocks=pool_blocks,
                 pool_block_tokens=bt)
    t0 = time.time()
    handles = [eng.submit(Request(prompt=r.prompt, max_new=r.max_new,
                                  seed=r.seed)) for r in reqs]
    concurrency = 0
    while any(not h.finished for h in handles):
        if not eng.step():
            break
        concurrency = max(concurrency, sum(
            h is not None for h in eng._slot_handle))
    wall = time.time() - t0
    st = eng.stats()
    toks = sum(len(h.tokens) for h in handles)
    return {"wall_s": wall, "tok_s": toks / max(wall, 1e-9),
            "streams": [h.result().tolist() for h in handles],
            "concurrency": concurrency, "stats": st}


def _shared_prefix_suite(emit, params, cfg, smoke):
    """Content-addressed prefix sharing under the block pool: N requests
    with an identical long prefix must quantize it ONCE, share the blocks
    copy-on-write, and keep fewer packed bytes resident than per-slot
    stripes would.  CI-gated — a regression that silently re-quantizes the
    prefix or stops sharing fails the smoke benchmark run."""
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5,
                      group_size=min(16, cfg.head_dim), window=16, n_sink=4)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
    rng = np.random.default_rng(7)
    bt, max_len, slots = 8, 84, 3          # packed = 64 tokens = 8 blocks
    n_req = 3 if smoke else 6
    prefix = corpus.sample(72, np.random.default_rng(100))
    reqs = []
    for i in range(n_req):
        tail = rng.integers(0, cfg.vocab_size, size=6).astype(prefix.dtype)
        reqs.append(Request(prompt=np.concatenate([prefix, tail]),
                            max_new=6, seed=i))

    pooled = _serve_pool(params, cfg, pol, reqs, max_len,
                         pool_blocks=4 * 8, bt=bt, slots=slots)
    # striped baseline: same wave through per-slot stripes; its packed
    # worst case is what the pool's resident bytes are gated against
    eng = Engine(params, cfg, pol, batch_slots=slots, max_len=max_len,
                 steps_per_sync=4)
    handles = [eng.submit(Request(prompt=r.prompt, max_new=r.max_new,
                                  seed=r.seed)) for r in reqs]
    t0 = time.time()
    eng.run(handles)
    wall = time.time() - t0
    striped_streams = [h.result().tolist() for h in handles]
    if pooled["streams"] != striped_streams:
        raise RuntimeError("pooled streams diverged from striped baseline")

    st = pooled["stats"]
    ratio = st["peak_resident_bytes"] / max(st["striped_worst_case_bytes"], 1)
    emit(f"serve_shared_prefix_pooled,"
         f"{pooled['wall_s'] * 1e6 / len(reqs):.1f},"
         f"mode=closed;offered_rps=unbounded;"
         f"achieved_rps={len(reqs) / max(pooled['wall_s'], 1e-9):.2f};"
         f"resident_peak_bytes={st['peak_resident_bytes']};"
         f"striped_worst_case_bytes={st['striped_worst_case_bytes']};"
         f"resident_ratio={ratio:.3f};"
         f"prefix_hit_rate={st['prefix_hit_rate']:.3f};"
         f"prefix_hits={st['prefix_hits']};"
         f"prefix_misses={st['prefix_misses']};"
         f"cow_copies={st['cow_copies']};"
         f"peak_used_blocks={st['peak_used']};"
         f"admitted_concurrency={pooled['concurrency']};"
         f"tok_s={pooled['tok_s']:.2f}")
    emit(f"serve_shared_prefix_striped,{wall * 1e6 / len(reqs):.1f},"
         f"packed_bytes={st['striped_worst_case_bytes']};"
         f"admitted_concurrency={slots};tok_s="
         f"{sum(len(s) for s in striped_streams) / max(wall, 1e-9):.2f}")
    # CI gates: sharing must actually happen, and pooled residency must
    # beat per-slot stripes by >= 2x on this workload
    gates = {"prefix_hit_rate>0": st["prefix_hit_rate"] > 0,
             "cow_copies>0": st["cow_copies"] > 0,
             "resident_ratio<0.5": ratio < 0.5}
    emit(f"serve_pool_summary,0.0,"
         f"pool_blocks={st['pool_blocks']};"
         f"pool_block_tokens={st['pool_block_tokens']};"
         f"resident_ratio={ratio:.3f};"
         f"prefix_hit_rate={st['prefix_hit_rate']:.3f};"
         f"cow_copies={st['cow_copies']};"
         f"gate={'pass' if all(gates.values()) else 'FAIL'}")
    failed = [k for k, ok in gates.items() if not ok]
    if failed:
        raise RuntimeError(
            f"shared-prefix pool gates failed: {failed} (stats: {st})")


def _open_loop_suite(emit, params, cfg, smoke):
    """Open-loop serving under a Poisson clock (DESIGN.md §10): AOT-warm a
    chunked + pooled + async engine, then drive a seeded arrival trace and
    report offered vs achieved load, TTFT/TPOT percentiles, and goodput
    under an SLA, plus a small saturation sweep reusing the SAME engine.

    CI-gated twice: the jax compile counter must read ZERO over the traffic
    window (everything was compiled by ``Engine.warmup()``), and the
    goodput/percentile rows must be non-empty (every request finished)."""
    pol = QuantPolicy(bits_k=2.0, bits_v=2.0,
                      group_size=min(16, cfg.head_dim), window=16, n_sink=4)
    bt, max_len, slots = 16, 148, 3        # packed = 128 tokens = 8 blocks
    eng = Engine(params, cfg, pol, batch_slots=slots, max_len=max_len,
                 steps_per_sync=4, prefill_chunk=16,
                 pool_blocks=64, pool_block_tokens=bt, async_host=True)
    rep = eng.warmup()
    emit(f"serve_warmup,{rep['compile_s'] * 1e6:.1f},"
         f"n_executables={rep['n_executables']};"
         f"compile_s={rep['compile_s']:.2f};"
         f"rehearse_s={rep['rehearse_s']:.2f}")

    sla_ttft_ms, sla_tpot_ms = 2000.0, 500.0
    spec = WorkloadSpec(n_requests=8 if smoke else 24, arrival_rate=8.0,
                        prompt_lens=(24, 40, 56), max_news=(6, 10),
                        shared_prefix_ratio=0.5, shared_prefix_len=12,
                        vocab=cfg.vocab_size, seed=0)
    rec = MetricsRecorder()
    with count_compiles() as n_compiles:
        handles, _ = run_open_loop(eng, poisson_trace(spec), rec)
    post = eng.warmup_report()["post_warmup_compiles"]
    summ = rec.summary(sla_ttft_ms=sla_ttft_ms, sla_tpot_ms=sla_tpot_ms)
    good = summ["goodput"]
    gates = {"zero_compiles": n_compiles() == 0 and post == 0,
             "all_finished": summ["n_finished"] == summ["n_requests"],
             "goodput_rows": summ["n_requests"] > 0
             and good["goodput_rps"] >= 0.0}
    emit(f"serve_open_loop,{summ['makespan_s'] * 1e6:.1f},"
         f"mode=open;"
         f"offered_rps={summ['offered_rps']:.2f};"
         f"achieved_rps={summ['achieved_rps']:.2f};"
         f"achieved_tok_s={summ['achieved_tok_s']:.2f};"
         f"n_requests={summ['n_requests']};"
         f"n_finished={summ['n_finished']};"
         f"ttft_p50_ms={summ['ttft_ms']['p50']:.0f};"
         f"ttft_p90_ms={summ['ttft_ms']['p90']:.0f};"
         f"ttft_p99_ms={summ['ttft_ms']['p99']:.0f};"
         f"tpot_p50_ms={summ['tpot_ms']['p50']:.1f};"
         f"tpot_p90_ms={summ['tpot_ms']['p90']:.1f};"
         f"tpot_p99_ms={summ['tpot_ms']['p99']:.1f};"
         f"queue_wait_p90_ms={summ['queue_wait_ms']['p90']:.0f};"
         f"queue_depth_max={summ.get('queue_depth_max', 0)};"
         f"pool_used_max={summ.get('pool_used_max', 0)};"
         f"sla_ttft_ms={sla_ttft_ms:.0f};sla_tpot_ms={sla_tpot_ms:.0f};"
         f"sla_attainment={good['attainment']:.3f};"
         f"goodput_rps={good['goodput_rps']:.2f};"
         f"goodput_tok_s={good['goodput_tok_s']:.2f};"
         f"post_warmup_compiles={post};"
         f"traffic_compiles={n_compiles()};"
         f"gate={'pass' if all(gates.values()) else 'FAIL'}")

    # saturation sweep: same engine, ascending offered load, find the last
    # rate whose SLA attainment still clears the target
    rates = (4.0, 12.0) if smoke else (4.0, 8.0, 16.0, 32.0)

    def eval_at_rate(rate):
        s = dataclasses.replace(spec, arrival_rate=rate,
                                seed=int(round(rate * 1000)))
        r = MetricsRecorder()
        run_open_loop(eng, poisson_trace(s), r)
        return r.summary(sla_ttft_ms=sla_ttft_ms, sla_tpot_ms=sla_tpot_ms)

    sat = find_saturation(eval_at_rate, rates, attainment_target=0.9)
    table = ";".join(
        f"rate{row['rate']:.0f}_att={row['attainment']:.3f}"
        for row in sat["table"])
    sat_rps = sat["saturation_rps"]
    emit(f"serve_saturation,0.0,"
         f"mode=open;attainment_target={sat['attainment_target']:.2f};"
         f"saturation_rps={'none' if sat_rps is None else f'{sat_rps:.1f}'};"
         f"{table}")
    eng.close()
    failed = [k for k, ok in gates.items() if not ok]
    if failed:
        raise RuntimeError(
            f"open-loop serving gates failed: {failed} "
            f"(traffic_compiles={n_compiles()}, post_warmup={post}, "
            f"summary={summ})")


def _overload_suite(emit, params, cfg, smoke):
    """Graceful degradation under overload (DESIGN.md §11): offered load
    well past saturation, a priority mix, and a block pool sized at ~50%
    of the wave's working-set demand, so admission must stall, preempt,
    and spill instead of expanding.

    CI-gated: the run must terminate (no deadlock), every request must
    carry a valid terminal FinishReason (no hung streams), goodput must
    stay positive, and the post-run pool/spill invariant audit must be
    clean (zero leaked blocks)."""
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5,
                      group_size=min(16, cfg.head_dim), window=16, n_sink=4)
    # 40-token prompts + <=12 new + 4-step sync margin - (sink+window) = 36
    # packed tokens -> 5 blocks eventual demand per request; 2 slots x 5 =
    # 10 working-set blocks, pool_blocks=5 puts the pool at 50% of that
    bt, max_len, slots = 8, 84, 2
    eng = Engine(params, cfg, pol, batch_slots=slots, max_len=max_len,
                 steps_per_sync=4, prefill_chunk=8,
                 pool_blocks=5, pool_block_tokens=bt, async_host=True,
                 host_spill_bytes=4 << 20)
    rep = eng.warmup()
    # offered ~2x+ past anything this pool can sustain: every arrival hits
    # a busy engine, so the queue/preemption/stall machinery carries it
    spec = WorkloadSpec(n_requests=6 if smoke else 14, arrival_rate=100.0,
                        prompt_lens=(40,), max_news=(8, 12),
                        shared_prefix_ratio=0.5, shared_prefix_len=16,
                        vocab=cfg.vocab_size, priorities=(0, 1), seed=11)
    rec = MetricsRecorder()
    handles, makespan = run_open_loop(eng, poisson_trace(spec), rec)
    summ = rec.summary(sla_ttft_ms=120_000.0, sla_tpot_ms=None)
    st = eng.stats()
    c = st["counters"]
    try:
        eng.check_invariants()
        leak_ok = True
    except RuntimeError:
        leak_ok = False
    gates = {
        "all_terminal": all(
            h.finished and h.finish_reason in FinishReason.TERMINAL
            for h in handles),
        "goodput>0": summ["goodput"]["goodput_rps"] > 0,
        "no_block_leak": leak_ok,
        "zero_compiles": rep["post_warmup_compiles"] == 0
        and eng.warmup_report()["post_warmup_compiles"] == 0,
    }
    emit(f"serve_overload,{makespan * 1e6 / len(handles):.1f},"
         f"mode=open;offered_rps={summ['offered_rps']:.1f};"
         f"achieved_rps={summ['achieved_rps']:.2f};"
         f"n_requests={summ['n_requests']};"
         f"n_finished={summ['n_finished']};"
         f"finish_reasons={summ['finish_reasons']};"
         f"pool_blocks=5;working_set_blocks=10;"
         f"preemptions={c['preemptions']};"
         f"pool_stalls={c['pool_exhausted_stalls']};"
         f"spilled_blocks={c['spilled_blocks']};"
         f"restored_blocks={c['restored_blocks']};"
         f"goodput_rps={summ['goodput']['goodput_rps']:.2f};"
         f"gate={'pass' if all(gates.values()) else 'FAIL'}")
    eng.close()
    failed = [k for k, ok in gates.items() if not ok]
    if failed:
        raise RuntimeError(
            f"overload gates failed: {failed} "
            f"(reasons={summ['finish_reasons']}, counters={c})")


def run_chaos(emit, smoke: bool = False):
    """Seeded chaos smoke (DESIGN.md §11): drive pooled engines through
    pool-exhaustion and NaN-logit fault traces and gate the degradation
    invariants in CI — every stream terminates with a valid FinishReason
    (no hangs), the pool/spill audit finds zero leaked blocks, and no XLA
    compile hits traffic after warmup.

        PYTHONPATH=src python -m benchmarks.serving_bench --smoke --chaos
    """
    cfg = configs.get_smoke("llama3p2_1b")
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5,
                      group_size=min(16, cfg.head_dim), window=16, n_sink=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
    bt, max_len, slots = 8, 84, 2
    n_req = 5 if smoke else 10
    rng = np.random.default_rng(3)

    def wave():
        return [Request(prompt=corpus.sample(40, np.random.default_rng(i)),
                        max_new=int(rng.integers(6, 11)), seed=i,
                        priority=i % 2)
                for i in range(n_req)]

    scenarios = {
        # exhaustion bursts seize 60% of free blocks for 6 ticks, twice
        "pool": [ChaosEvent(tick=t, kind="pool", duration=6, magnitude=0.6)
                 for t in (3, 14)],
        # two NaN-poisoned decode chunks -> slot quarantine, others clean
        "nan": [ChaosEvent(tick=t, kind="nan") for t in (4, 12)],
    }
    for name, events in scenarios.items():
        inj = FaultInjector(events)
        eng = Engine(params, cfg, pol, batch_slots=slots, max_len=max_len,
                     steps_per_sync=4, prefill_chunk=8,
                     pool_blocks=12, pool_block_tokens=bt, async_host=True,
                     host_spill_bytes=4 << 20, clock=TickClock(0.01),
                     faults=inj)
        rep = eng.warmup()
        t0 = time.time()
        handles = [eng.submit(r) for r in wave()]
        ticks = 0
        while eng.step():
            ticks += 1
            if ticks > 5000:
                raise RuntimeError(f"chaos '{name}': engine still busy "
                                   f"after {ticks} ticks — hung stream")
        eng.drain()
        wall = time.time() - t0
        st = eng.stats()
        c = st["counters"]
        try:
            eng.check_invariants()
            leak_ok = True
        except RuntimeError:
            leak_ok = False
        post = eng.warmup_report()["post_warmup_compiles"]
        gates = {
            "all_terminal": all(
                h.finished and h.finish_reason in FinishReason.TERMINAL
                for h in handles),
            "no_block_leak": leak_ok,
            "zero_compiles": rep["post_warmup_compiles"] == 0 and post == 0,
            "faults_fired": sum(inj.stats()["injected"].values()) > 0,
        }
        reasons = {}
        for h in handles:
            reasons[h.finish_reason] = reasons.get(h.finish_reason, 0) + 1
        emit(f"serve_chaos_{name},{wall * 1e6 / len(handles):.1f},"
             f"mode=closed;n_requests={len(handles)};"
             f"finish_reasons={reasons};"
             f"injected={inj.stats()['injected']};"
             f"preemptions={c['preemptions']};"
             f"pool_stalls={c['pool_exhausted_stalls']};"
             f"nan_quarantines={c['nan_quarantines']};"
             f"spilled_blocks={c['spilled_blocks']};"
             f"restored_blocks={c['restored_blocks']};"
             f"post_warmup_compiles={post};"
             f"gate={'pass' if all(gates.values()) else 'FAIL'}")
        eng.close()
        failed = [k for k, ok in gates.items() if not ok]
        if failed:
            raise RuntimeError(
                f"chaos '{name}' gates failed: {failed} "
                f"(reasons={reasons}, injected={inj.stats()['injected']}, "
                f"counters={c})")


def run(emit, smoke: bool = False):
    cfg = configs.get_smoke("llama3p2_1b")
    pol = QuantPolicy(bits_k=2.0, bits_v=1.5,
                      group_size=min(16, cfg.head_dim), window=16, n_sink=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
    rng = np.random.default_rng(0)

    # >= 6 distinct prompt lengths: the ragged regime whole-prompt admission
    # pays one compile each for
    lens = [24, 41, 57, 33, 62, 49] if smoke else [24, 41, 57, 33, 62, 49,
                                                   70, 91, 108, 77]
    reqs = [Request(prompt=corpus.sample(n, np.random.default_rng(i)),
                    max_new=int(rng.integers(4, 9)), seed=i)
            for i, n in enumerate(lens)]
    max_len = max(lens) + 16
    chunk = 16

    whole = _serve(params, cfg, pol, reqs, max_len, None)
    chunked = _serve(params, cfg, pol, reqs, max_len, chunk)

    for name, r in (("serve_ragged_whole_prompt", whole),
                    (f"serve_ragged_chunked_c{chunk}", chunked)):
        # mode=closed: every request is submitted up front, so the offered
        # load is unbounded (the engine sets the pace) and only the
        # achieved rate is meaningful — labeled so these rows are never
        # silently compared against open-loop rows (DESIGN.md §10)
        emit(f"{name},{r['wall_s'] * 1e6 / max(len(reqs), 1):.1f},"
             f"mode=closed;offered_rps=unbounded;"
             f"achieved_rps={len(reqs) / max(r['wall_s'], 1e-9):.2f};"
             f"occupancy_mean={r['occ_mean']:.2f};"
             f"occupancy_max={r['occ_max']:.2f};"
             f"ttft_p50_ms={r['ttft_p50_ms']:.0f};"
             f"ttft_max_ms={r['ttft_max_ms']:.0f};"
             f"tok_s={r['tok_s']:.2f};"
             f"prefill_shapes={r['prefill_shapes']}")
    emit(f"serve_prefill_shape_ratio,0.0,"
         f"whole={whole['prefill_shapes']};chunked={chunked['prefill_shapes']}"
         f";bound=len(chunk_buckets)")
    # per-layer tuples (layer_avg_bits/layer_cache_bytes) would leak commas
    # into the CSV contract and balloon on deep models — the scalar schedule
    # facts (avg_bits, cache_bytes_per_slot, n_policies) carry the row
    info = {k: v for k, v in whole["backend_info"].items()
            if not isinstance(v, tuple)}
    emit("serve_backend_info,0.0," +
         ";".join(f"{k}={v}" for k, v in sorted(info.items())))

    _shared_prefix_suite(emit, params, cfg, smoke)
    _open_loop_suite(emit, params, cfg, smoke)
    _overload_suite(emit, params, cfg, smoke)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset (shrunk waves)")
    ap.add_argument("--chaos", action="store_true",
                    help="run only the seeded fault-injection suite "
                         "(DESIGN.md §11) — the CI chaos-smoke gate")
    _args = ap.parse_args()
    print("name,us_per_call,derived")

    def _emit(row):
        print(row, flush=True)

    if _args.chaos:
        run_chaos(_emit, smoke=_args.smoke)
    else:
        run(_emit, smoke=_args.smoke)
