"""End-to-end serving driver: continuous batching over the request Engine.

Submits ``--requests`` generation jobs (ragged prompt lengths via
``--prompt-jitter``, ragged ``max_new`` via ``--max-new-jitter``) onto
``--batch`` decode slots — more requests than slots means multiple
admission waves, so freed slots immediately refill from the queue (the
continuous-batching path the SKVQ cache is built for).  ``--prefill-chunk``
streams prompts through the cache in fixed-size chunks (DESIGN.md §7):
long prompts stop head-of-line-blocking decode and ragged traffic compiles
a bounded set of prefill shapes.  Reports aggregate tok/s, per-request
latency AND time-to-first-token percentiles.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3p2_1b --smoke \
        --batch 4 --requests 8 --prompt-len 256 --prompt-jitter 64 \
        --new-tokens 32 --max-new-jitter 8 --prefill-chunk 64 \
        --bits-k 2 --bits-v 1.5

``--open-loop`` switches from the closed loop above to the throughput
harness of DESIGN.md §10: requests arrive on a seeded Poisson clock at
``--arrival-rate`` req/s regardless of engine progress, ``--warmup``
AOT-compiles every executable before the first arrival (the run fails if
any compile hits traffic afterwards), ``--async-host`` moves delivery to
the background host loop, and the report becomes TTFT/TPOT percentiles +
goodput under the ``--sla-ttft-ms``/``--sla-tpot-ms`` SLA:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3p2_1b --smoke \
        --open-loop --arrival-rate 8 --requests 16 --warmup --async-host \
        --prefill-chunk 16 --pool-blocks 64 --prompt-len 40 \
        --prompt-jitter 16 --new-tokens 12 --sla-ttft-ms 2000 \
        --sla-tpot-ms 500

Degradation knobs (DESIGN.md §11): ``--deadline-ms`` expires requests that
outstay their budget, ``--priority-mix`` assigns priority levels (under
pool pressure higher-priority arrivals preempt strictly-lower running
slots, which requeue and replay bit-identically), ``--host-spill-mb``
turns on the host-RAM block spill tier, and ``--chaos {pool,nan,crash,
timeout}`` runs a seeded fault-injection trace.  Every run ends with a
degradation summary table and a pool invariant audit — a leak exits
non-zero:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3p2_1b --smoke \
        --batch 2 --requests 6 --prompt-len 24 --new-tokens 8 \
        --prefill-chunk 8 --pool-blocks 12 --pool-block-tokens 8 \
        --priority-mix 0,0,1 --host-spill-mb 16 --chaos pool
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np
import jax

from .. import configs
from ..core.policy import QuantPolicy, PolicySchedule, as_schedule
from ..core.kv_cache import schedule_cache_nbytes
from ..core.quant import packed_nbytes
from ..data import SyntheticCorpus
from ..models import transformer as T
from ..serving import (Engine, Request, WorkloadSpec, poisson_trace,
                       run_open_loop, MetricsRecorder,
                       ChaosSpec, chaos_trace, FaultInjector)
from .compile_cache import enable_compile_cache


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def _print_schedule_table(schedule, cfg, max_len, dtype):
    """Per-layer avg-bits + KV-bytes table (DESIGN.md §8 accounting).

    Contiguous equal-policy layer bands print as one row; cache KB is the
    exact per-LAYER allocation at ``max_len`` capacity in the served cache
    dtype (the total line sums every layer)."""
    nbytes = schedule_cache_nbytes(schedule, cfg.n_layers, max_len,
                                   cfg.n_kv_heads, cfg.head_dim, dtype=dtype)
    print("  layers      bits_k  bits_v  window  sinks  avg_bits  cache_KB/layer")
    for bs, be, p in schedule.bands():
        span = f"{bs}" if be == bs + 1 else f"{bs}-{be - 1}"
        print(f"  {span:<10}  {p.bits_k:<6g}  {p.bits_v:<6g}  {p.window:<6d}"
              f"  {p.n_sink:<5d}  {p.avg_bits(cfg.head_dim):<8.3f}"
              f"  {nbytes[bs] / 1024:.1f}")
    print(f"  schedule avg_bits={schedule.avg_bits(cfg.head_dim):.3f} "
          f"total cache KB/slot={sum(nbytes) / 1024:.1f}")


def _priority_mix(args):
    """Parse ``--priority-mix`` into the tuple of levels requests cycle
    through / are sampled from (DESIGN.md §11)."""
    try:
        mix = tuple(int(x) for x in args.priority_mix.split(","))
    except ValueError:
        raise SystemExit(f"--priority-mix must be comma-separated ints, "
                         f"got {args.priority_mix!r}")
    if not mix:
        raise SystemExit("--priority-mix must name at least one level")
    return mix


def _chaos_injector(args, horizon_ticks=64):
    """Build the seeded :class:`FaultInjector` for ``--chaos`` (DESIGN.md
    §11), or None when chaos is off."""
    if args.chaos == "none":
        return None
    spec = ChaosSpec(n_events=args.chaos_events, kinds=(args.chaos,),
                     horizon_ticks=horizon_ticks, seed=args.chaos_seed)
    events = chaos_trace(spec)
    print(f"chaos: {len(events)} '{args.chaos}' events at ticks "
          f"{[e.tick for e in events]} (seed {args.chaos_seed})")
    return FaultInjector(events)


def _degradation_summary(eng, inj=None):
    """Degradation ladder report + invariant audit (DESIGN.md §11).

    Prints the overload-behaviour table (how many requests were preempted,
    shed, deadline-missed, cancelled; blocks spilled/restored; NaN
    quarantines; watchdog trips), the fault injector's accounting when
    chaos was on, and then runs :meth:`Engine.check_invariants` — a failed
    audit (leaked or double-owned pool blocks, spill-tier corruption)
    exits non-zero so CI catches it."""
    st = eng.stats()
    c = st["counters"]
    print("degradation summary (DESIGN.md §11):")
    print(f"  preempted={c['preemptions']} shed={c['shed']} "
          f"deadline_misses={c['deadline_misses']} "
          f"cancelled={c['cancelled']} "
          f"nan_quarantines={c['nan_quarantines']} "
          f"watchdog_trips={c['watchdog_trips']} "
          f"pool_stalls={c['pool_exhausted_stalls']}")
    if "host_spill" in st:
        t = st["host_spill"]
        print(f"  host spill: {c['spilled_blocks']} spilled / "
              f"{c['restored_blocks']} restored "
              f"({t['bytes']}/{t['budget_bytes']} B resident, "
              f"{t['evicted']} LRU-evicted, {t['rejected']} rejected)")
    if inj is not None:
        s = inj.stats()
        print(f"  chaos: {s['injected']} injected, {s['skipped']} skipped, "
              f"{s['active_holds']} holds outstanding")
    try:
        eng.check_invariants()
        print("  invariant audit: PASS (no leaked blocks)")
    except RuntimeError as e:
        print(f"FAIL: invariant audit: {e}", file=sys.stderr)
        raise SystemExit(1)


def pool_tiled_max_len(max_len, schedule, block_tokens):
    """Round ``max_len`` up until every quantized band's packed region
    (``max_len - n_sink - window``) tiles into whole pool blocks
    (DESIGN.md §9)."""
    for _ in range(block_tokens):
        if all(p.is_fp16 or (max_len - p.n_sink - p.window) % block_tokens == 0
               for p in schedule.distinct()):
            break
        max_len += 1
    return max_len


def _open_loop(eng, args, cfg, n_req, max_len, inj=None):
    """Open-loop serving run + SLA goodput report (DESIGN.md §10).

    Generates a seeded Poisson trace from the CLI's prompt/max-new knobs,
    drives the engine on the wall clock, and prints offered vs achieved
    load, TTFT/TPOT/e2e percentiles, queue/pool gauges, and goodput under
    the ``--sla-*`` bounds.  With ``--warmup``, exits non-zero if any XLA
    compile hit traffic after warmup — the CI smoke gate."""
    plens = sorted({max(1, args.prompt_len + d) for d in
                    (-args.prompt_jitter, 0, args.prompt_jitter)})
    mnews = sorted({max(1, args.new_tokens + d) for d in
                    (-args.max_new_jitter, 0, args.max_new_jitter)})
    spec = WorkloadSpec(
        n_requests=n_req, arrival_rate=args.arrival_rate,
        prompt_lens=plens, max_news=mnews, temperature=args.temperature,
        eos_id=args.eos_id, shared_prefix_ratio=args.shared_prefix_ratio,
        shared_prefix_len=min(plens) // 2 if args.shared_prefix_ratio else 0,
        vocab=cfg.vocab_size, deadline_ms=args.deadline_ms,
        priorities=_priority_mix(args), seed=0)
    rec = MetricsRecorder()
    handles, makespan = run_open_loop(eng, poisson_trace(spec), rec)
    s = rec.summary(sla_ttft_ms=args.sla_ttft_ms,
                    sla_tpot_ms=args.sla_tpot_ms)
    print(f"open loop: {s['n_finished']}/{s['n_requests']} requests in "
          f"{makespan:.2f}s — offered {s['offered_rps']:.2f} req/s, "
          f"achieved {s['achieved_rps']:.2f} req/s "
          f"({s['achieved_tok_s']:.1f} tok/s)")
    for name in ("ttft_ms", "tpot_ms", "e2e_ms", "queue_wait_ms"):
        p = s[name]
        print(f"  {name:<14} p50={p['p50']:.1f} p90={p['p90']:.1f} "
              f"p99={p['p99']:.1f}")
    print(f"  gauges: queue max={s.get('queue_depth_max', 0)} "
          f"host-queue max={s.get('host_queue_depth_max', 0)} "
          f"slots max={s.get('active_slots_max', 0)}"
          + (f" pool-used max={s['pool_used_max']}"
             if "pool_used_max" in s else ""))
    st = eng.stats()
    print(f"  counters: {st['counters']}")
    if "goodput" in s:
        g = s["goodput"]
        print(f"  goodput @ SLA(ttft<={g['sla_ttft_ms']}ms, "
              f"tpot<={g['sla_tpot_ms']}ms): {g['n_ok']}/{s['n_finished']} "
              f"ok ({100 * g['attainment']:.0f}%), "
              f"{g['goodput_rps']:.2f} req/s, {g['goodput_tok_s']:.1f} tok/s")
    if args.warmup:
        cold = eng.warmup_report()["post_warmup_compiles"]
        if cold:
            print(f"FAIL: {cold} XLA compiles hit traffic after warmup "
                  f"({eng.warmup_report()['cold_names']})", file=sys.stderr)
            raise SystemExit(1)
        print("  zero XLA compiles after warmup ✓")
    reasons = s.get("finish_reasons", {})
    if reasons:
        print(f"  finish reasons: {reasons}")
    _degradation_summary(eng, inj)
    eng.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3p2_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (concurrent requests)")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests to serve (default: 2x batch — two "
                         "admission waves exercise continuous batching)")
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--prompt-jitter", type=int, default=0,
                    help="per-request prompt length drawn from prompt-len ± "
                         "jitter (ragged arrivals; pair with --prefill-chunk "
                         "to keep the compiled prefill-shape set bounded)")
    ap.add_argument("--new-tokens", type=int, default=32,
                    help="base max_new per request")
    ap.add_argument("--max-new-jitter", type=int, default=0,
                    help="per-request max_new drawn from new-tokens ± jitter "
                         "(ragged budgets -> slots free at different times)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop generation at this token id")
    ap.add_argument("--bits-k", type=float, default=2.0)
    ap.add_argument("--bits-v", type=float, default=1.5)
    ap.add_argument("--group-size", type=int, default=64)
    ap.add_argument("--window", type=int, default=32)
    ap.add_argument("--sinks", type=int, default=5)
    ap.add_argument("--policy-schedule", default="uniform",
                    choices=("uniform", "first_last_fp16", "ladder"),
                    help="per-layer policy schedule preset (DESIGN.md §8): "
                         "uniform = every layer runs the --bits-* policy; "
                         "first_last_fp16 = --guard-layers fp16 guard layers "
                         "at each end; ladder = 4/4 -> base -> base bits "
                         "over even layer thirds")
    ap.add_argument("--guard-layers", type=int, default=2,
                    help="fp16 guard layers per end (first_last_fp16 preset)")
    ap.add_argument("--backend", default=None,
                    help="decode backend: reference | pallas (default: host)")
    ap.add_argument("--steps-per-sync", type=int, default=8,
                    help="decode tokens per host sync (scanned decode)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: stream prompts through the cache "
                         "in chunks of at most this many tokens, bounded "
                         "compile shapes (0 = whole-prompt prefill, one "
                         "executable per distinct prompt length)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged KV block pool (DESIGN.md §9): share this "
                         "many physical quantized-KV blocks per band across "
                         "all slots, with per-slot block tables, "
                         "content-addressed prefix sharing and block-level "
                         "admission (0 = per-slot stripes)")
    ap.add_argument("--pool-block-tokens", type=int, default=16,
                    help="tokens per pool block (>= 8; max_len is rounded "
                         "up so every quantized band tiles into whole "
                         "blocks)")
    ap.add_argument("--pool-memory-mb", type=float, default=0,
                    help="size the block pool from a device-memory budget "
                         "instead of --pool-blocks (DESIGN.md §10): blocks "
                         "= budget // per-block bytes summed across bands")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the engine's executable set before "
                         "traffic (DESIGN.md §10); with --open-loop the run "
                         "fails if any compile hits traffic afterwards")
    ap.add_argument("--async-host", action="store_true",
                    help="deliver tokens on the background host loop "
                         "(DESIGN.md §10) instead of the scheduler thread")
    ap.add_argument("--open-loop", action="store_true",
                    help="open-loop load: Poisson arrivals at "
                         "--arrival-rate req/s, SLA goodput report "
                         "(DESIGN.md §10)")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="open-loop offered load, requests/second")
    ap.add_argument("--shared-prefix-ratio", type=float, default=0.0,
                    help="fraction of open-loop prompts sharing one common "
                         "prefix (exercises pool prefix sharing)")
    ap.add_argument("--sla-ttft-ms", type=float, default=None,
                    help="TTFT SLA bound for the goodput report, ms")
    ap.add_argument("--sla-tpot-ms", type=float, default=None,
                    help="TPOT SLA bound for the goodput report, ms")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (DESIGN.md §11): a request "
                         "still queued or running this many ms after submit "
                         "finishes 'deadline' and frees its blocks")
    ap.add_argument("--priority-mix", default="0",
                    help="comma-separated priority levels assigned to "
                         "requests (DESIGN.md §11); under pool pressure a "
                         "higher-priority arrival preempts strictly-lower-"
                         "priority running slots (e.g. '0,0,1')")
    ap.add_argument("--host-spill-mb", type=float, default=0,
                    help="host-RAM spill tier byte budget (DESIGN.md §11): "
                         "cold refcount-0 pool blocks and preempted slots' "
                         "blocks spill to host arrays and restore on demand "
                         "instead of re-quantizing (0 = off)")
    ap.add_argument("--chaos", default="none",
                    choices=("none", "pool", "nan", "crash", "timeout"),
                    help="seeded fault injection (DESIGN.md §11): pool "
                         "exhaustion bursts, NaN-logit quarantine, host-"
                         "loop consumer crashes, or simulated device-step "
                         "timeouts; the run prints injector accounting and "
                         "exits non-zero if the invariant audit fails")
    ap.add_argument("--chaos-events", type=int, default=4,
                    help="number of chaos events to schedule")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos trace seed (same seed, same fault ticks)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    # the fp16 baseline stores every token raw: window/sink buffers would
    # duplicate storage, so QuantPolicy rejects them — drop the CLI defaults
    is_fp16 = args.bits_k >= 16 and args.bits_v >= 16
    policy = QuantPolicy(bits_k=args.bits_k, bits_v=args.bits_v,
                         group_size=min(args.group_size, cfg.head_dim),
                         window=0 if is_fp16 else args.window,
                         n_sink=0 if is_fp16 else args.sinks)
    if args.policy_schedule == "first_last_fp16":
        # at least one interior layer must stay quantized (the preset
        # refuses all-fp16 degeneration) — clamp for shallow smoke archs
        guard = min(args.guard_layers, max((cfg.n_layers - 1) // 2, 0))
        if guard != args.guard_layers:
            print(f"note: --guard-layers {args.guard_layers} clamped to "
                  f"{guard} ({cfg.n_layers}-layer arch needs 2*guard < "
                  f"layers)")
        schedule = PolicySchedule.first_last_fp16(policy, guard, cfg.n_layers)
    elif args.policy_schedule == "ladder":
        schedule = PolicySchedule.bits_ladder(
            policy, ((4.0, 4.0), (args.bits_k, args.bits_v),
                     (args.bits_k, args.bits_v)), cfg.n_layers)
    else:
        schedule = as_schedule(policy, cfg.n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=0)
    n_req = args.requests or 2 * args.batch
    rng = np.random.default_rng(0)
    jit = args.max_new_jitter

    mix = _priority_mix(args)
    reqs = []
    for i in range(n_req):
        max_new = args.new_tokens + (int(rng.integers(-jit, jit + 1)) if jit
                                     else 0)
        max_new = max(1, max_new)
        plen = args.prompt_len
        if args.prompt_jitter:
            plen = max(1, plen + int(rng.integers(-args.prompt_jitter,
                                                  args.prompt_jitter + 1)))
        prompt = corpus.sample(plen, np.random.default_rng(i))
        reqs.append(Request(prompt=prompt, max_new=max_new,
                            temperature=args.temperature, eos_id=args.eos_id,
                            deadline_ms=args.deadline_ms,
                            priority=mix[i % len(mix)], seed=i))

    max_len = (args.prompt_len + args.prompt_jitter + args.new_tokens + jit
               + args.steps_per_sync)
    pooled = args.pool_blocks or args.pool_memory_mb
    if pooled:
        max_len = pool_tiled_max_len(max_len, schedule, args.pool_block_tokens)
    inj = _chaos_injector(args)
    eng = Engine(params, cfg, schedule, batch_slots=args.batch,
                 max_len=max_len, backend=args.backend,
                 steps_per_sync=args.steps_per_sync,
                 prefill_chunk=args.prefill_chunk or None,
                 pool_blocks=args.pool_blocks or None,
                 pool_block_tokens=args.pool_block_tokens,
                 pool_memory_bytes=int(args.pool_memory_mb * 2**20) or None,
                 host_spill_bytes=int(args.host_spill_mb * 2**20) or None,
                 async_host=args.async_host, faults=inj)
    if args.warmup:
        rep = eng.warmup()
        print(f"warmup: {rep['n_executables']} executables AOT-compiled in "
              f"{rep['compile_s']:.2f}s, rehearsal {rep['rehearse_s']:.2f}s")
    if args.open_loop:
        return _open_loop(eng, args, cfg, n_req, max_len, inj)
    t0 = time.time()
    handles = [eng.submit(r) for r in reqs]
    occ_at_finish = {}
    if pooled:
        # step manually so the pool occupancy each request finished at is
        # sampled live (run() would only expose the drained end state)
        while any(not h.finished for h in handles):
            before = eng.stats()["used"]
            if not eng.step():
                break
            # a request's tick-local occupancy: blocks held entering the
            # tick vs still held after its retire released the finishers
            used = max(before, eng.stats()["used"])
            for h in handles:
                if h.finished and h.rid not in occ_at_finish:
                    occ_at_finish[h.rid] = used
    else:
        eng.run(handles)
    eng.drain()              # async host loop: all streams final (§10)
    dt = time.time() - t0

    total_toks = sum(len(h.tokens) for h in handles)
    lat = [(h.finish_time - h.submit_time) * 1e3 for h in handles
           if h.finish_time is not None]
    ttft = [(h.first_token_time - h.submit_time) * 1e3 for h in handles
            if h.first_token_time is not None]
    fp16_b = 2 * cfg.head_dim * 2
    q_b = packed_nbytes(cfg.head_dim, policy.bits_k, policy.group_size,
                        policy.meta_dtype_bits) + \
        packed_nbytes(cfg.head_dim, policy.bits_v, policy.group_size,
                      policy.meta_dtype_bits)
    print(f"arch={cfg.name} policy=K{args.bits_k}V{args.bits_v} "
          f"g{policy.group_size} w{policy.window} slots={args.batch} "
          f"requests={n_req} schedule={args.policy_schedule}")
    _print_schedule_table(schedule, cfg, max_len, params["embed"].dtype)
    info = {k: v for k, v in eng.backend_info.items()
            if k not in ("layer_avg_bits", "layer_cache_bytes")}
    print("backend:", " ".join(f"{k}={v}" for k, v in sorted(info.items())))
    print(f"served {n_req} requests / {total_toks} tokens in {dt:.2f}s "
          f"({total_toks / dt:.1f} tok/s aggregate)")
    print(f"latency ms/request: p50={_pct(lat, 50):.0f} "
          f"p90={_pct(lat, 90):.0f} p99={_pct(lat, 99):.0f} "
          f"max={max(lat, default=0):.0f}")
    print(f"time-to-first-token ms: p50={_pct(ttft, 50):.0f} "
          f"p90={_pct(ttft, 90):.0f} p99={_pct(ttft, 99):.0f} "
          f"max={max(ttft, default=0):.0f}")
    if args.prefill_chunk:
        print(f"chunked prefill: chunk={args.prefill_chunk} "
              f"buckets={eng.chunk_buckets} "
              f"compiled prefill shapes={eng.prefill_shapes} "
              f"(whole-prompt mode would compile one per distinct "
              f"prompt length)")
    if pooled:
        st = eng.stats()
        print("  req  plen  new  ttft_ms  lat_ms  pool_used  reason")
        for h in handles:
            t1 = (f"{(h.first_token_time - h.submit_time) * 1e3:<8.0f}"
                  if h.first_token_time is not None else f"{'-':<8}")
            t2 = (f"{(h.finish_time - h.submit_time) * 1e3:<7.0f}"
                  if h.finish_time is not None else f"{'-':<7}")
            print(f"  {h.rid:<4d} {len(h.request.prompt):<5d} "
                  f"{len(h.tokens):<4d} {t1} {t2} "
                  f"{occ_at_finish.get(h.rid, 0)}/{st['blocks']}"
                  f"{'':<6}{h.finish_reason}")
        print(f"pool: {st['pool_blocks']} blocks x "
              f"{st['pool_block_tokens']} tok/band, peak used "
              f"{st['peak_used']} ({st['peak_resident_bytes']} B packed "
              f"vs {st['striped_worst_case_bytes']} B striped worst case), "
              f"prefix hit rate {st['prefix_hit_rate']:.2f} "
              f"({st['prefix_hits']} hits / {st['prefix_misses']} misses), "
              f"cow copies {st['cow_copies']}")
    print(f"KV bytes/token-head: fp16={fp16_b}  skvq={q_b} "
          f"({fp16_b / q_b:.1f}x compression)")
    if handles[0].tokens:
        print("sample:", handles[0].tokens[:16])
    _degradation_summary(eng, inj)


if __name__ == "__main__":
    main()
