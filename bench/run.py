"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with bf16 weights drawn on the device from
``--seed``, enables the persistent compile cache at its fixed path inside
the checkout, warms only the cell's own shapes (``Engine.warmup()``), builds
what the traffic needs (for a closed loop: every session prefilled), then
measures for ``--seconds``.  With ``--trace 1`` it traces the last part of
the window and reports the cell's per-layer metrics instead of its
end-to-end ones.  Afterwards the program's state is freed and the served
tokens are compared with the plain reference (``bench/check.py``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``).  Without a TPU, or with fewer chips
than the cell asks for, it exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TRACE_S = 12.0          # the traced part of a --trace 1 window, at most
TRACE_DIR = ROOT / ".bench_traces"


def _log(*a):
    print(*a, flush=True)


class Built:
    """A cell made ready for its window: engine warmed, arrivals drawn."""


def build(name: str, seed: int, seconds: float, smoke: bool = False
          ) -> Built:
    """Configuration, seeded weights, warmed engine and arrivals of a cell."""
    import jax
    from bench import model, spec
    from bench import traffic as tr
    from bench import weights

    b = Built()
    b.cell = c = spec.cell(name)
    config = c["config"]
    b.mix = mix = tr.merged(c["traffic"], smoke)
    b.dims = dims = model.dims(config, smoke)
    cfg, pol = model.arch(dims), model.policy(config, dims)
    b.pol_d = {"bits_k": pol.bits_k, "bits_v": pol.bits_v,
               "group_size": pol.group_size, "window": pol.window,
               "n_sink": pol.n_sink, "fp8_meta": pol.fp8_meta}
    knobs = model.engine_knobs(c["traffic"], smoke)
    dev = jax.devices()[0]
    b.device = {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())}
    _log(f"device: {b.device}")
    if not smoke:
        _log(f"compile cache: {model.enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        _log(f"pallas: {model.interpret_mode()}")
    b.params = weights.make(dims, seed)
    b.arrivals = tr.generate(mix, seed, seconds, c.get("rate"),
                             dims["vocab_size"])
    max_len = model.capacity(pol, knobs, *tr.longest(mix))
    b.eng = eng = model.build_engine(b.params, cfg, pol, knobs, max_len)
    rep = eng.warmup()
    _log(f"warmup: {rep['n_executables']} executables, compile "
         f"{rep['compile_s']:.3f}s, rehearsal {rep['rehearse_s']:.3f}s; "
         f"capacity {max_len}, {knobs['slots']} slots, "
         f"pool {eng.pool_blocks} blocks of {knobs['pool_block_tokens']}")

    def request(a):
        return model.request(a.prompt, a.max_new, seed=a.index,
                             temperature=mix.get("temperature", 0.0))
    b.request = request
    return b


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             smoke: bool = False, t_start: float = None,
             control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    ``smoke`` takes the configuration's and the mix's smoke sizes (tests).
    ``control`` also scores the sample with the fp8 reference, for the
    readings a limit is set from (``bench/control.py``; never in a
    benchmark run): the line then has ``control_checks``."""
    import jax
    from bench import check, costs, endtoend, loops, model, reference, spec
    from bench import weights
    from bench.trace import Tracer

    t_start = T_START if t_start is None else t_start
    bm = spec.benchmark()
    b = build(name, seed, seconds, smoke)
    c, mix, dims, pol_d, eng, device = (b.cell, b.mix, b.dims, b.pol_d,
                                        b.eng, b.device)
    params, arrivals, request = b.params, b.arrivals, b.request
    tracer = Tracer(eng, str(TRACE_DIR / name), seconds, TRACE_S) \
        if trace else None
    with model.count_compiles() as n_compiles:
        if mix["loop"] == "closed":
            w = loops.run_closed(eng, arrivals, seconds, request, tracer)
        else:
            w = loops.run_open(eng, arrivals, seconds, request,
                               mix.get("drain_limit_s", 60), tracer)
    end_s = eng.now()
    compiles = n_compiles() + eng.warmup_report()["post_warmup_compiles"]
    _log(f"compiles after warm-up, inside the set-up traffic and the "
         f"window: {compiles}")
    device["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:1]))
    counters = eng.stats()["counters"]
    _log(f"engine counters: {counters}")
    recs = endtoend.due(w.records)
    attempted = len(recs)
    failed = sum(1 for r in recs
                 if r.n_tokens == 0 or r.reason not in (None, "length"))
    _log(f"window: {w.t1 - w.t0:.3f}s, {w.tokens} tokens delivered, "
         f"{attempted} requests due, {failed} failed; "
         f"finished {sum(r.finish_s is not None for r in recs)}")
    if w.step_s:
        _log(f"window steps: {len(w.step_s)}, seconds min "
             f"{min(w.step_s):.4f} median {sorted(w.step_s)[len(w.step_s) // 2]:.4f}"
             f" max {max(w.step_s):.4f}")

    ctx = {"window": w, "setup_s": w.t0 - t_start, "end_s": end_s,
           "dims": dims, "pol": pol_d, "trace": None, "traced": {}}
    out_metrics, breakdown = {}, None
    if trace:
        summary = tracer.summary()
        ctx.update(trace=summary, traced=tracer.info,
                   peaks=costs.peaks(device["kind"]))
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
            _log(f"trace: modules {summary['modules']}")
            _log(f"trace: traced window {tracer.info}")
    for m in spec.metrics_for(name, bm, trace):
        v = (spec.reader(m["name"])(ctx) if trace
             else endtoend.compute(m["name"], ctx))
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state before the reference runs
    sample = check.sample(w.records, mix["check"]["sample"], seed)
    pairs = [(r.prompt, list(r.tokens)) for r in sample]
    eng.close()
    del eng, params, w, ctx, tracer
    gc.collect()
    ref_params = weights.make(dims, seed)
    t_ref = time.monotonic()
    per_pos = reference.gaps(ref_params, dims, pol_d, pairs) if pairs else []
    ctl_pos = (reference.control_gaps(ref_params, dims, pol_d, pairs)
               if control and pairs else None)
    del ref_params
    _log(f"reference: {len(pairs)} requests, "
         f"{sum(len(s) for _, s in pairs)} served tokens, "
         f"{time.monotonic() - t_ref:.3f}s; widest gap per request "
         f"{[float(g.max()) for g in per_pos]}")
    limits = c["smoke_limits" if smoke else "limits"]
    numbers = check.numbers(per_pos)
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    checks["compiles"] = {"value": compiles, "limit": 0}
    compared = [v for v in checks.values() if v["limit"] is not None]
    correct = (bool(per_pos) and len(compared) > 2
               and all(v["value"] is not None and v["value"] <= v["limit"]
                       for v in compared))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if ctl_pos is not None:
        result["control_checks"] = {
            k: {"value": v, "limit": limits.get(k)}
            for k, v in check.numbers(ctl_pos).items()}
    result["checks"] = checks
    for k, v in checks.items():
        print(check.line(k, v["value"], v["limit"]), file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax
    from bench import spec
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform}); "
              f"this benchmark runs on the chip only", file=sys.stderr)
        return 2
    chips = spec.workload(spec.benchmark(), args.workload)["chips"]
    if len(devs) < chips:
        print(f"bench: cell {args.workload} needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
