"""The program's own host spans in a profiler trace, and a window that keeps
them.

The serving program opens a host span ``engine.step`` around each scheduler
tick and one span per phase inside it (``serving/tracing.py``:
``engine.lifecycle``, ``engine.retire``, ``engine.admit``,
``engine.prefill_chunk``, ``engine.cow``, ``engine.flush_tables``,
``engine.decode.dispatch``, ``engine.decode.wait``, ``engine.deliver``);
``engine.step`` carries the engine's gauges as metadata.  :func:`reduce`
keeps, on the host thread line that holds ``bench.traced_window``:

* ``spans``: per ``engine.*`` name, total seconds, self seconds (duration
  minus its ``engine.*`` children on the same line) and count;
* ``steps``: the metadata of each ``engine.step``; ``longest_span_s``:
  per name, the longest single span;
* ``idle_gaps``: the first device's longest idle gaps, each named after
  the ``bench.*`` or ``engine.*`` span whose self time overlaps it most
  (with no program spans in the trace this is ``bench.trace``'s
  attribution); ``long_gaps``: per name, the count and seconds of all
  gaps of 1 ms or more;
* ``clock``: whether each ``jit_multi`` execution on the device lies
  between the start of its ``engine.decode.dispatch`` and the end of the
  ``engine.decode.wait`` after it; ``longest_modules``: the longest
  ``jit_multi`` executions, as [start in the window, seconds].

The benchmark's own reduction (``bench/trace.py``) does not read program
spans; :func:`engine_host_ms` and :func:`pool_used_share` compute the two
numbers a per-layer reader would report from them.

Run as a script, it runs one window of a cell as ``bench/run.py`` does
(same build, loop and tracer), without the reference check, and prints one
JSON line: the window's tokens/s and step seconds, the engine's decode
counters over the window, and with ``--trace 1`` the reduction above and
the cell's per-layer metrics from the same trace:

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
        [--trace-seconds <s>]

Without a TPU it exits with 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import trace  # noqa: E402

PROGRAM, HARNESS = "engine.", "bench."
STEP, DISPATCH, WAIT = ("engine.step", "engine.decode.dispatch",
                        "engine.decode.wait")
MODULE = "jit_multi"
LONG_GAP_S = 1e-3


def _nest(evs: List[tuple]) -> List[dict]:
    """Spans of one thread line, ``(name, start, end, stats)``, each with
    its self intervals: its own interval minus its direct children's."""
    out = [{"name": n, "start": s, "end": e, "stats": st, "kids": []}
           for n, s, e, st in sorted(evs, key=lambda x: (x[1], -x[2]))]
    stack: List[dict] = []
    for sp in out:
        while stack and stack[-1]["end"] <= sp["start"]:
            stack.pop()
        if stack and sp["end"] <= stack[-1]["end"]:
            stack[-1]["kids"].append((sp["start"], sp["end"]))
        stack.append(sp)
    for sp in out:
        iv, at = [], sp["start"]
        for s, e in sorted(sp["kids"]):
            if s > at:
                iv.append((at, s))
            at = max(at, e)
        if sp["end"] > at:
            iv.append((at, sp["end"]))
        sp["self"] = iv
    return out


def _host_line(pd) -> Optional[List[tuple]]:
    """The ``bench.*``/``engine.*`` spans of the host line that holds the
    traced window (else of the first line with program spans)."""
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(e.name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns), dict(e.stats))
                   for e in ln.events
                   if e.name.startswith((PROGRAM, HARNESS))]
            if any(ev[0] == trace.WINDOW_SPAN for ev in evs):
                return evs
            if any(ev[0].startswith(PROGRAM) for ev in evs):
                lines.append(evs)
    return lines[0] if lines else None


def reduce(pd, top: int = 10) -> Dict:
    """Program spans, step metadata, self-time gap attribution and the
    shared-clock check of a ``ProfileData`` (seconds)."""
    evs = _host_line(pd) or []
    win = [ev for ev in evs if ev[0] == trace.WINDOW_SPAN]
    devices = [{ln.name: trace._events(ln) for ln in plane.lines}
               for plane in pd.planes
               if plane.name.startswith("/device:") and "CPU" not in plane.name]
    devices = [d for d in devices if d.get(trace.OPS_LINE)]
    if win:
        lo, hi = win[0][1], win[0][2]
    elif devices:
        ops = devices[0][trace.OPS_LINE]
        lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    else:
        lo, hi = -np.inf, np.inf
    nested = _nest([ev for ev in evs if ev[0] != trace.WINDOW_SPAN
                    and lo <= ev[1] and ev[2] <= hi])
    spans: Dict[str, List[float]] = {}
    longest: Dict[str, float] = {}
    for sp in nested:
        if not sp["name"].startswith(PROGRAM):
            continue
        dur = (sp["end"] - sp["start"]) * 1e-9
        t = spans.setdefault(sp["name"], [0.0, 0.0, 0])
        t[0] += dur
        t[1] += sum(e - s for s, e in sp["self"]) * 1e-9
        t[2] += 1
        longest[sp["name"]] = max(longest.get(sp["name"], 0.0), dur)
    out = {"spans": spans, "longest_span_s": longest,
           "steps": [sp["stats"] for sp in nested if sp["name"] == STEP],
           "idle_gaps": [], "long_gaps": {}, "clock": None,
           "longest_modules": []}
    if not devices:
        return out
    d = devices[0]
    busy = trace.union(trace._clip(
        np.asarray([e[1:] for e in d[trace.OPS_LINE]], float), lo, hi))
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = [[attribute(s, e, nested), (e - s) * 1e-9]
            for s, e in edges if e > s]
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])[:top]
    for name, secs in gaps:
        if secs >= LONG_GAP_S:
            g = out["long_gaps"].setdefault(name, [0, 0.0])
            g[0] += 1
            g[1] += secs
    multi = [ev for ev in d.get(trace.MODULES_LINE, [])
             if trace.module_base(ev[0]) == MODULE and lo <= ev[1]
             and ev[2] <= hi]
    out["clock"] = clock_check(multi, nested)
    out["longest_modules"] = sorted(
        ([(ms - lo) * 1e-9, (me - ms) * 1e-9] for _, ms, me in multi),
        key=lambda m: -m[1])[:top]
    return out


def attribute(s: float, e: float, nested: List[dict]) -> str:
    """The span whose self time overlaps ``[s, e)`` most, or ``none``."""
    best, name = 0.0, "none"
    for sp in nested:
        ov = sum(max(0.0, min(e, b) - max(s, a)) for a, b in sp["self"])
        if ov > best:
            best, name = ov, sp["name"]
    return name


def clock_check(modules: List[tuple], nested: List[dict]) -> Dict:
    """Each ``jit_multi`` execution against the last dispatch span that
    began before it and the first wait span after that dispatch: it must
    start after the dispatch begins and end before the wait ends."""
    disp = sorted(sp["start"] for sp in nested if sp["name"] == DISPATCH)
    waits = sorted((sp["start"], sp["end"]) for sp in nested
                   if sp["name"] == WAIT)
    ok, lead, tail = 0, [], []
    for _, ms, me in modules:
        before = [t for t in disp if t <= ms]
        if not before:
            continue
        after = [w for w in waits if w[0] >= before[-1]]
        if after and me <= after[0][1]:
            ok += 1
            lead.append((ms - before[-1]) * 1e-6)
            tail.append((after[0][1] - me) * 1e-6)
    return {"executions": len(modules), "held": ok,
            "dispatch_to_start_ms": [min(lead), max(lead)] if lead else None,
            "end_to_wait_end_ms": [min(tail), max(tail)] if tail else None}


def engine_host_ms(summary: Optional[Dict]) -> Optional[float]:
    """Scheduler host time per decode sync, ms: (Σ ``engine.step`` − Σ
    ``engine.decode.wait``) ÷ the number of waits in the traced window."""
    spans = (summary or {}).get("spans") or {}
    if STEP not in spans or not spans.get(WAIT, [0, 0, 0])[2]:
        return None
    return (spans[STEP][0] - spans[WAIT][0]) / spans[WAIT][2] * 1e3


def pool_used_share(summary: Optional[Dict]) -> Optional[float]:
    """Mean over the traced ``engine.step`` spans of pool blocks used ÷
    pool blocks, %."""
    steps = [st for st in (summary or {}).get("steps") or []
             if st.get("pool_blocks")]
    if not steps:
        return None
    return 100.0 * float(np.mean([st["pool_used"] / st["pool_blocks"]
                                  for st in steps]))


def per_sync_ms(summary: Dict) -> Dict[str, float]:
    """Self ms of each program span but the wait, per decode sync; they
    add up to :func:`engine_host_ms`."""
    spans = summary["spans"]
    n = spans.get(WAIT, [0, 0, 0])[2]
    return {k: v[1] / n * 1e3 for k, v in spans.items()
            if k != WAIT and n}


class _Counters:
    """Loop hook that reads the engine's counters where the window (or the
    traced part of it) starts and ends."""

    KEYS = ("decode_syncs", "decode_call_s")

    def __init__(self, eng):
        self.eng, self.at = eng, {}

    def read(self, when: str) -> None:
        c = self.eng.stats()["counters"]
        self.at[when] = {k: c.get(k) for k in self.KEYS}

    def delta(self) -> Optional[Dict]:
        a, b = self.at.get("start"), self.at.get("stop")
        if not a or not b or a["decode_syncs"] is None:
            return None
        n = b["decode_syncs"] - a["decode_syncs"]
        s = b["decode_call_s"] - a["decode_call_s"]
        return {"decode_syncs": n, "decode_call_s": s,
                "decode_call_ms_per_sync": s / n * 1e3 if n else None}


class _Untraced(_Counters):
    def tick(self, now, pairs) -> None:
        if "start" not in self.at:
            self.read("start")

    def stop(self, pairs) -> None:
        self.read("stop")


class _Traced(trace.Tracer):
    """``bench.trace.Tracer`` that also reads the counters at the traced
    part's ends and hands back the trace itself."""

    def __init__(self, *a):
        super().__init__(*a)
        self.counters = _Counters(self.eng)

    def tick(self, now, pairs) -> None:
        was = self.active
        super().tick(now, pairs)
        if self.active and not was:
            self.counters.read("start")

    def stop(self, pairs) -> None:
        if self.active:
            self.counters.read("stop")
        super().stop(pairs)

    def profile(self):
        if not self.done:
            return None
        pd = trace.load(self.log_dir)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return pd


def window(name: str, seed: int, seconds: float, traced: bool,
           smoke: bool = False, t_start: float = None,
           trace_s: Optional[float] = None) -> Dict:
    """One window of cell ``name``; returns the result line as a dict.
    ``trace_s`` is how much of the window's end is traced (default
    ``bench/run.py``'s)."""
    from bench import costs, loops, run, spec

    t_start = T_START if t_start is None else t_start
    b = run.build(name, seed, seconds, smoke)
    eng, mix = b.eng, b.mix
    hook = (_Traced(eng, str(run.TRACE_DIR / f"spans-{name}"), seconds,
                    trace_s or run.TRACE_S) if traced else _Untraced(eng))
    if mix["loop"] == "closed":
        w = loops.run_closed(eng, b.arrivals, seconds, b.request, hook)
    else:
        w = loops.run_open(eng, b.arrivals, seconds, b.request,
                           mix.get("drain_limit_s", 60), hook)
    rep = eng.warmup_report()
    out = {"workload": name, "seed": seed, "device": b.device,
           "output_tok_s": w.tokens / (w.t1 - w.t0),
           "setup_s": w.t0 - t_start, "window_s": w.t1 - w.t0,
           "tokens": w.tokens, "compile_s": rep["compile_s"],
           "rehearse_s": rep["rehearse_s"],
           "post_warmup_compiles": rep["post_warmup_compiles"],
           "counters": eng.stats()["counters"]}
    if w.step_s:
        out["step_s"] = {"n": len(w.step_s), "min": min(w.step_s),
                         "median": statistics.median(w.step_s),
                         "max": max(w.step_s), "all": w.step_s}
    if not traced:
        out["window_counters"] = hook.delta()
        eng.close()
        return out
    out["traced_counters"] = hook.counters.delta()
    pd = hook.profile()
    eng.close()
    if pd is None:
        return out
    info = hook.info
    if info.get("host_window_s"):
        out["traced_tok_s"] = info["tokens"] / info["host_window_s"]
    summary = trace.reduce(pd)
    prog = reduce(pd)
    ctx = {"window": w, "setup_s": out["setup_s"], "end_s": eng.now(),
           "dims": b.dims, "pol": b.pol_d, "trace": summary, "traced": info,
           "peaks": costs.peaks(b.device["kind"])}
    bm = spec.benchmark()
    out["metrics"] = {m["name"]: spec.reader(m["name"])(ctx)
                      for m in spec.metrics_for(name, bm, True)}
    out["metrics"]["engine_host_ms"] = engine_host_ms(prog)
    out["metrics"]["pool_used_share"] = pool_used_share(prog)
    out["per_sync_ms"] = per_sync_ms(prog)
    out["spans"] = prog["spans"]
    out["longest_span_s"] = prog["longest_span_s"]
    out["steps"] = {"n": len(prog["steps"]), "first": prog["steps"][:1],
                    "last": prog["steps"][-1:]}
    out["idle_gaps"] = prog["idle_gaps"]
    out["long_gaps"] = prog["long_gaps"]
    out["bench_idle_gaps"] = summary["idle_gaps"]
    out["clock"] = prog["clock"]
    out["longest_modules"] = prog["longest_modules"]
    out["busy_s"], out["traced_window_s"] = summary["busy_s"], summary["window_s"]
    out["modules"] = summary["modules"]
    out["device_ops"] = summary["device_ops"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="trace the last this many seconds of the window "
                         "(default: bench/run.py's TRACE_S)")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("spans: JAX found no TPU; this runs on the chip only",
              file=sys.stderr)
        return 2
    print(json.dumps(window(args.workload, args.seed, args.seconds,
                            bool(args.trace), trace_s=args.trace_seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
