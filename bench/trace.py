"""From a profiler trace to the numbers the per-layer readers take.

The traced run starts ``jax.profiler`` for the last part of the window
(:class:`Tracer`), inside a host span ``bench.traced_window``, and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``.  :func:`reduce` keeps,
for the devices (planes ``/device:...``, lines ``XLA Ops`` and ``XLA
Modules``) and within the traced window:

* busy time: the union of the device-op intervals, averaged over devices;
* per-module device time and execution count (``jit_multi``, ``jit_chunk``);
* per-op device time (kernels are found by name);
* the idle gaps of the first device, each attributed to the ``bench.*``
  host span that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench.traced_window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def module_base(name: str) -> str:
    """``jit_multi(123)`` / ``jit_multi.4`` -> ``jit_multi``."""
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", name.strip())


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def short_op(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..), ..`` -> ``%fusion.3 fusion``: an
    op's HLO name and kind, for the breakdown (TPU traces name each op by
    its whole HLO text)."""
    lhs, eq, rhs = name.partition(" = ")
    kind = re.search(r" ([a-z][a-z0-9-]*)\(", " " + rhs) if eq else None
    return f"{lhs} {kind.group(1)}" if kind else name[:120]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce(pd, top: int = 10) -> Dict:
    """Reduce a ``ProfileData`` to busy/window seconds, module and op
    times, and attributed idle gaps (all in seconds)."""
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if lines.get(OPS_LINE):
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [ev for ev in _events(ln) if ev[0].startswith("bench.")]
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    win = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        allops = [ev for d in devices for ev in d[OPS_LINE]]
        lo, hi = min(e[1] for e in allops), max(e[2] for e in allops)
    spans = [ev for ev in host if ev[0] != WINDOW_SPAN]

    busy, modules, ops = [], {}, {}
    first_union = None
    for d in devices:
        iv = _clip(np.asarray([e[1:] for e in d[OPS_LINE]], float), lo, hi)
        u = union(iv)
        if first_union is None:
            first_union = u
        busy.append(float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0)
        for name, s, e in d[OPS_LINE]:
            if s >= lo and e <= hi:
                ops[name] = ops.get(name, 0.0) + (e - s)
        for name, s, e in d.get(MODULES_LINE, []):
            if s >= lo and e <= hi:
                m = modules.setdefault(module_base(name), [0.0, 0])
                m[0] += e - s
                m[1] += 1
    n_dev = len(devices)
    gaps = []
    edges = np.concatenate([[lo], first_union.ravel(), [hi]]).reshape(-1, 2)
    for s, e in edges:
        if e - s <= 0:
            continue
        best, name = 0.0, "none"
        for sn, ss, se in spans:
            ov = min(e, se) - max(s, ss)
            if ov > best:
                best, name = ov, sn
        gaps.append([name, (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "n_devices": n_dev,
        "modules": {k: [v[0] * 1e-9 / n_dev, v[1] / n_dev]
                    for k, v in modules.items()},
        "ops": {k: v * 1e-9 / n_dev for k, v in ops.items()},
        "device_ops": sorted(([short_op(k), v * 1e-9 / n_dev]
                              for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": gaps[:top],
    }


def op_time(summary: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds of ops whose name matches ``pattern`` and how many
    distinct op names matched."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary["ops"].items() if rx.search(k)]
    return sum(hits), len(hits)


def load(log_dir: str):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(paths[-1])


class Tracer:
    """Traces the last ``trace_s`` seconds of a window.  The engine is
    drained at both ends, so the tokens counted between them are exactly
    the ones the traced device work produced."""

    def __init__(self, eng, log_dir: str, seconds: float, trace_s: float):
        self.eng, self.log_dir = eng, log_dir
        self.start_at = max(0.0, seconds - trace_s)
        self.active = self.done = False
        self.n0: Dict[int, int] = {}
        self.info: Dict = {}

    def tick(self, now: float, pairs) -> None:
        if self.active or self.done or now < self.start_at:
            return
        import jax
        self.eng.drain()
        self.n0 = {a.index: len(h.tokens) for a, h in pairs}
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t_start = self.eng.now()
        self.active = True

    def stop(self, pairs) -> None:
        if not self.active:
            return
        import jax
        self.eng.drain()
        self._span.__exit__(None, None, None)
        t_end = self.eng.now()
        jax.profiler.stop_trace()
        self.active, self.done = False, True
        steps, firsts, contexts = 0, 0, []
        for a, h in pairs:
            n0, n1 = self.n0.get(a.index, 0), len(h.tokens)
            if n1 > n0 and n0 == 0:
                firsts += 1          # its first token came from prefill
            if n0 > 0 and n1 > n0:
                contexts.append((len(a.prompt), n0, n1))
            steps += n1 - n0
        self.info = {"host_window_s": t_end - self.t_start,
                     "tokens": steps, "decode_tokens": steps - firsts,
                     "contexts": contexts}

    def summary(self) -> Optional[Dict]:
        if not self.done:
            return None
        out = reduce(load(self.log_dir))
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return out


def decode_token_ms(ctx) -> Optional[float]:
    """Device time of ``jit_multi`` per decode token delivered in the
    traced window, ms (shared by the ``decode_token_ms.*`` readers)."""
    t, info = ctx["trace"], ctx["traced"]
    if not t or "jit_multi" not in t["modules"] or not info.get("decode_tokens"):
        return None
    return t["modules"]["jit_multi"][0] / info["decode_tokens"] * 1e3
