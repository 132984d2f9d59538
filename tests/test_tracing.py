"""Host spans and counters of the scheduler (DESIGN.md §10).

A small pooled engine with chunked prefill runs under ``jax.profiler``,
recorded into ``tmp_path``; the trace must hold every ``engine.*`` span,
nested inside ``engine.step`` on one host thread line, with the step's
gauges as metadata.  The counters ``decode_syncs``/``decode_call_s`` are
checked under deterministic clocks.
"""
import glob
import os

import numpy as np
import pytest

import jax

from repro.core.policy import QuantPolicy
from repro.models.config import ArchConfig
from repro.models import transformer as T
from repro.serving import Engine, Request, TickClock
from repro.serving.tracing import span

CFG = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=32, d_ff=32, vocab_size=64)
POL = QuantPolicy(bits_k=2.0, bits_v=1.5, group_size=16, window=8, n_sink=4)
POOL_LEN, POOL_BT = 44, 8      # packed region 44 - 12 = 32 = 4 blocks of 8

SPANS = ("engine.step", "engine.lifecycle", "engine.retire", "engine.admit",
         "engine.prefill_chunk", "engine.cow", "engine.flush_tables",
         "engine.decode.dispatch", "engine.decode.wait", "engine.deliver")
GAUGES = ("queue_depth", "active_slots", "pool_used", "pool_reserved",
          "pool_blocks")


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(3))


def _pooled(params, **kw):
    return Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                  steps_per_sync=4, prefill_chunk=8, pool_blocks=12,
                  pool_block_tokens=POOL_BT, **kw)


def _load(tmp_path):
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return jax.profiler.ProfileData.from_file(path)


def _record(tmp_path, eng, prompts):
    """Serve ``prompts`` under the profiler; returns the trace and the
    gauges read after each step."""
    hs = [eng.submit(Request(prompt=p, max_new=6, seed=i))
          for i, p in enumerate(prompts)]
    after = []
    with jax.profiler.trace(str(tmp_path)):
        while eng.step():
            after.append(eng.gauges())
        eng.drain()
    eng.close()
    assert all(h.finished for h in hs)
    return _load(tmp_path), after


def _engine_line(pd):
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in line.events if e.name.startswith("engine.")]
            if evs:
                return evs
    raise AssertionError("no engine.* span in the trace")


@pytest.mark.parametrize("async_host", [False, True], ids=["sync", "async"])
def test_spans_nest_inside_engine_step(tmp_path, params, async_host):
    eng = _pooled(params, async_host=async_host)
    prompts = [np.arange(22, dtype=np.int32) + i for i in range(2)]
    pd, after = _record(tmp_path, eng, prompts)
    evs = _engine_line(pd)      # every engine span sits on this one line
    n_lines = sum(1 for plane in pd.planes for ln in plane.lines
                  if any(e.name.startswith("engine.") for e in ln.events))
    assert n_lines == 1
    assert {e[0] for e in evs} == set(SPANS)
    steps = [e for e in evs if e[0] == "engine.step"]
    for name, s, e, _ in evs:
        if name != "engine.step":
            assert any(ss <= s and e <= se for _, ss, se, _ in steps), name
    assert len(steps) == len(after) + 1      # the last step returned False
    meta = [st for _, _, _, st in sorted(steps, key=lambda x: x[1])]
    assert [m["tick"] for m in meta] == list(range(1, len(meta) + 1))
    for m, g in zip(meta, after):
        assert {k: m[k] for k in GAUGES} == {k: g[k] for k in GAUGES}
    assert max(m["pool_used"] for m in meta) > 0
    assert sum(m["prefill_tokens"] for m in meta) == 2 * 22
    chunks = [st for name, _, _, st in evs if name == "engine.prefill_chunk"]
    assert sum(c["n"] for c in chunks) == 2 * 22
    assert all(c["bucket"] >= c["n"] for c in chunks)
    assert all(st == {"copies": 0} for name, _, _, st in evs
               if name == "engine.cow")


def test_cow_span_counts_copies(tmp_path, params):
    """Whole-prompt admission takes both requests in one pass, so the
    second shares the first's partly filled tail block and the first
    decode write into it is copied."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4, pool_blocks=12, pool_block_tokens=POOL_BT)
    prompt = np.arange(22, dtype=np.int32)
    pd, _ = _record(tmp_path, eng, [prompt, prompt])
    copies = [st["copies"] for name, _, _, st in _engine_line(pd)
              if name == "engine.cow"]
    assert sum(copies) == eng.stats()["counters"]["cow_copies"] > 0


def test_counters_not_called_without_a_trace():
    def boom():
        raise AssertionError("counters read with no trace recording")
    with span("engine.test", boom):
        pass


def test_counters_read_once_when_the_span_closes(tmp_path):
    calls = []
    with jax.profiler.trace(str(tmp_path)):
        with span("engine.test", lambda: calls.append(1) or {"n": 1}):
            assert calls == []
    assert calls == [1]


class _ReadClock(TickClock):
    """Advances ``dt_s`` on every read, so each interval between two reads
    is known exactly."""

    def __call__(self) -> float:
        self.tick()
        return self.now


@pytest.mark.parametrize("async_host,clock_cls,per_call", [
    (False, TickClock, 0.0), (True, TickClock, 0.0),
    (False, _ReadClock, 0.25)], ids=["sync-tick", "async-tick", "sync-read"])
def test_decode_counters_under_virtual_clocks(params, async_host, clock_cls,
                                              per_call):
    """One request, max_new 9, 4 tokens per sync: its first token comes
    from prefill and the other 8 from two decode chunks.  A TickClock does
    not move inside a decode call; a clock that ticks per read moves once
    between the call's two reads (with the sync host only: the async host
    loop's thread reads the same clock while a call is in flight)."""
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4, async_host=async_host,
                 clock=clock_cls(dt_s=0.25))
    h = eng.submit(Request(prompt=np.arange(10, dtype=np.int32), max_new=9))
    eng.run([h])
    eng.close()
    assert len(h.tokens) == 9
    c = eng.stats()["counters"]
    assert c["decode_syncs"] == 2
    assert c["decode_call_s"] == pytest.approx(2 * per_call)


def test_rehearsal_resets_decode_counters(params):
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4)
    eng.warmup(prompt_lens=[10])
    c = eng.stats()["counters"]
    assert (c["decode_syncs"], c["decode_call_s"]) == (0, 0.0)
    assert isinstance(c["decode_call_s"], float)
    h = eng.submit(Request(prompt=np.arange(10, dtype=np.int32), max_new=5))
    eng.run([h])
    c = eng.stats()["counters"]
    assert c["decode_syncs"] == 1 and c["decode_call_s"] > 0.0


def test_gauges_of_a_striped_engine(params):
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4)
    eng.submit(Request(prompt=np.arange(10, dtype=np.int32), max_new=9))
    assert eng.gauges() == {"queue_depth": 1, "active_slots": 0,
                            "host_queue_depth": 0, "pool_used": 0,
                            "pool_reserved": 0, "pool_blocks": 0}
    eng.step()
    g = eng.gauges()
    assert (g["queue_depth"], g["active_slots"]) == (0, 1)


@pytest.mark.parametrize("pooled", [False, True], ids=["striped", "pooled"])
def test_recorder_samples_engine_gauges(params, pooled):
    """``MetricsRecorder.on_step`` samples ``Engine.gauges()``: the pool
    column appears only for a pooled engine."""
    from repro.serving import (MetricsRecorder, WorkloadSpec, poisson_trace,
                               run_open_loop)
    kw = dict(pool_blocks=12, pool_block_tokens=POOL_BT) if pooled else {}
    eng = Engine(params, CFG, POL, batch_slots=2, max_len=POOL_LEN,
                 steps_per_sync=4, async_host=True, **kw)
    spec = WorkloadSpec(n_requests=4, arrival_rate=40.0, prompt_lens=(14,),
                        max_news=(9,), vocab=CFG.vocab_size, seed=2)
    rec = MetricsRecorder()
    run_open_loop(eng, poisson_trace(spec), rec, time_scale=0.01)
    eng.close()
    summ = rec.summary()
    assert summ["active_slots_max"] >= 1
    assert ("pool_used_max" in summ) == pooled
    if pooled:
        assert summ["pool_used_max"] > 0
