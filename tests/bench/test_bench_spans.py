"""The program-span reduction (``bench/spans.py``) on small recorded traces
(XSpaces in text form): nested ``engine.*`` spans with step metadata, and a
trace with ``bench.*`` spans alone."""
import pytest

from bench import spans, trace

MS = 1_000_000_000  # picoseconds in a millisecond


def _ev(meta, start_ms, dur_ms, stats=None):
    st = " ".join(f"stats {{ metadata_id: {k} int64_value: {v} }}"
                  for k, v in (stats or {}).items())
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} {st} }}")


def _plane(pid, name, lines, names, stat_names=None):
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in names.items())
    smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for i, n in (stat_names or {}).items())
    body = " ".join(
        f'lines {{ id: {j} name: "{ln}" timestamp_ns: 1000000 {" ".join(evs)} }}'
        for j, (ln, evs) in enumerate(lines, 1))
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} {smeta} }}'


def _device(ops, modules):
    return _plane(1, "/device:TPU:0", [
        ("XLA Ops", [_ev(1, s, d) for s, d in ops]),
        ("XLA Modules", [_ev(m, s, d) for m, s, d in modules]),
    ], {1: "fusion.1", 4: "jit_multi(12)", 5: "jit_chunk(3)"})


# stat ids of the step metadata
POOL_USED, POOL_BLOCKS, TICK = 21, 22, 23


def _program_xspace():
    """Busy [0,12), [21,38) (one jit_multi), [50,60); two ticks: the
    first admits, dispatches, waits and delivers, the second retires."""
    dev = _device([(0, 12), (21, 17), (50, 10), (120, 5)],
                  [(4, 21, 17), (5, 50, 10)])
    names = {1: trace.WINDOW_SPAN, 2: "bench.step", 3: "bench.wait",
             10: "engine.step", 11: "engine.admit",
             12: "engine.decode.dispatch", 13: "engine.decode.wait",
             14: "engine.deliver", 15: "engine.retire"}
    host = _plane(2, "/host:CPU", [
        ("python", [
            _ev(1, 0, 100), _ev(2, 10, 38),
            _ev(10, 11, 36, {POOL_USED: 30, POOL_BLOCKS: 40, TICK: 1}),
            _ev(11, 12, 2), _ev(12, 19, 2), _ev(13, 21, 18), _ev(14, 39, 7),
            _ev(2, 61, 20),
            _ev(10, 62, 18, {POOL_USED: 10, POOL_BLOCKS: 40, TICK: 2}),
            _ev(15, 63, 2), _ev(3, 85, 15)]),
        ("repro-host-loop", [_ev(11, 0, 100)]),
    ], names, {POOL_USED: "pool_used", POOL_BLOCKS: "pool_blocks",
               TICK: "tick"})
    return dev + " " + host


def _bench_only_xspace():
    """``tests/bench/test_bench_trace.py``'s trace: no program spans."""
    dev = _device([(0, 10), (5, 15), (50, 10), (120, 5)],
                  [(4, 0, 8), (4, 8, 12), (5, 50, 10)])
    host = _plane(2, "/host:CPU", [
        ("python", [_ev(1, 0, 100), _ev(2, 15, 30), _ev(3, 55, 45)]),
    ], {1: trace.WINDOW_SPAN, 2: "bench.step", 3: "bench.wait"})
    return dev + " " + host


def _pd(text):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def program():
    return spans.reduce(_pd(_program_xspace()))


def test_span_totals_self_and_counts(program):
    s = program["spans"]
    assert s["engine.step"] == [pytest.approx(0.054), pytest.approx(0.023), 2]
    assert s["engine.decode.wait"] == [pytest.approx(0.018),
                                       pytest.approx(0.018), 1]
    assert s["engine.admit"] == [pytest.approx(0.002), pytest.approx(0.002),
                                 1]
    # only the line holding the traced window counts; no bench.* totals
    assert set(s) == {"engine.step", "engine.admit", "engine.decode.dispatch",
                      "engine.decode.wait", "engine.deliver", "engine.retire"}


def test_step_metadata(program):
    assert [st["tick"] for st in program["steps"]] == [1, 2]
    assert [st["pool_used"] for st in program["steps"]] == [30, 10]
    assert program["longest_span_s"]["engine.step"] == pytest.approx(0.036)


def test_gaps_named_by_self_time(program):
    assert program["idle_gaps"] == [
        ["engine.step", pytest.approx(0.040)],
        ["engine.deliver", pytest.approx(0.012)],
        ["engine.step", pytest.approx(0.009)]]
    assert program["long_gaps"] == {
        "engine.step": [2, pytest.approx(0.049)],
        "engine.deliver": [1, pytest.approx(0.012)]}


def test_benchmark_reduction_unchanged_by_program_spans():
    """``bench/trace.py`` still names every gap after ``bench.*`` spans."""
    summary = trace.reduce(_pd(_program_xspace()))
    assert [g[0] for g in summary["idle_gaps"]] == ["bench.step"] * 3
    assert summary["busy_s"] == pytest.approx(0.039)
    assert summary["modules"]["jit_multi"] == [pytest.approx(0.017), 1]


def test_without_program_spans_gaps_match_the_benchmark():
    pd = _pd(_bench_only_xspace())
    out, summary = spans.reduce(pd), trace.reduce(pd)
    assert out["idle_gaps"] == summary["idle_gaps"]
    assert out["idle_gaps"][0] == ["bench.wait", pytest.approx(0.040)]
    assert out["spans"] == {} and out["steps"] == []
    assert spans.engine_host_ms(out) is None
    assert spans.pool_used_share(out) is None


def test_shared_clock_check(program):
    c = program["clock"]
    assert (c["executions"], c["held"]) == (1, 1)
    assert c["dispatch_to_start_ms"] == [pytest.approx(2.0)] * 2
    assert c["end_to_wait_end_ms"] == [pytest.approx(1.0)] * 2
    assert program["longest_modules"] == [[pytest.approx(0.021),
                                           pytest.approx(0.017)]]


def test_clock_check_sees_an_execution_outside_its_wait():
    nested = spans._nest([("engine.decode.dispatch", 0.0, 2e6, {}),
                          ("engine.decode.wait", 2e6, 5e6, {})])
    assert spans.clock_check([("jit_multi", 1e6, 6e6)], nested)["held"] == 0
    assert spans.clock_check([("jit_multi", 1e6, 4e6)], nested)["held"] == 1


def test_engine_host_ms_and_its_split(program):
    assert spans.engine_host_ms(program) == pytest.approx(36.0)
    split = spans.per_sync_ms(program)
    assert split["engine.step"] == pytest.approx(23.0)
    assert sum(split.values()) == pytest.approx(36.0)


def test_pool_used_share(program):
    assert spans.pool_used_share(program) == pytest.approx(50.0)


@pytest.mark.parametrize("summary", [None, {}, {"window_s": 1.0}])
def test_numbers_find_nothing_without_a_trace(summary):
    assert spans.engine_host_ms(summary) is None
    assert spans.pool_used_share(summary) is None


def test_nesting_takes_self_time_from_direct_children_only():
    out = {sp["name"]: sp["self"] for sp in spans._nest([
        ("a", 0.0, 10.0, {}), ("b", 1.0, 6.0, {}), ("c", 2.0, 3.0, {}),
        ("d", 7.0, 8.0, {})])}
    assert out["a"] == [(0.0, 1.0), (6.0, 7.0), (8.0, 10.0)]
    assert out["b"] == [(1.0, 2.0), (3.0, 6.0)]
    assert out["c"] == [(2.0, 3.0)]


def test_script_exits_2_without_a_tpu():
    assert spans.main(["--workload", "qwen2-7b.longctx-decode", "--seed",
                       str(2 ** 31 + 7), "--seconds", "1"]) == 2


def test_window_reads_the_decode_counters_over_the_window():
    """An untraced window at smoke size on the CPU: every closed-loop step
    of the window is one decode sync of the engine."""
    import time
    r = spans.window("qwen2-7b.longctx-decode", 2 ** 31 + 13, 1.0, False,
                     smoke=True, t_start=time.monotonic())
    wc = r["window_counters"]
    assert wc["decode_syncs"] == r["step_s"]["n"] > 0
    assert 0 < wc["decode_call_s"] <= r["window_s"]
    assert r["counters"]["decode_syncs"] >= wc["decode_syncs"]
    assert r["output_tok_s"] > 0 and r["post_warmup_compiles"] == 0
