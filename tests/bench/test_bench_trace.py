"""The trace reduction on a small recorded trace (an XSpace in text form)."""
import pytest

from bench import trace

MS = 1_000_000_000  # picoseconds in a millisecond


def _ev(meta, start_ms, dur_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def _plane(pid, name, lines, names):
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in names.items())
    body = " ".join(
        f'lines {{ id: {j} name: "{ln}" timestamp_ns: 1000000 {" ".join(evs)} }}'
        for j, (ln, evs) in enumerate(lines, 1))
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


def _xspace():
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [_ev(1, 0, 10), _ev(2, 5, 15), _ev(3, 50, 10),
                     _ev(1, 120, 5)]),
        ("XLA Modules", [_ev(4, 0, 8), _ev(4, 8, 12), _ev(5, 50, 10)]),
    ], {1: "fusion.1", 2: "decode_attn", 3: "convolution.7",
        4: "jit_multi(12)", 5: "jit_chunk(3)"})
    host = _plane(2, "/host:CPU", [
        ("python", [_ev(1, 0, 100), _ev(2, 15, 30), _ev(3, 55, 45)]),
    ], {1: trace.WINDOW_SPAN, 2: "bench.step", 3: "bench.wait"})
    return dev + " " + host


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(_xspace()))


def test_window_and_busy_union(summary):
    assert summary["window_s"] == pytest.approx(0.100)
    # [0, 10) and [5, 20) merge; [50, 60); the op at 120 ms is outside
    assert summary["busy_s"] == pytest.approx(0.030)
    assert summary["n_devices"] == 1


def test_module_time_and_count(summary):
    total, n = summary["modules"]["jit_multi"]
    assert (total, n) == (pytest.approx(0.020), 2)
    total, n = summary["modules"]["jit_chunk"]
    assert (total, n) == (pytest.approx(0.010), 1)


def test_op_time_by_name(summary):
    secs, n = trace.op_time(summary, r"decode_attn")
    assert (secs, n) == (pytest.approx(0.015), 1)
    assert summary["device_ops"][0] == ["decode_attn", pytest.approx(0.015)]


def test_idle_gaps_attributed_to_host_spans(summary):
    gaps = summary["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(0.040)]
    assert gaps[1] == ["bench.step", pytest.approx(0.030)]
    assert sum(g[1] for g in gaps) == pytest.approx(
        summary["window_s"] - summary["busy_s"])


@pytest.mark.parametrize("raw,base", [("jit_multi(12)", "jit_multi"),
                                      ("jit_chunk.3", "jit_chunk"),
                                      ("jit_multi", "jit_multi")])
def test_module_base(raw, base):
    assert trace.module_base(raw) == base


def test_union_merges_touching_and_nested():
    import numpy as np
    u = trace.union(np.asarray([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0],
                                [3.0, 4.0], [5.5, 5.7]]))
    assert u.tolist() == [[0.0, 4.0], [5.0, 6.0]]


@pytest.mark.parametrize("name,short", [
    ("%closed_call.42 = (f32[4,8,4,128]{3,2,1,0:T(4,128)S(1)}, "
     "f32[4,8,4,1]{3,2,1,0:T(4,128)S(1)}) custom-call(s32[4,2]{1,0} %p0)",
     "%closed_call.42 custom-call"),
    ("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p0), kind=kLoop",
     "%fusion.3 fusion"),
    ("decode_attn", "decode_attn"),
])
def test_breakdown_names_ops_by_name_and_kind(name, short):
    from bench.trace import short_op
    assert short_op(name) == short
