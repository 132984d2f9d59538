"""End-to-end and per-layer arithmetic on fixed records."""
import pytest

from bench import costs, endtoend, spec
from bench.loops import Record, Window

# the decode kernel's op as a TPU trace names it (HLO text)
KERNEL_OP = ("%closed_call.42 = (f32[4,8,4,128]{3,2,1,0:T(4,128)S(1)}, "
             "f32[4,8,4,1]{3,2,1,0:T(4,128)S(1)}, f32[4,8,4,1]{3,2,1,0:"
             "T(4,128)S(1)}) custom-call(s32[4,2]{1,0} %p0)")


def _window():
    w = Window()
    w.t0, w.t1, w.tokens = 100.0, 110.0, 500
    # due at 100 + i; submitted 1 ms late; admitted 10*i ms after due;
    # first token 100 ms after due; 11 tokens over the next 500 ms
    w.records = [Record(index=i, prompt_len=10, max_new=11, due=True,
                        due_s=100.0 + i, submit_s=100.001 + i,
                        admit_s=100.0 + i + 0.01 * i, first_s=100.1 + i,
                        finish_s=100.6 + i, n_tokens=11, reason="length")
                 for i in range(20)]
    w.records.append(Record(index=99, prompt_len=10, max_new=11, due=False,
                            due_s=111.0, submit_s=111.0, first_s=140.0))
    return w


@pytest.fixture
def ctx():
    return {"window": _window(), "setup_s": 42.5, "end_s": 121.0,
            "trace": None, "traced": {}}


def test_end_to_end(ctx):
    assert endtoend.compute("setup_s", ctx) == 42.5
    assert endtoend.compute("ttft_p95_ms", ctx) == pytest.approx(100.0)
    assert endtoend.compute("tpot_p95_ms", ctx) == pytest.approx(50.0)
    assert endtoend.compute("output_tok_s", ctx) == pytest.approx(50.0)


def test_request_without_first_token_waits_to_the_end(ctx):
    ctx["window"].records[0].first_s = None
    assert endtoend.ttft_ms(ctx["window"].records[0], ctx["end_s"]) == \
        pytest.approx(21_000.0)


def _traced(ctx):
    dims = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 16,
            "vocab_size": 32, "family": "dense"}
    pol = {"bits_k": 2.0, "bits_v": 1.5, "group_size": 64, "window": 32,
           "n_sink": 5, "fp8_meta": True}
    ctx.update(dims=dims, pol=pol,
               peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
               trace={"window_s": 2.0, "busy_s": 1.5,
                      "modules": {"jit_multi": [0.8, 10],
                                  "jit_chunk": [0.3, 6]},
                      "ops": {KERNEL_OP: 0.25, "fusion.3": 0.1}},
               traced={"tokens": 44, "decode_tokens": 40,
                       "contexts": [(100, 1, 21), (200, 5, 25)]})
    return ctx


def test_device_readers(ctx):
    ctx = _traced(ctx)
    assert spec.reader("decode_token_ms.longctx")(ctx) == pytest.approx(20.0)
    assert spec.reader("device_idle.longctx")(ctx) == pytest.approx(25.0)


def test_roofline_and_mfu_readers(ctx):
    ctx = _traced(ctx)
    # slot 1: lengths 101..120, slot 2: 205..224; 37 fp tokens never packed
    live = sum(n - 37 for n in range(101, 121)) + sum(
        n - 37 for n in range(205, 225))
    nbytes = live * 2 * (36 + 28) * 2          # 2 KV heads, 2 layers
    assert costs.decode_attn_bytes(ctx["traced"]["contexts"], ctx["dims"],
                                   ctx["pol"]) == nbytes
    roof = spec.reader("decode_attn_roofline")(ctx)
    assert roof == pytest.approx(100.0 * nbytes / 1e9 / 0.25)
    w = 2 * (2 * (8 * 512 + 2 * 8 * 256 + 512 * 8 + 3 * 8 * 16) + 8 * 32)
    flops = 40 * w + sum(4 * 512 * n * 2 for n in
                         list(range(101, 121)) + list(range(205, 225)))
    assert spec.reader("decode_mfu")(ctx) == pytest.approx(
        100.0 * flops / 2.0 / 1e12)


def test_readers_find_nothing_without_a_trace(ctx):
    for name in ("decode_token_ms.longctx", "device_idle.longctx",
                 "decode_attn_roofline", "decode_mfu"):
        assert spec.reader(name)(ctx) is None


def test_roofline_silent_without_the_kernel(ctx):
    ctx = _traced(ctx)
    ctx["trace"]["ops"] = {"fusion.3": 0.1}
    assert spec.reader("decode_attn_roofline")(ctx) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9000")
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
