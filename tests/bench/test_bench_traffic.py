"""Traffic is a pure function of the mix, the rate, the window and the seed."""
import numpy as np
import pytest

from bench import spec, traffic

SEED = 2 ** 31 + 977


def _bytes(arrivals):
    return b"".join(np.float64(a.t).tobytes() + a.prompt.tobytes()
                    + np.int64(a.max_new).tobytes() + bytes([a.due])
                    for a in arrivals)


@pytest.mark.parametrize("mix,rate", [("chat-poisson", 1.5),
                                      ("longctx-decode", None)])
def test_same_seed_same_bytes(mix, rate):
    t = traffic.merged(spec.load("traffic", mix))
    a = traffic.generate(t, SEED, 40.0, rate, 152064)
    b = traffic.generate(t, SEED, 40.0, rate, 152064)
    assert _bytes(a) == _bytes(b)
    c = traffic.generate(t, SEED + 1, 40.0, rate, 152064)
    assert _bytes(a) != _bytes(c)


def test_open_loop_seeds_share_sizes_and_gaps():
    t = traffic.merged(spec.load("traffic", "chat-poisson"))
    runs = [traffic.generate(t, s, 40.0, 1.5, 1000) for s in (1, 2, SEED)]
    for r in runs:
        due = [a for a in r if a.due]
        assert len(due) == 60
        assert all(0.0 <= a.t < 40.0 for a in due)
        assert all(a.t >= 40.0 for a in r if not a.due)
    def shape(r):
        due = [a for a in r if a.due]
        return (sorted(len(a.prompt) for a in due),
                sorted(a.max_new for a in due),
                np.round(sorted(np.diff([a.t for a in due])), 9).tolist())
    assert shape(runs[0])[:2] == shape(runs[1])[:2] == shape(runs[2])[:2]
    assert [a.t for a in runs[0]] != [a.t for a in runs[1]]


def test_chat_mix_lengths_and_shared_prefix():
    t = traffic.merged(spec.load("traffic", "chat-poisson"))
    arr = traffic.generate(t, 5, 40.0, 1.5, 152064)
    prefix = arr[0].prompt[:256]
    for a in arr:
        assert np.array_equal(a.prompt[:256], prefix)
        assert 256 + 16 <= len(a.prompt) <= 256 + 1536
        assert 16 <= a.max_new <= 512
        assert a.prompt.dtype == np.int32
    assert traffic.longest(t) == (256 + 1536, 512)


def test_closed_loop_sessions():
    t = traffic.merged(spec.load("traffic", "longctx-decode"))
    arr = traffic.generate(t, SEED, 40.0, None, 152064)
    assert [(len(a.prompt), a.max_new, a.t, a.due) for a in arr] == \
        [(16384, 8192, 0.0, True)] * 4
    assert len({a.prompt.tobytes() for a in arr}) == 4


def test_open_loop_needs_a_rate():
    t = traffic.merged(spec.load("traffic", "chat-poisson"))
    with pytest.raises(ValueError):
        traffic.generate(t, 1, 10.0, None, 100)
