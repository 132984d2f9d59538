"""A configuration names its model family, found by name under
``bench/families``; a family added as files alone is reached by every
function the harness takes from it; and the dense family gives, bit for
bit, the weights, reference logits and cost counts it gave when its
equations lived in the shared modules (the literals below were recorded
then)."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs, model, reference, spec, weights

CONFIG = "qwen2-7b"


def _config():
    return spec.load("configs", CONFIG)


def _pol(config, dims):
    p = dict(config["cache_policy"])
    p["group_size"] = min(p["group_size"], dims["head_dim"])
    return p


def _digest(params) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf.astype(np.float32)).tobytes())
    return h.hexdigest()


# ---- the dense family, held to the bits it gave before it had a file ----

@pytest.mark.parametrize("seed,digest", [
    (7, "b6de9abd97d23e717b87f98bcc66ca2cdc0bec46d972e0c41058baea03a58d67"),
    (2 ** 31 + 101,
     "2f20b71f7042f33b304348e6d0905022449f6150a7657c80d85ba1de2ec0e734"),
])
def test_dense_weight_bytes_unchanged(seed, digest):
    d = model.dims(_config(), smoke=True)
    assert d["family"] == "dense"
    assert _digest(weights.make(d, seed)) == digest


LOGITS_SHA = {
    "fp32": "6adc29c5b296ddfe3219e3f89547217946348f92ae4ac1a6b49de8fb1efc6a50",
    "fp8": "2eb2f4df813ffdd6565a723debc2747e33f35fa17123eac85e28c49458ab7963",
}
# rows 0, 6, 12, 18 of the fp32 logits, first four entries
LOGITS_SLICE = [
    ["0x1.7fa8fe0000000p-2", "-0x1.b032400000000p-2", "0x1.9924440000000p-2",
     "-0x1.6315500000000p+0"],
    ["-0x1.bea9f00000000p-2", "-0x1.1d77cc0000000p-1", "0x1.9f55a80000000p+0",
     "-0x1.9101380000000p+0"],
    ["0x1.904a080000000p-1", "-0x1.7db7340000000p-1", "0x1.07262c0000000p+1",
     "-0x1.e4281c0000000p-1"],
    ["0x1.925c300000000p-3", "-0x1.0a93ec0000000p-3", "0x1.a17dd20000000p+0",
     "-0x1.00165e0000000p+1"],
]


def _prompt_and_tail(vocab):
    rng = np.random.default_rng(11)
    return (rng.integers(0, vocab, 60).astype(np.int32),
            rng.integers(0, vocab, 24).astype(np.int32))


@pytest.mark.parametrize("prec", ["fp32", "fp8"])
def test_dense_reference_logits_unchanged(prec):
    config = _config()
    d = model.dims(config, smoke=True)
    prompt, served = _prompt_and_tail(d["vocab_size"])
    lg = np.asarray(reference.logits(weights.make(d, 7), d, _pol(config, d),
                                     prompt, served, prec=prec))
    assert lg.shape == (24, d["vocab_size"]) and lg.dtype == np.float32
    assert hashlib.sha256(lg.tobytes()).hexdigest() == LOGITS_SHA[prec]
    if prec == "fp32":
        want = np.array([[float.fromhex(x) for x in row] for row in LOGITS_SLICE],
                        np.float32)
        assert np.array_equal(lg[::6, :4], want)


# (prompt, n0, n1) -> (decode_attn_bytes, decode_flops) at qwen2-7b's run sizes
COSTS = [
    ((16384, 0, 640), 19114475520, 3858475909120),
    ((16384, 77, 700), 18683221760, 3760267714560),
    ((16500, 3, 12), 265628160, 54082344960),
    ((40, 0, 1), 5376, 4356653056),
    ((10, 0, 5), 0, 21769216000),
    ((37, 0, 3), 5376, 13069357056),
]


@pytest.mark.parametrize("ctx,nbytes,flops", COSTS)
def test_dense_cost_counts_unchanged(ctx, nbytes, flops):
    config = _config()
    d = model.dims(config)
    assert d["num_hidden_layers"] == 7
    assert costs.decode_attn_bytes([ctx], d, _pol(config, d)) == nbytes
    assert costs.decode_flops([ctx], d) == flops


def test_dense_cost_counts_sum_over_slots():
    config = _config()
    d = model.dims(config)
    ctxs = [c for c, _, _ in COSTS]
    assert costs.decode_attn_bytes(ctxs, d, _pol(config, d)) == 38063336192
    assert costs.decode_flops(ctxs, d) == 7712021194752


def test_every_config_names_its_family():
    bm = spec.benchmark()
    for cfg in bm["configs"]:
        data = json.loads((spec.ROOT / cfg["file"]).read_text())
        fam = spec.family(data["family"])
        assert model.dims(data)["family"] == data["family"]
        assert fam is spec.family(data["family"])


def test_unknown_family_names_its_file(tmp_path):
    with pytest.raises(KeyError, match=r"families/no-such-family\.py"):
        spec.family("no-such-family")
    with pytest.raises(KeyError, match="family"):
        model.dims({k: v for k, v in _config().items() if k != "family"})
    with pytest.raises(ValueError):
        spec.family("../dense")


# ---- a family added as files alone ----

TOY = '''"""Toy family: the dense block, each layer's output scaled by a gain of
its own, and cost counts over a sliding window."""
import dataclasses

import jax.numpy as jnp

from bench import spec

dense = spec.family("dense")
CALLS = []


def dims(config, smoke=False):
    return dict(dense.dims(config, smoke), sliding_window=config["sliding_window"])


def arch(d):
    return dataclasses.replace(dense.arch(d), name="toy")


def shapes(d):
    out = dense.shapes(d)
    out["layers/out_gain"] = ((d["num_hidden_layers"], d["hidden_size"]), 0.1)
    return out


def tables(d, s):
    CALLS.append("tables")
    return dense.tables(d, s)


def layer(h, lw, i, tables, n_prompt, d, pol, prec):
    CALLS.append("layer")
    h = dense.layer(h, lw, i, tables, n_prompt, d, pol, prec)
    return h * (1.0 + lw["out_gain"].astype(jnp.float32))


def head(h, params, rows, d, prec):
    CALLS.append("head")
    return dense.head(h, params, rows, d, prec)


def weight_flops_per_token(d):
    return (dense.weight_flops_per_token(d)
            + d["num_hidden_layers"] * d["hidden_size"])


def attn_flops(d, length):
    return dense.attn_flops(d, min(length, d["sliding_window"]))


def attended_lengths(d, length):
    return [min(length, d["sliding_window"])] * d["num_hidden_layers"]
'''


def _add_family(root):
    bench = root / "bench"
    for sub in ("cells", "configs", "families", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "families" / "toy.py").write_text(TOY)
    for have in ("families/dense.py", "traffic/longctx-decode.json"):
        (bench / have).write_text((spec.BENCH / have).read_text())
    cfg = _config()
    del cfg["name"]
    cfg.update(family="toy", sliding_window=64)
    (bench / "configs" / "toy-model.json").write_text(json.dumps(cfg))
    (bench / "cells" / "toy-model.longctx-decode.json").write_text(json.dumps(
        {"config": "toy-model", "traffic": "longctx-decode", "chips": 1,
         "rate": None, "why": "added as files",
         "limits": {"mean_logit_gap": 0.02},
         "smoke_limits": {"mean_logit_gap": 0.22}}))
    return bench


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A bench root holding the dense and the toy family, and the
    harness's family lookup pointed at it (the sizes carry only the
    family's name)."""
    bench = _add_family(tmp_path)
    real = spec.family
    monkeypatch.setattr(spec, "family",
                        lambda name, root=bench: real(name, root))
    return bench


def test_new_family_as_files_alone(toy):
    fam = spec.family("toy")
    assert fam.__file__ == str((toy / "families" / "toy.py").resolve())
    c = spec.cell("toy-model.longctx-decode", root=toy)
    config = c["config"]
    assert config["family"] == "toy"

    d = model.dims(config, smoke=True)
    assert d["family"] == "toy" and d["sliding_window"] == 64
    cfg = model.arch(d)
    assert (cfg.name, cfg.n_layers) == ("toy", config["smoke"]["num_hidden_layers"])

    params = weights.make(d, 5)
    n, h = d["num_hidden_layers"], d["hidden_size"]
    assert params["layers"]["out_gain"].shape == (n, h)
    dense_d = model.dims(_config(), smoke=True)
    assert "out_gain" not in weights.make(dense_d, 5)["layers"]

    pol = _pol(config, d)
    prompt, served = _prompt_and_tail(d["vocab_size"])
    lg = reference.logits(params, d, pol, prompt, served)
    assert {"tables", "layer", "head"} <= set(fam.CALLS)
    assert not np.array_equal(
        np.asarray(lg), np.asarray(reference.logits(params, dense_d, pol,
                                                    prompt, served)))

    ctxs = [(1000, 0, 9), (30, 2, 5)]
    steps = list(costs.decode_steps(ctxs))
    assert costs.decode_flops(ctxs, d) == sum(
        fam.weight_flops_per_token(d) + fam.attn_flops(d, s) for s in steps)
    assert costs.decode_attn_bytes(ctxs, d, pol) == (
        costs.kv_bytes_per_token_layer(d, pol)
        * sum(costs.live_packed_tokens(n, pol) for s in steps
              for n in fam.attended_lengths(d, s)))
    assert costs.decode_flops(ctxs, d) != costs.decode_flops(ctxs, dense_d)
    assert costs.decode_attn_bytes(ctxs, d, pol) != \
        costs.decode_attn_bytes(ctxs, dense_d, pol)


def test_unknown_family_in_a_new_root(toy):
    with pytest.raises(KeyError, match=str(toy / "families" / "nope.py")):
        spec.family("nope")


NO_PROGRAM = """
import json, sys
import numpy as np
from bench import reference, spec, weights
config = spec.load("configs", "qwen2-7b")
fam = spec.family(config["family"])
d = fam.dims(config, smoke=True)
pol = dict(config["cache_policy"])
pol["group_size"] = min(pol["group_size"], d["head_dim"])
rng = np.random.default_rng(0)
sample = [(rng.integers(0, d["vocab_size"], 40), rng.integers(0, d["vocab_size"], 6))]
gaps = reference.gaps(weights.make(d, 3), d, pol, sample)
assert len(gaps) == 1 and gaps[0].shape == (6,)
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""


def test_family_and_reference_import_nothing_of_the_program():
    env = dict(os.environ, PYTHONPATH=str(spec.ROOT), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", NO_PROGRAM], cwd=spec.ROOT,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


# ---- the reference's attention with and without a sliding window ----

def _attend_before(q, k, v, kq, vq, n_prompt, pol):
    """``reference._attend`` as it was before it took a window."""
    Q_BLOCK = reference.Q_BLOCK
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = d ** -0.5
    j = jnp.arange(s)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, hkv, g, d)

    def block(args):
        i, qx = args
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        causal = j[None, :] <= t[:, None]
        s_fp = jnp.einsum("qhgd,khd->hgqk", qx, k) * scale

        def served(_):
            useq = ((t[:, None] >= n_prompt) & (j[None, :] >= pol["n_sink"])
                    & (j[None, :] <= t[:, None] - pol["window"]))
            s_q = jnp.einsum("qhgd,khd->hgqk", qx, kq) * scale
            sc = jnp.where(useq, s_q, s_fp)
            p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
            return (jnp.einsum("hgqk,khd->qhgd", p * useq, vq)
                    + jnp.einsum("hgqk,khd->qhgd", p * ~useq, v))

        def prompt(_):
            p = jax.nn.softmax(jnp.where(causal, s_fp, -jnp.inf), -1)
            return jnp.einsum("hgqk,khd->qhgd", p, v)

        return jax.lax.cond(t[-1] >= n_prompt, served, prompt, None)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, hq, d)


POL = {"n_sink": 3, "window": 6}
N_PROMPT = 300          # the first query block is all prompt, the second mixed


def _qkv(seed=0, s=2 * reference.Q_BLOCK, hq=4, hkv=2, d=16):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((s, hq, d)).astype(np.float32)
    k, v = (rng.standard_normal((s, hkv, d)).astype(np.float32)
            for _ in range(2))
    kq, vq = (x + 0.3 * rng.standard_normal(x.shape).astype(np.float32)
              for x in (k, v))
    return q, k, v, kq, vq


def _brute(q, k, v, kq, vq, n_prompt, pol, w):
    """Masked softmax, one query row and head at a time, in float64."""
    q, k, v, kq, vq = (np.asarray(x, np.float64) for x in (q, k, v, kq, vq))
    s, hq, d = q.shape
    g = hq // k.shape[1]
    out = np.zeros(q.shape)
    for t in range(s):
        j = np.arange(t + 1)
        if w:
            j = j[j > t - w]
        quant = ((t >= n_prompt) & (j >= pol["n_sink"])
                 & (j <= t - pol["window"]))[:, None]
        for h in range(hq):
            kk = np.where(quant, kq[j, h // g], k[j, h // g])
            vv = np.where(quant, vq[j, h // g], v[j, h // g])
            sc = kk @ q[t, h] * d ** -0.5
            p = np.exp(sc - sc.max())
            out[t, h] = (p / p.sum()) @ vv
    return out


@pytest.mark.parametrize("w", [0, 8, 40])
def test_attend_window_against_brute_force(w):
    args = _qkv()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference.attend(*map(jnp.asarray, args), N_PROMPT,
                                          POL, window=w))
    np.testing.assert_allclose(got, _brute(*args, N_PROMPT, POL, w),
                               rtol=1e-5, atol=1e-5)


def test_attend_without_window_bitwise_as_before():
    args = tuple(map(jnp.asarray, _qkv(seed=1)))
    with jax.default_matmul_precision("highest"):
        new = np.asarray(reference.attend(*args, N_PROMPT, POL))
        zero = np.asarray(reference.attend(*args, N_PROMPT, POL, window=0))
        old = np.asarray(_attend_before(*args, N_PROMPT, POL))
    assert np.array_equal(new, old) and np.array_equal(zero, old)
