"""Plain fp32 reference of the served decoder, and its fp8 control.

A straightforward forward pass in ``jax.numpy`` at
``default_matmul_precision("highest")``, written from the model's
equations and the SKVQ paper, importing nothing of the program.  It takes
the bf16 weights from the seed (:mod:`bench.weights`) and computes in fp32:

* pre-norm decoder: RMSNorm with gain ``1 + w``, q/k/v projections (+bias),
  rotate-half RoPE at absolute positions, grouped-query attention with scale
  ``head_dim ** -0.5``, SwiGLU MLP, final RMSNorm, head (tied or not);
* prompt positions attend to the prompt in full precision (the paper's
  full-precision prefill);
* a served position ``t`` attends to keys and values ``j`` quantized and
  dequantized (per token and KV head, groups of channels, min/max clipped,
  scale and zero rounded to fp8 E4M3) where ``n_sink <= j <= t - window``,
  and in full precision elsewhere: the sinks and the window.

Two steps are spelled out so the result does not depend on the device it
runs on: the E4M3 rounding is done in arithmetic (on a TPU, XLA drops a
``float32 -> float8 -> float32`` round trip of ``astype``), and the RoPE
angles' cosines and sines come from a float64 table made on the host (the
TPU's ``cos`` is off by up to 0.025 at angles of some 10^4 radians).

``precision="fp8"`` is the control: every matrix product takes its operands
rounded to E4M3 (weights per output column, activations per row, each
scaled to the format's range), the step one precision below the bf16 that
the configurations state.

It is computed layer by layer, in blocks of query rows, so it fits beside
nothing else on one chip at the timed sizes.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

E4M3 = jnp.float8_e4m3fn
E4M3_MAX = 448.0
Q_BLOCK = 256
ROW_BLOCK = 1024


def _planes(d: int, bits: float):
    split = {1.5: (2, 1), 3.0: (4, 2)}
    if bits not in split:
        return [(0, d, int(bits))]
    hi, lo = split[bits]
    d_hi = max(d // 2 - (d // 2) % 8, 8)
    return [(0, d_hi, hi), (d_hi, d - d_hi, lo)]


def _fp8(x):
    """Round fp32 to the nearest E4M3 value (ties to even, saturating):
    3 mantissa bits, exponents down to -6, subnormal steps of 2^-9."""
    x = jnp.clip(x, -E4M3_MAX, E4M3_MAX)
    _, e = jnp.frexp(x)
    step = jnp.maximum(e - 1, -6) - 3
    return jnp.ldexp(jnp.round(jnp.ldexp(x, -step)), step)


def _fp8_scaled(x, axis):
    """E4M3 rounding of ``x`` scaled so its largest entry along ``axis``
    meets the format's largest value (the control's operands)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return _fp8(x / s) * s


def fake_quant(x, bits: float, group_size: int, fp8_meta: bool = True):
    """Quantize and dequantize the last axis of ``x`` (fp32)."""
    parts = []
    for start, width, b in _planes(x.shape[-1], bits):
        gs = min(group_size, width)
        xg = x[..., start:start + width].reshape(
            x.shape[:-1] + (width // gs, gs))
        lo, hi = xg.min(-1), xg.max(-1)
        h = jnp.maximum((hi - lo) / (2 ** b - 1), 1e-8)
        if fp8_meta:
            h, lo = _fp8(h), _fp8(lo)
        else:
            h, lo = (h.astype(jnp.float16).astype(jnp.float32),
                     lo.astype(jnp.float16).astype(jnp.float32))
        q = jnp.clip(jnp.round((xg - lo[..., None]) / h[..., None]),
                     0, 2 ** b - 1)
        parts.append((q * h[..., None] + lo[..., None]).reshape(
            x.shape[:-1] + (width,)))
    return jnp.concatenate(parts, -1) if len(parts) > 1 else parts[0]


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def rope_table(n: int, head_dim: int, theta: float):
    """cos and sin ``(n, head_dim // 2)`` of the RoPE angles at positions
    ``0 .. n - 1``: the angle is rounded to fp32 as the model computes it,
    its cosine and sine taken in float64 on the host."""
    half = head_dim // 2
    freq = (float(theta) ** (-np.arange(half) / half)).astype(np.float32)
    ang = (np.arange(n, dtype=np.float32)[:, None] * freq).astype(np.float64)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cs):
    half = x.shape[-1] // 2
    cos, sin = cs[0][:, None], cs[1][:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, kq, vq, n_prompt, pol):
    """q (S, Hq, D), k/v/kq/vq (S, Hkv, D) -> (S, Hq, D), by query blocks."""
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


def _mm(x, w, prec):
    """``x @ w`` with fp32 operands, or, for the control, E4M3 ones."""
    w = _f32(w)
    if prec == "fp8":
        x, w = _fp8_scaled(x, -1), _fp8_scaled(w, 0)
    return x @ w


@functools.partial(jax.jit, static_argnames=("dims_t", "pol_t", "prec"))
def _layer(h, lw, cs, n_prompt, dims_t, pol_t, prec):
    dims, pol = dict(dims_t), dict(pol_t)
    s = h.shape[0]
    hq, hkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    eps = dims["rms_norm_eps"]
    a = lw["attn"]
    x = _rms(h, lw["norm1"]["w"], eps)
    q = _mm(x, a["wq"], prec)
    k = _mm(x, a["wk"], prec)
    v = _mm(x, a["wv"], prec)
    if "bq" in a:
        q, k, v = (q + a["bq"].astype(jnp.float32),
                   k + a["bk"].astype(jnp.float32),
                   v + a["bv"].astype(jnp.float32))
    q = _rope(q.reshape(s, hq, hd), cs)
    k = _rope(k.reshape(s, hkv, hd), cs)
    v = v.reshape(s, hkv, hd)
    if prec == "fp8":
        q, k, v = (_fp8_scaled(t, -1) for t in (q, k, v))
    gs = min(pol["group_size"], hd)
    kq = fake_quant(k, pol["bits_k"], gs, pol["fp8_meta"])
    vq = fake_quant(v, pol["bits_v"], gs, pol["fp8_meta"])
    o = _attend(q, k, v, kq, vq, n_prompt, pol).reshape(s, hq * hd)
    h = h + _mm(o, a["wo_attn"], prec)
    m = lw["mlp"]

    def mlp(xr):
        xn = _rms(xr, lw["norm2"]["w"], eps)
        return xr + _mm(jax.nn.silu(_mm(xn, m["wi_gate"], prec))
                        * _mm(xn, m["wi_up"], prec), m["wo"], prec)

    rb = min(ROW_BLOCK, s)
    return jax.lax.map(mlp, h.reshape(s // rb, rb, -1)).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("dims_t", "prec"))
def _logits(h, params, rows, dims_t, prec):
    dims = dict(dims_t)
    x = _rms(h[rows], params["final_norm"]["w"], dims["rms_norm_eps"])
    head = (params["embed"].T if dims["tie_word_embeddings"]
            else params["lm_head"])
    return _mm(x, head, prec)


def _tuple(d: Dict) -> Tuple:
    return tuple(sorted(d.items()))


def logits(params, dims: dict, pol: dict, prompt: np.ndarray,
           served: np.ndarray, pad_to: int = 0, pad_scored: int = 0,
           prec: str = "fp32"):
    """Logits ``(len(served), vocab)`` at the positions that chose
    ``served``: the prompt's last position and every served position but
    the last.  ``pad_to``/``pad_scored`` round the sequence and the scored
    rows up, so requests of a sample share one compiled program."""
    pol = {k: pol[k] for k in ("bits_k", "bits_v", "group_size", "window",
                               "n_sink", "fp8_meta")}
    tokens = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n, p = len(served), len(prompt)
    s = max(pad_to, len(tokens))
    s = -(-s // Q_BLOCK) * Q_BLOCK
    if s > ROW_BLOCK:
        s = -(-s // ROW_BLOCK) * ROW_BLOCK
    toks = np.zeros(s, np.int32)
    toks[:len(tokens)] = tokens
    d = max(pad_scored, n)
    rows = np.full(d, p - 1 + n - 1, np.int32)
    rows[:n] = p - 1 + np.arange(n)
    dims_t, pol_t = _tuple(dims), _tuple(pol)
    cs = rope_table(s, dims["head_dim"], dims["rope_theta"])
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(toks)])
        for i in range(dims["num_hidden_layers"]):
            lw = jax.tree.map(lambda x: x[i], params["layers"])
            h = _layer(h, lw, cs, jnp.int32(p), dims_t, pol_t, prec)
        out = _logits(h, params, jnp.asarray(rows), dims_t, prec)
    return out[:n]


def _pads(sample):
    return (max(len(p) + len(s) - 1 for p, s in sample),
            -(-max(len(s) for _, s in sample) // 64) * 64)


def gaps(params, dims: dict, pol: dict, sample: Sequence[Tuple]
         ) -> List[np.ndarray]:
    """Per request of ``sample`` (``(prompt, served)`` pairs), the gap at
    each served position by which the served token's reference logit lies
    below the reference's best."""
    pad, pad_n = _pads(sample)
    out = []
    for prompt, served in sample:
        served = np.asarray(served, np.int32)
        lg = logits(params, dims, pol, prompt, served, pad, pad_n)
        at = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
        out.append(np.asarray(lg.max(-1) - at))
    return out


def control_gaps(params, dims: dict, pol: dict, sample: Sequence[Tuple]
                 ) -> List[np.ndarray]:
    """The control's readings on the same prompts and served tokens: at
    each position, the gap below the fp32 reference's best of the token
    that the fp8 reference puts first."""
    pad, pad_n = _pads(sample)
    out = []
    for prompt, served in sample:
        served = np.asarray(served, np.int32)
        first = logits(params, dims, pol, prompt, served, pad, pad_n,
                       "fp8").argmax(-1)
        lg = logits(params, dims, pol, prompt, served, pad, pad_n)
        at = jnp.take_along_axis(lg, first[:, None], -1)[:, 0]
        out.append(np.asarray(lg.max(-1) - at))
    return out
