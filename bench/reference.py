"""Plain fp32 reference of the served decoder, and its fp8 control.

A straightforward forward pass in ``jax.numpy`` at
``default_matmul_precision("highest")``, written from the model's
equations and the SKVQ paper, importing nothing of the program.  It takes
the bf16 weights from the seed (:mod:`bench.weights`) and computes in fp32.
The equations of a model family's block and head, and its position tables,
live in ``bench/families/<family>.py`` (``layer``, ``head``, ``tables``);
this module holds what every family shares:

* RMSNorm with gain ``1 + w``, rotate-half RoPE from a table, and matrix
  products (:func:`rms`, :func:`rope`, :func:`mm`);
* SKVQ attention (:func:`attend`): prompt positions attend to the prompt
  in full precision (the paper's full-precision prefill); a served
  position ``t`` attends to keys and values ``j`` quantized and dequantized
  (per token and KV head, groups of channels, min/max clipped, scale and
  zero rounded to fp8 E4M3; :func:`fake_quant`) where
  ``n_sink <= j <= t - window``, and in full precision elsewhere: the sinks
  and the window; a layer with a sliding window of ``w`` sees only
  ``t - w < j <= t``;
* the walk over the layers and the scoring (:func:`logits`, :func:`gaps`,
  :func:`control_gaps`).

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

from . import spec

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


def fp8_scaled(x, axis):
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


def rms(x, w, eps):
    """RMSNorm of fp32 ``x`` over its last axis, with gain ``1 + w``."""
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


def rope(x, cs):
    """Rotate-half RoPE of ``x`` (S, H, D) by a table ``(cos, sin)``."""
    half = x.shape[-1] // 2
    cos, sin = cs[0][:, None], cs[1][:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(q, k, v, kq, vq, n_prompt, pol, window: int = 0):
    """q (S, Hq, D), k/v/kq/vq (S, Hkv, D) -> (S, Hq, D), by query blocks.
    ``window`` (a Python int): 0 attends causally over the whole sequence;
    ``w > 0`` lets the query at ``t`` see only keys ``t - w < j <= t``."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = d ** -0.5
    j = jnp.arange(s)
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, hkv, g, d)

    def block(args):
        i, qx = args
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        seen = j[None, :] <= t[:, None]
        if window:
            seen = seen & (j[None, :] > t[:, None] - window)
        s_fp = jnp.einsum("qhgd,khd->hgqk", qx, k) * scale

        def served(_):
            useq = ((t[:, None] >= n_prompt) & (j[None, :] >= pol["n_sink"])
                    & (j[None, :] <= t[:, None] - pol["window"]))
            s_q = jnp.einsum("qhgd,khd->hgqk", qx, kq) * scale
            sc = jnp.where(useq, s_q, s_fp)
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
            return (jnp.einsum("hgqk,khd->qhgd", p * useq, vq)
                    + jnp.einsum("hgqk,khd->qhgd", p * ~useq, v))

        def prompt(_):
            p = jax.nn.softmax(jnp.where(seen, s_fp, -jnp.inf), -1)
            return jnp.einsum("hgqk,khd->qhgd", p, v)

        return jax.lax.cond(t[-1] >= n_prompt, served, prompt, None)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, hq, d)


def mm(x, w, prec):
    """``x @ w`` with fp32 operands, or, for the control, E4M3 ones."""
    w = _f32(w)
    if prec == "fp8":
        x, w = fp8_scaled(x, -1), fp8_scaled(w, 0)
    return x @ w


@functools.partial(jax.jit,
                   static_argnames=("fam", "dims_t", "pol_t", "prec"))
def _layer(h, lw, i, tables, n_prompt, fam, dims_t, pol_t, prec):
    # ``i`` is traced, so one compiled program serves every layer; a family
    # whose layers differ tells them apart by ``i`` inside its ``layer``
    return fam.layer(h, lw, i, tables, n_prompt, dict(dims_t), dict(pol_t),
                     prec)


@functools.partial(jax.jit, static_argnames=("fam", "dims_t", "prec"))
def _head(h, params, rows, fam, dims_t, prec):
    return fam.head(h, params, rows, dict(dims_t), prec)


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
    fam = spec.family(dims["family"])
    dims_t, pol_t = _tuple(dims), _tuple(pol)
    tables = fam.tables(dims, s)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(toks)])
        for i in range(dims["num_hidden_layers"]):
            lw = jax.tree.map(lambda x: x[i], params["layers"])
            h = _layer(h, lw, jnp.int32(i), tables, jnp.int32(p), fam,
                       dims_t, pol_t, prec)
        out = _head(h, params, jnp.asarray(rows), fam, dims_t, prec)
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
