"""Pallas TPU kernel: fused clipped group-quantize + bit-pack of K/V tokens.

One grid step quantizes a (BLOCK_T, D) tile of tokens resident in VMEM:
per-group min/max -> clip by the calibrated alpha -> fp8-round scale/zero ->
codes -> in-register bit-pack (4×2-bit or 8×1-bit per byte).  The packed tile
plus metadata stream back to HBM; the bf16 tensor never returns to HBM, which
is the quantize-side half of SKVQ's bandwidth win.

Layout is plane-structured for fractional widths (e.g. V1.5 = 2-bit plane on
the first half of channels + 1-bit plane on the second; DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

from ..core.fp8 import decode_fp8, encode_fp8, quantize_meta
from ..core.quant import plane_layout
from ._compat import resolve_interpret

BLOCK_T = 128
_EPS = 1e-8


def _pack_block(codes, bits):
    """codes: (T, W) int32 values < 2**bits -> (T, W*bits//8) uint8 in the
    strided layout of ``core.packing`` (byte j holds channels j, j+Wb, ...):
    lane slices OR-ed together, so no lane interleave reaches Mosaic."""
    cpb = 8 // bits
    wb = codes.shape[1] // cpb
    out = codes[:, :wb]
    for i in range(1, cpb):
        out = out | (codes[:, i * wb:(i + 1) * wb] << (i * bits))
    return out.astype(jnp.uint8)


def _expand_groups(m, gs):
    """(T, G) per-group values -> (T, G*gs) per-channel values (groups are
    contiguous channel runs), built from lane broadcasts rather than a
    (T, G, gs) reshape, which Mosaic cannot lower."""
    t, g = m.shape
    return jnp.concatenate(
        [jnp.broadcast_to(m[:, i:i + 1], (t, gs)) for i in range(g)], axis=-1)


def _group_reduce(x, gs, fn):
    """(T, G*gs) -> (T, G): ``fn`` over each contiguous group of ``gs`` lanes."""
    return jnp.concatenate([fn(x[:, i:i + gs], axis=-1, keepdims=True)
                            for i in range(0, x.shape[1], gs)], axis=-1)


def _encode_meta(x, fp8_meta):
    """Storage bits of metadata already rounded by ``quantize_meta``."""
    if fp8_meta:
        return encode_fp8(x)
    return x.astype(jnp.float16)


def _decode_meta(x, fp8_meta):
    if fp8_meta:
        return decode_fp8(x)
    return x.astype(jnp.float32)


def _kernel(x_ref, alpha_ref, *out_refs, layout, fp8_meta):
    x = x_ref[...].astype(jnp.float32)          # (BT, D)
    g_off = 0
    for pi, (start, width, bits, gs) in enumerate(layout):
        xp = x[:, start:start + width]
        g = width // gs
        lo = _group_reduce(xp, gs, jnp.min)    # (BT, G)
        hi = _group_reduce(xp, gs, jnp.max)
        a = alpha_ref[:, g_off:g_off + g]      # (1, G) shared or (BT, G) rows
        lo = lo * a
        hi = hi * a
        h = jnp.maximum((hi - lo) / (2 ** bits - 1), _EPS)
        h = quantize_meta(h, fp8_meta)
        lo = quantize_meta(lo, fp8_meta)
        # f32 -> int32 -> uint8: Mosaic has no direct float <-> uint8 cast
        q = jnp.clip(jnp.round((xp - _expand_groups(lo, gs)) /
                               _expand_groups(h, gs)),
                     0, 2 ** bits - 1).astype(jnp.int32)
        codes_ref, scale_ref, zero_ref = (out_refs[3 * pi + j] for j in range(3))
        codes_ref[...] = _pack_block(q, bits)
        scale_ref[...] = _encode_meta(h, fp8_meta)
        zero_ref[...] = _encode_meta(lo, fp8_meta)
        g_off += g


def kv_quant_pallas(x: jnp.ndarray, bits: float, group_size: int,
                    alpha: Optional[jnp.ndarray] = None, fp8_meta: bool = True,
                    interpret: Optional[bool] = None, block_t: int = BLOCK_T):
    """x: (N, D) tokens -> QTensor dict matching repro.core.quant layout.

    N must divide by block_t (wrapper pads). ``alpha`` may be a scalar,
    (G_total,) shared clip factors, or (N, G_total) per-row factors (used by
    the serving path, where rows are (batch·head) tokens with per-head
    calibration).  ``interpret=None`` resolves via
    ``kernels._compat.resolve_interpret`` (compiled on TPU, interpreter
    elsewhere, ``REPRO_PALLAS_INTERPRET`` overriding); the interpreter run
    is the CPU correctness path, the compiled path targets TPU v5e VMEM
    tiles of (block_t, D).
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    assert n % block_t == 0, (n, block_t)
    layout = plane_layout(d, bits, group_size)
    g_total = sum(w // gs for (_, w, _, gs) in layout)
    if alpha is None:
        alpha = jnp.ones((g_total,), jnp.float32)
    alpha = alpha.astype(jnp.float32)
    if alpha.ndim < 2:  # shared factors: one (1, G) block reused per grid step
        alpha = jnp.broadcast_to(alpha, (g_total,)).reshape(1, g_total)
        alpha_spec = pl.BlockSpec((1, g_total), lambda i: (0, 0))
    else:               # per-row factors (serving path: per-head calibration)
        alpha = jnp.broadcast_to(alpha, (n, g_total))
        alpha_spec = pl.BlockSpec((block_t, g_total), lambda i: (i, 0))

    meta_dt = jnp.uint8 if fp8_meta else jnp.float16
    out_shapes, out_specs, names = [], [], []
    for name, (start, width, b, gs) in zip(("hi", "lo"), layout):
        g = width // gs
        out_shapes += [jax.ShapeDtypeStruct((n, width * b // 8), jnp.uint8),
                       jax.ShapeDtypeStruct((n, g), meta_dt),
                       jax.ShapeDtypeStruct((n, g), meta_dt)]
        out_specs += [pl.BlockSpec((block_t, width * b // 8), lambda i: (i, 0)),
                      pl.BlockSpec((block_t, g), lambda i: (i, 0)),
                      pl.BlockSpec((block_t, g), lambda i: (i, 0))]
        names += [f"codes_{name}", f"scale_{name}", f"zero_{name}"]

    outs = pl.pallas_call(
        functools.partial(_kernel, layout=layout, fp8_meta=fp8_meta),
        grid=(n // block_t,),
        in_specs=[pl.BlockSpec((block_t, d), lambda i: (i, 0)), alpha_spec],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(x, alpha)
    return dict(zip(names, outs))
