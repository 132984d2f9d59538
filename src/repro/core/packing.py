"""Bit-packing of integer quantization codes along the last (channel) axis.

Codes are packed *strided*: with ``cpb = 8 // b`` codes per byte and
``Wb = N / cpb`` bytes, byte ``j`` holds channels ``j, j + Wb, j + 2*Wb, ...``
and channel ``j + i*Wb`` occupies bits ``[i*b, (i+1)*b)``.  Unpacking is then
``cpb`` shift-and-mask passes over the whole byte row, concatenated along the
channel axis — no lane interleave, which a Mosaic (TPU) kernel cannot lower.
SKVQ permutes channels anyway (:mod:`repro.core.reorder`), so this order is
internal to the cache.  Supported code widths are 1, 2, 4 and 8 bits (8 is the
identity).  Mixed widths (the paper's "1.5-bit" values) are handled one level
up (see :mod:`repro.core.quant`) by packing two planes — one per width — so the
kernels never see fractional widths.

All functions are shape-polymorphic over leading dims and jit-safe.
"""
from __future__ import annotations

import jax.numpy as jnp

SUPPORTED_BITS = (1, 2, 4, 8)


def codes_per_byte(bits: int) -> int:
    if bits not in SUPPORTED_BITS:
        raise ValueError(f"unsupported bit width {bits}; want one of {SUPPORTED_BITS}")
    return 8 // bits


def packed_width(n: int, bits: int) -> int:
    """Number of bytes needed to pack ``n`` codes of ``bits`` width."""
    cpb = codes_per_byte(bits)
    if n % cpb != 0:
        raise ValueError(f"channel count {n} not divisible by codes/byte {cpb}")
    return n // cpb


def pack(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack uint codes (< 2**bits) along the last axis into uint8.

    codes: (..., N) integer array with values in [0, 2**bits).
    returns: (..., N * bits / 8) uint8.
    """
    cpb = codes_per_byte(bits)
    if bits == 8:
        return codes.astype(jnp.uint8)
    *lead, n = codes.shape
    out_w = packed_width(n, bits)
    c = codes.astype(jnp.uint8).reshape(*lead, cpb, out_w)
    shifts = (jnp.arange(cpb, dtype=jnp.uint8) * bits)[:, None]
    return (c << shifts).sum(axis=-2, dtype=jnp.uint8)


def unpack_u8(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack`, staying in uint8 (keeps dequant intermediates
    1 byte/code — 4× less HBM traffic than int32 on the non-fused path)."""
    cpb = codes_per_byte(bits)
    if bits == 8:
        return packed
    *lead, w = packed.shape
    shifts = (jnp.arange(cpb, dtype=jnp.uint8) * bits)[:, None]
    mask = jnp.uint8((1 << bits) - 1)
    codes = (packed[..., None, :] >> shifts) & mask
    return codes.reshape(*lead, cpb * w)


def unpack(packed: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack`. Returns int32 codes in [0, 2**bits)."""
    return unpack_u8(packed, bits).astype(jnp.int32)
