"""Pallas TPU kernel: fused dequantize + online-softmax decode attention.

THE perf-critical op of the paper: during decode, attention over a long
context is bound by HBM reads of the KV cache.  This kernel streams the
*packed* 2-bit K / 1.5-bit V tiles (plus fp8 metadata) from HBM into VMEM,
dequantizes in-register, and runs flash-style online-softmax accumulation —
the bf16 cache never exists in HBM.  Per **live** token the packed planes
are ~8× smaller than an fp16 cache (197 TF / 819 GB/s v5e: decode roofline
is entirely the memory term), and block pruning makes bytes/step scale with
live tokens rather than capacity: a slot 2k tokens into a 128k-capacity
engine streams ~2k tokens of planes, not ~128k — so the ~8× reduction holds
for the ragged serving traffic the engine actually sees, not just for full
caches.

Block pruning (DESIGN.md §4): the caller passes per-slot packed block
bounds ``[lo, hi)`` (from ``segments.packed_block_bounds`` — lower bound
from the effective local window, upper bound from each slot's packed
frontier).  The bounds ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps can read
them: out-of-range grid steps re-request the nearest in-range block index
(Pallas elides the repeated DMA — same block, no new copy) while
``pl.when`` skips the dequant + flash math entirely.  A skipped block is
*exactly* a no-op — its mask is all-zero, so its flash contribution is
``exp(s - m) * 0`` — which makes the pruned triple bit-identical to the
unpruned one (asserted in tests/test_block_pruning.py).

Shapes (one grid program per (batch, kv-head); sequence is the sequential
grid axis so the accumulator scratch persists across KV tiles):

    q         (B, Hkv, Gq, D)      Gq = query heads per kv head (GQA)
    k planes  (B, Hkv, S, W_b)     packed uint8 + (B, Hkv, S, G) metadata
    v planes  likewise
    mask      (B, S) f32           1.0 for attendable tokens (validity ∧ local
                                   window — computed by the wrapper).  Per
                                   batch slot: ragged serving batches place
                                   each row's packed frontier independently.
    bounds    (B, 2) i32           per-slot live block range [lo, hi)

Returns the UNNORMALIZED flash triple (num, m, l) so the wrapper can
logsumexp-merge with the fp sliding-window/sink segments (ops.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ..core.quant import plane_layout
from ..core.policy import QuantPolicy
from ._compat import resolve_interpret
from .kv_quant import _decode_meta, _expand_groups

BLOCK_S = 256
_NEG = -1e30


def _unpack_block(packed, bits):
    """(T, Wb) uint8 -> (T, Wb * 8//bits) int32 codes in channel order: one
    shift-and-mask pass per code slot of the strided ``core.packing``
    layout, concatenated along lanes (uint8 widens through int32 — Mosaic
    has no direct uint8 <-> float cast)."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate([(p >> (i * bits)) & ((1 << bits) - 1)
                            for i in range(8 // bits)], axis=-1)


def _dequant_tile(refs, off, layout, fp8_meta):
    """Read one (BLOCK_S, D) tile from plane refs, dequantize to f32."""
    parts = []
    for pi, (start, width, bits, gs) in enumerate(layout):
        codes = _unpack_block(refs[off + 3 * pi][0, 0], bits).astype(jnp.float32)
        h = _decode_meta(refs[off + 3 * pi + 1][0, 0], fp8_meta)   # (BS, G)
        lo = _decode_meta(refs[off + 3 * pi + 2][0, 0], fp8_meta)
        parts.append(codes * _expand_groups(h, gs) + _expand_groups(lo, gs))
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]


def _kernel(bnd_ref, q_ref, mask_ref, *refs, layout_k, layout_v, fp8_meta,
            scale, softcap, hkv, n_sblocks):
    nk = 3 * len(layout_k)
    k_refs = refs[:nk]
    v_refs = refs[nk:nk + 3 * len(layout_v)]
    num_ref, m_ref, l_ref = refs[-6], refs[-5], refs[-4]
    acc, m_sc, l_sc = refs[-3], refs[-2], refs[-1]

    slot = pl.program_id(0) // hkv
    sblk = pl.program_id(1)
    lo_b = bnd_ref[slot, 0]
    hi_b = bnd_ref[slot, 1]

    @pl.when(sblk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    # dead block for this slot (below the window's reach or past the packed
    # frontier): its mask is all-zero, so its flash contribution would be
    # exactly zero — skip the dequant + matmul work entirely.  The BlockSpec
    # remap already re-requested the previous block's index, so no new HBM
    # bytes moved either.
    @pl.when((sblk >= lo_b) & (sblk < hi_b))
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (Gq, D)
        k = _dequant_tile(k_refs, 0, layout_k, fp8_meta)      # (BS, D)
        v = _dequant_tile(v_refs, 0, layout_v, fp8_meta)      # (BS, D)
        mask = mask_ref[0, 0]                                 # (1, BS) this slot

        s = jax.lax.dot_general(                              # (Gq, BS) = q k^T
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask > 0, s, _NEG)

        m_prev = m_sc[...]                                    # (Gq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # multiply by the mask so a partially-masked tile contributes exactly
        # zero weight on its dead lanes instead of exp(0)=1 per lane when
        # m_cur is still _NEG.
        p = jnp.exp(s - m_cur) * mask
        alpha = jnp.exp(m_prev - m_cur)                       # rescale old acc
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_sc[...] = m_cur

    @pl.when(sblk == n_sblocks - 1)
    def _finish():
        num_ref[0, 0] = acc[...]
        m_ref[0, 0] = m_sc[...]
        l_ref[0, 0] = l_sc[...]


def decode_attn_pallas(q: jnp.ndarray, k_qt: dict, v_qt: dict,
                       mask: jnp.ndarray, policy: QuantPolicy, head_dim: int,
                       scale: float, interpret: Optional[bool] = None,
                       block_s: int = BLOCK_S, softcap: float = 0.0,
                       block_bounds: Optional[jnp.ndarray] = None,
                       block_table: Optional[jnp.ndarray] = None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns flash triple (num (B,H,Gq,D), m (B,H,Gq,1), l (B,H,Gq,1)).

    k_qt/v_qt leaves have shape (B, S, Hkv, ...) (cache layout) — transposed
    here to (B, Hkv, S, ...) tile order.  ``mask``: (B, S) per-slot float
    validity ((S,) accepted and broadcast — uniform-length batches).
    ``softcap`` > 0 applies the gemma-style tanh logit cap in-kernel.

    ``block_bounds``: optional (B, 2) int32 per-slot live block range
    ``[lo, hi)`` over the ``block_s`` grid (``segments.packed_block_bounds``
    of the same mask).  Blocks outside a slot's range are neither fetched
    (index remap re-requests the previous block; Pallas elides the DMA) nor
    computed (``pl.when``) — work scales with live tokens, not capacity.
    None walks every block (the unpruned baseline).  When the bounds are
    concrete (eager callers), the sequence grid additionally shrinks to the
    batch's max ``hi``; under jit they are traced, the grid stays
    capacity-sized, and pruning rides entirely on the remap + skip.

    ``block_table`` (DESIGN.md §9): optional (B, NB) int32 per-slot
    logical->physical block map for the pooled layout, in which case
    k_qt/v_qt leaves are pool-major — (NP, BT, Hkv, ...) with BT ==
    ``block_s`` — and the logical sequence length is ``NB * BT``.  The
    table rides in as a second scalar-prefetch operand so the plane
    BlockSpec index maps gather ``tbl[slot, logical_block]`` — the
    PagedAttention-style remap — while the mask, bounds, and flash math
    all stay in logical coordinates.  Dead grid steps clamp onto a live
    logical block, hence a repeated *physical* id, so the DMA-eliding
    pruning behaviour carries over unchanged.  Table contents are data,
    not shape: tables growing/shrinking under ragged traffic never
    recompile.

    ``interpret=None`` resolves via ``kernels._compat.resolve_interpret``:
    compiled on TPU, interpreter elsewhere, ``REPRO_PALLAS_INTERPRET``
    overriding.
    """
    b, hkv, gq, d = q.shape
    pooled = block_table is not None
    if pooled:
        block_table = jnp.asarray(block_table, jnp.int32)
        bt = k_qt["codes_hi"].shape[1]
        assert block_s == bt, (
            f"pooled mode requires block_s == block_tokens, got "
            f"block_s={block_s} block_tokens={bt}")
        s_len = block_table.shape[1] * bt
    else:
        s_len = k_qt["codes_hi"].shape[1]
    assert s_len % block_s == 0, (s_len, block_s)
    interpret = resolve_interpret(interpret)
    gsz = min(policy.group_size, head_dim)
    layout_k = plane_layout(head_dim, policy.bits_k, gsz)
    layout_v = plane_layout(head_dim, policy.bits_v, gsz)
    n_sblocks = s_len // block_s

    if block_bounds is None:
        block_bounds = jnp.broadcast_to(
            jnp.asarray([0, n_sblocks], jnp.int32), (b, 2))
    block_bounds = jnp.asarray(block_bounds, jnp.int32)
    grid_s = n_sblocks
    if not isinstance(block_bounds, jax.core.Tracer):
        # concrete bounds (eager benchmarks/tests): shrink the sequence grid
        # to the live frontier across the batch — dead trailing steps do not
        # even enter the grid.  Traced bounds (the jitted serving path) keep
        # the static capacity grid; the remap + pl.when skip does the work.
        grid_s = max(1, min(n_sblocks, int(jnp.max(block_bounds[:, 1]))))

    def _tile(qt, name):
        return jnp.swapaxes(qt[name], 1, 2)  # (B, Hkv, S, W)

    def _blk(bh, s, bnd):
        """Remapped block index: clamp dead steps onto the nearest live
        block so Pallas sees a repeated request and elides the copy."""
        lo = bnd[bh // hkv, 0]
        hi1 = jnp.maximum(bnd[bh // hkv, 1] - 1, lo)
        return jnp.clip(s, lo, hi1)

    # Index maps: pooled mode prefetches TWO scalar operands (bounds, table),
    # so every map grows a trailing ``tbl`` argument.  Only the plane map
    # actually reads it — the q/mask/out maps and the logical-coordinate
    # `_blk` clamp are identical across layouts.
    if pooled:
        def _head_map(bh, s, bnd, tbl):
            return (bh // hkv, bh % hkv, 0, 0)

        def _mask_map(bh, s, bnd, tbl):
            return (bh // hkv, _blk(bh, s, bnd), 0, 0)

        def _plane_map(bh, s, bnd, tbl):
            return (tbl[bh // hkv, _blk(bh, s, bnd)], bh % hkv, 0, 0)
    else:
        def _head_map(bh, s, bnd):
            return (bh // hkv, bh % hkv, 0, 0)

        def _mask_map(bh, s, bnd):
            return (bh // hkv, _blk(bh, s, bnd), 0, 0)

        def _plane_map(bh, s, bnd):
            return (bh // hkv, bh % hkv, _blk(bh, s, bnd), 0)

    mask = jnp.asarray(mask, jnp.float32)
    if mask.ndim == 1:
        mask = jnp.broadcast_to(mask[None], (b, s_len))
    # one lane-dense (1, block_s) mask row per (slot, block): the kernel
    # broadcasts it over the query rows without a sublane->lane relayout
    ins = [q, mask.reshape(b, n_sblocks, 1, block_s)]
    in_specs = [
        pl.BlockSpec((1, 1, gq, d), _head_map),
        pl.BlockSpec((1, 1, 1, block_s), _mask_map),
    ]
    for qt, layout in ((k_qt, layout_k), (v_qt, layout_v)):
        for name, _ in zip(("hi", "lo"), layout):
            for part in ("codes", "scale", "zero"):
                arr = _tile(qt, f"{part}_{name}")
                ins.append(arr)
                w = arr.shape[-1]
                in_specs.append(pl.BlockSpec((1, 1, block_s, w), _plane_map))

    out_shape = [jax.ShapeDtypeStruct((b, hkv, gq, d), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, gq, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, gq, 1), jnp.float32)]
    out_specs = [
        pl.BlockSpec((1, 1, gq, d), _head_map),
        pl.BlockSpec((1, 1, gq, 1), _head_map),
        pl.BlockSpec((1, 1, gq, 1), _head_map),
    ]
    scratch = [pltpu.VMEM((gq, d), jnp.float32),
               pltpu.VMEM((gq, 1), jnp.float32),
               pltpu.VMEM((gq, 1), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if pooled else 1,
        grid=(b * hkv, grid_s),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kern = functools.partial(_kernel, layout_k=layout_k, layout_v=layout_v,
                             fp8_meta=policy.fp8_meta, scale=scale,
                             softcap=softcap, hkv=hkv, n_sblocks=grid_s)
    if pooled:
        base = kern

        def kern(bnd_ref, tbl_ref, *rest):
            # the table is consumed by the BlockSpec index maps; the kernel
            # body itself works in logical coordinates and never reads it.
            del tbl_ref
            return base(bnd_ref, *rest)

        scalars = (block_bounds, block_table)
    else:
        scalars = (block_bounds,)
    num, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*scalars, *ins)
    return num, m[..., 0:1], l[..., 0:1]
