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

Two layouts, one flash body (DESIGN.md §4, §9):

**Striped** (planes ``(B, S, Hkv, W)``): one grid program per (slot,
kv-head), the sequence the sequential grid axis over ``block_s``-token
tiles fetched by BlockSpecs.  Per-slot packed block bounds ``[lo, hi)``
(``segments.packed_block_bounds``: lower bound from the effective local
window, upper bound from each slot's packed frontier) ride in via scalar
prefetch (``pltpu.PrefetchScalarGridSpec``) so the index maps can read
them: out-of-range grid steps re-request the nearest in-range block
(Pallas elides the repeated DMA) while ``pl.when`` skips the dequant +
flash math.

**Pooled** (planes pool-major ``(NP, BT, Hkv, W)``, a per-slot block
table of ``BT``-token pages): one grid program per slot, all kv heads in
it, the sequential grid axis over *compute blocks* of
``P = pages_per_block(BT, NB)`` pages (``BLOCK_S`` tokens at the serving
pool's 16-token pages).  The wrapper packs each page's planes, all heads,
into one lane-dense byte record (:func:`_page_records`: Mosaic copies only
slices whose minor dim is whole 128-lane rows, and the planes' are 1–32
bytes).  The records stay in HBM (``pl.ANY``); the kernel reads
``tbl[slot, page]`` from the scalar-prefetched table and issues one async
copy per page into a double-buffered VMEM scratch, starting block
``i+1``'s copies before it dequantizes block ``i``, and decodes the
metadata from integer bits.  Pruning is by not issuing copies: a compute
block wholly outside the slot's page bounds moves no bytes and runs no
math; inside a partly-live block, pages outside ``[lo, hi)`` copy the null
page 0 (finite data) under a zero mask.

A skipped block or page is *exactly* a no-op — its mask is zero, so its
flash contribution is ``exp(s - m) * 0`` — which makes the pruned triple
bit-identical to the unpruned one, and the pooled triple bit-identical to
the striped one at ``block_s == P * BT`` (same tiles, same merge order;
tests/test_block_pruning.py, tests/test_paged_decode.py).

Shapes:

    q         (B, Hkv, Gq, D)      Gq = query heads per kv head (GQA)
    k planes  packed uint8 codes + per-group metadata, striped or pooled
    v planes  likewise
    mask      (B, S) f32           1.0 for attendable tokens (validity ∧ local
                                   window — computed by the wrapper), in
                                   logical token coordinates.  Per batch
                                   slot: ragged serving batches place each
                                   row's packed frontier independently.
    bounds    (B, 2) i32           per-slot live block (pooled: page) range
                                   [lo, hi)

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


def pages_per_block(block_tokens: int, n_pages: int) -> int:
    """Pages one pooled compute block attends over: ``BLOCK_S`` tokens of
    ``block_tokens``-token pages, at least one, at most the table width."""
    return max(1, min(BLOCK_S // block_tokens, n_pages))


def _unpack_block(packed, bits):
    """(T, Wb) uint8 -> (T, Wb * 8//bits) int32 codes in channel order: one
    shift-and-mask pass per code slot of the strided ``core.packing``
    layout, concatenated along lanes (uint8 widens through int32 — Mosaic
    has no direct uint8 <-> float cast)."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate([(p >> (i * bits)) & ((1 << bits) - 1)
                            for i in range(8 // bits)], axis=-1)


def _dequant(planes, layout):
    """Per plane (codes (T, Wb), scale (T, G) f32, zero (T, G) f32) ->
    the (T, D) f32 tile."""
    parts = []
    for (codes, h, lo), (start, width, bits, gs) in zip(planes, layout):
        codes = _unpack_block(codes, bits).astype(jnp.float32)
        parts.append(codes * _expand_groups(h, gs) + _expand_groups(lo, gs))
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]


def _dequant_tile(refs, off, layout, fp8_meta):
    """Read one (BLOCK_S, D) tile from plane refs, dequantize to f32."""
    return _dequant([(refs[off + 3 * pi][0, 0],
                      _decode_meta(refs[off + 3 * pi + 1][0, 0], fp8_meta),
                      _decode_meta(refs[off + 3 * pi + 2][0, 0], fp8_meta))
                     for pi in range(len(layout))], layout)


def _flash_update(q, k, v, mask, acc, m_sc, l_sc, softcap):
    """Fold one (T, D) tile into the running (acc, m, l) of one kv head."""
    s = jax.lax.dot_general(                              # (Gq, T) = q k^T
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
        _flash_update(q, k, v, mask_ref[0, 0], acc, m_sc, l_sc, softcap)

    @pl.when(sblk == n_sblocks - 1)
    def _finish():
        num_ref[0, 0] = acc[...]
        m_ref[0, 0] = m_sc[...]
        l_ref[0, 0] = l_sc[...]


def _fp8_to_f32(b):
    """int32 E4M3 bit patterns (0..255) -> f32, exactly as
    ``core.fp8.decode_fp8``, from integer ops alone (no fp8 vector type)."""
    e = (b >> 3) & 15
    m = b & 7
    normal = jax.lax.bitcast_convert_type(
        ((b >> 7) << 31) | ((e + 120) << 23) | (m << 20), jnp.float32)
    sub = jnp.where(b >= 128, -1.0, 1.0) * m.astype(jnp.float32) * 2.0 ** -9
    val = jnp.where(e == 0, sub, normal)
    return jnp.where((b & 127) == 127, jnp.nan, val)


def _f16_to_f32(b):
    """int32 IEEE half bit patterns (0..65535) -> f32, exactly."""
    e = (b >> 10) & 31
    m = b & 1023
    normal = jax.lax.bitcast_convert_type(
        ((b >> 15) << 31) | (jnp.where(e == 31, 255, e + 112) << 23)
        | (m << 13), jnp.float32)
    sub = (jnp.where(b >= 32768, -1.0, 1.0) * m.astype(jnp.float32)
           * 2.0 ** -24)
    return jnp.where(e == 0, sub, normal)


def _page_records(k_qt, v_qt):
    """Pool planes (NP, BT, Hkv, W) -> one uint8 page record (NP, BT, L).

    Per token, the bytes of every array in kernel order (K planes first;
    per plane codes, scale, zero), each array's kv heads side by side; fp16
    metadata is two arrays, its low bytes and its high bytes.  ``L`` is
    padded to whole 128-lane rows, so one page of every plane and head is
    one lane-dense copy."""
    cols = []
    for qt in (k_qt, v_qt):
        for name in ("hi", "lo"):
            if f"codes_{name}" not in qt:
                continue
            for part in ("codes", "scale", "zero"):
                a = qt[f"{part}_{name}"]
                if a.dtype == jnp.uint8:
                    cols.append(a)
                else:
                    u = jax.lax.bitcast_convert_type(a, jnp.uint8)
                    cols += [u[..., 0], u[..., 1]]
    n_phys, bt = cols[0].shape[:2]
    rec = jnp.concatenate([c.reshape(n_phys, bt, -1) for c in cols], axis=-1)
    return jnp.pad(rec, ((0, 0), (0, 0), (0, -rec.shape[-1] % 128)))


def _record_tile(rec, h, hkv, layout, fp8_meta, base):
    """Head ``h``'s plane triples of ``layout`` ((codes, scale f32, zero
    f32) per plane) from the widened records (T, L) int32, reading arrays
    from lane ``base`` on; returns them and the lane after the last."""
    def take(w):
        nonlocal base
        out = rec[:, base + h * w:base + (h + 1) * w]
        base += hkv * w
        return out

    def meta(g):
        if fp8_meta:
            return _fp8_to_f32(take(g))
        low = take(g)
        return _f16_to_f32(low | (take(g) << 8))

    planes = []
    for (start, width, bits, gs) in layout:
        codes = take(width * bits // 8)
        scale = meta(width // gs)
        planes.append((codes, scale, meta(width // gs)))
    return planes, base


def _pooled_kernel(bnd_ref, tbl_ref, q_ref, mask_ref, rec_hbm, num_ref,
                   m_ref, l_ref, buf, sem, acc, m_sc, l_sc, *, layout_k,
                   layout_v, fp8_meta, scale, softcap, hkv, ppb,
                   n_pages, n_cblocks):
    slot = pl.program_id(0)
    cblk = pl.program_id(1)
    lo = bnd_ref[slot, 0]                   # live pages [lo, hi)
    hi = bnd_ref[slot, 1]
    first = lo // ppb                       # live compute blocks [first, end)
    end = jnp.where(hi > lo, (hi + ppb - 1) // ppb, first)
    live = (cblk >= first) & (cblk < end)

    def copy(slot_buf, p, phys):
        return pltpu.make_async_copy(rec_hbm.at[phys], buf.at[slot_buf, p],
                                     sem.at[slot_buf])

    def start(blk, slot_buf):
        for p in range(ppb):
            page = blk * ppb + p
            phys = jnp.where((page >= lo) & (page < hi),
                             tbl_ref[slot, jnp.minimum(page, n_pages - 1)], 0)
            copy(slot_buf, p, phys).start()

    @pl.when(cblk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(live & (cblk == first))
    def _fetch_first():
        start(cblk, 0)

    @pl.when(live & (cblk + 1 < end))
    def _prefetch_next():
        start(cblk + 1, jax.lax.rem(cblk + 1 - first, 2))

    @pl.when(live)
    def _block():
        slot_buf = jax.lax.rem(cblk - first, 2)
        for p in range(ppb):
            copy(slot_buf, p, 0).wait()
        rec = buf[slot_buf].astype(jnp.int32)           # (P, BT, L)
        rec = rec.reshape(-1, rec.shape[-1])            # (P*BT, L)
        mask = mask_ref[0, 0]                           # (1, P*BT)
        for h in range(hkv):
            k_planes, v_base = _record_tile(rec, h, hkv, layout_k, fp8_meta,
                                            0)
            v_planes, _ = _record_tile(rec, h, hkv, layout_v, fp8_meta,
                                       v_base)
            q = q_ref[0, h].astype(jnp.float32) * scale
            _flash_update(q, _dequant(k_planes, layout_k),
                          _dequant(v_planes, layout_v), mask, acc.at[h],
                          m_sc.at[h], l_sc.at[h], softcap)

    @pl.when(cblk == n_cblocks - 1)
    def _finish():
        num_ref[0] = acc[...]
        m_ref[0] = m_sc[...]
        l_ref[0] = l_sc[...]


def _pooled_attn(q, k_qt, v_qt, mask, block_bounds, block_table, layout_k,
                 layout_v, policy, scale, softcap, interpret):
    interpret = resolve_interpret(interpret)
    b, hkv, gq, d = q.shape
    bt = k_qt["codes_hi"].shape[1]
    n_pages = block_table.shape[1]
    ppb = pages_per_block(bt, n_pages)
    n_cblocks = -(-n_pages // ppb)
    tile = ppb * bt
    if block_bounds is None:
        block_bounds = jnp.broadcast_to(
            jnp.asarray([0, n_pages], jnp.int32), (b, 2))
    block_bounds = jnp.asarray(block_bounds, jnp.int32)

    def _head_map(s, c, bnd, tbl):
        return (s, 0, 0, 0)

    def _mask_map(s, c, bnd, tbl):
        """Dead steps re-request a live block's mask (the copy is elided)."""
        first = bnd[s, 0] // ppb
        last = jnp.maximum((bnd[s, 1] + ppb - 1) // ppb - 1, first)
        return (s, jnp.clip(c, first, last), 0, 0)

    # one lane-dense (1, P*BT) mask row per (slot, compute block); a ragged
    # last block is padded with dead (zero) lanes
    mask = jnp.pad(mask, ((0, 0), (0, n_cblocks * tile - mask.shape[1])))
    rec = _page_records(k_qt, v_qt)
    out_shape = [jax.ShapeDtypeStruct((b, hkv, gq, d), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, gq, 1), jnp.float32),
                 jax.ShapeDtypeStruct((b, hkv, gq, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((1, hkv, gq, d), _head_map),
                 pl.BlockSpec((1, hkv, gq, 1), _head_map),
                 pl.BlockSpec((1, hkv, gq, 1), _head_map)]
    kern = functools.partial(_pooled_kernel, layout_k=layout_k,
                             layout_v=layout_v, fp8_meta=policy.fp8_meta,
                             scale=scale, softcap=softcap, hkv=hkv,
                             ppb=ppb, n_pages=n_pages, n_cblocks=n_cblocks)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_cblocks),
            in_specs=[pl.BlockSpec((1, hkv, gq, d), _head_map),
                      pl.BlockSpec((1, 1, 1, tile), _mask_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2, ppb, *rec.shape[1:]), jnp.uint8),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((hkv, gq, d), jnp.float32),
                            pltpu.VMEM((hkv, gq, 1), jnp.float32),
                            pltpu.VMEM((hkv, gq, 1), jnp.float32)],
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_bounds, jnp.asarray(block_table, jnp.int32), q,
      mask.reshape(b, n_cblocks, 1, tile), rec)


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
    logical->physical page map for the pooled layout, in which case
    k_qt/v_qt leaves are pool-major — (NP, BT, Hkv, ...) with BT ==
    ``block_s``, the pool's page size — the logical sequence length is
    ``NB * BT``, and ``block_bounds`` count pages.  The kernel then walks
    compute blocks of :func:`pages_per_block` pages and fetches each live
    page itself (module docstring); the mask, bounds and flash math stay
    in logical coordinates, so the triple equals a striped run at
    ``block_s == P * BT`` bit for bit.  Table contents are data, not
    shape: tables growing/shrinking under ragged traffic never recompile.

    ``interpret=None`` resolves via ``kernels._compat.resolve_interpret``:
    compiled on TPU, interpreter elsewhere, ``REPRO_PALLAS_INTERPRET``
    overriding.
    """
    b, hkv, gq, d = q.shape
    gsz = min(policy.group_size, head_dim)
    layout_k = plane_layout(head_dim, policy.bits_k, gsz)
    layout_v = plane_layout(head_dim, policy.bits_v, gsz)
    mask = jnp.asarray(mask, jnp.float32)
    if mask.ndim == 1:
        mask = jnp.broadcast_to(mask[None], (b, mask.shape[0]))
    if block_table is not None:
        bt = k_qt["codes_hi"].shape[1]
        assert block_s == bt, (
            f"pooled mode requires block_s == block_tokens, got "
            f"block_s={block_s} block_tokens={bt}")
        return _pooled_attn(q, k_qt, v_qt, mask, block_bounds, block_table,
                            layout_k, layout_v, policy, scale, softcap,
                            interpret)

    interpret = resolve_interpret(interpret)
    s_len = k_qt["codes_hi"].shape[1]
    assert s_len % block_s == 0, (s_len, block_s)
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

    def _head_map(bh, s, bnd):
        return (bh // hkv, bh % hkv, 0, 0)

    def _mask_map(bh, s, bnd):
        return (bh // hkv, _blk(bh, s, bnd), 0, 0)

    def _plane_map(bh, s, bnd):
        return (bh // hkv, bh % hkv, _blk(bh, s, bnd), 0)

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
        num_scalar_prefetch=1,
        grid=(b * hkv, grid_s),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kern = functools.partial(_kernel, layout_k=layout_k, layout_v=layout_v,
                             fp8_meta=policy.fp8_meta, scale=scale,
                             softcap=softcap, hkv=hkv, n_sblocks=grid_s)
    num, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_bounds, *ins)
    return num, m[..., 0:1], l[..., 0:1]
