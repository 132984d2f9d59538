"""Public wrappers around the Pallas kernels.

:func:`pallas_decode_attention` is the "pallas" decode backend
(``repro.models.backends``; DESIGN.md §4): a drop-in replacement for the
pure-jnp reference path in ``repro.models.attention.decode_attention_skvq``.
The packed segment goes through the fused dequant+flash kernel; the (tiny)
fp sink/window segments (plus the pre-append extra token) run in plain jnp;
all partials merge by logsumexp.  Segment index math comes from
``repro.core.segments`` — the same source the reference path and the cache
container use, so the two backends share one layout contract.  (Prefill —
whole-prompt and chunked alike — never reads the packed planes: its
attention is full-precision per the paper's Sec. 3.2 workflow, DESIGN.md
§7; the kernel is decode-side only.)

:func:`make_kernel_quant_fn` routes the cache-side group quantize through the
fused pack kernel (``kv_quant_pallas``); it is bit-exact against
``repro.core.quant.quantize_groups`` so either quantizer can feed either
attention backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.policy import QuantPolicy
from ..core.quant import n_meta_groups, packed_nbytes
from ..core import segments as seg
from ..core.kv_cache import slot_lengths as kvc_slot_lengths
from .decode_attn import decode_attn_pallas, pages_per_block, BLOCK_S
from .kv_quant import kv_quant_pallas

# bit pattern of float8_e4m3fn(1.0): sign 0, exponent 0111 (bias 7), mantissa 0
_FP8_ONE = 0x38
_FAR = 2 ** 30  # position sentinel for padded slots (always masked out)


def _pad_to(x, s_to, axis=1, fill=0):
    pad = s_to - x.shape[axis]
    if pad <= 0:
        return x
    cfgp = [(0, 0)] * x.ndim
    cfgp[axis] = (0, pad)
    return jnp.pad(x, cfgp, constant_values=fill)


def _pad_planes(qt: dict, s_pad: int, fp8_meta: bool) -> dict:
    """Pad packed planes along the token axis to a block multiple.

    Scale planes are padded with the encoding of 1.0, NOT zero: a scale=0
    group is a degenerate quantization step that only stayed harmless because
    every padded slot also happened to be masked.  With a real nonzero scale
    the dequantized padding is ordinary finite data regardless of masking.
    """
    one = _FP8_ONE if fp8_meta else jnp.float16(1.0)
    return {k: _pad_to(v, s_pad, axis=1,
                       fill=(one if k.startswith("scale") else 0))
            for k, v in qt.items()}


def _block_pad(s_eff: int, block_s: int):
    """Kernel tile width + padded token count for an ``s_eff``-token packed
    view (shared by the wrapper and :func:`decode_block_report` so the
    pruning accounting uses the exact grid the kernel runs)."""
    bs = min(block_s, max(s_eff, 8))
    return bs, -(-s_eff // bs) * bs


def _packed_ok(j, lens, t_now, weff, policy: QuantPolicy, b: int):
    """Per-slot attendability over (padded) packed slots ``j`` — THE mask
    the kernel applies and the one the ``[lo, hi)`` bounds are reduced
    from.  Single source for :func:`pallas_decode_attention` and
    :func:`decode_block_report`: the CI pruning gate measures the same
    math the kernel prunes with."""
    pos_q, stored_q = seg.packed_segment(j, lens, policy.n_sink,
                                         policy.window)
    return seg.bcast_rows(seg.attend_ok(pos_q, stored_q, t_now, weff), b)


def quantize_tokens(x, policy: QuantPolicy, alpha=None, interpret=None):
    """(N, D) tokens -> packed QTensor via the fused Pallas kernel."""
    n, d = x.shape
    blk = min(128, n) if n % 128 else 128
    while n % blk:
        blk //= 2
    return kv_quant_pallas(x, policy.bits_k, min(policy.group_size, d),
                           alpha=alpha, fp8_meta=policy.fp8_meta,
                           interpret=interpret, block_t=max(blk, 1))


def make_kernel_quant_fn(interpret: Optional[bool] = None):
    """Build a ``quant_fn`` for ``kv_cache.prefill`` / ``decode_append``.

    Flattens the leading (batch, seq, head) axes to kernel rows, tiles the
    per-head clip factors to per-row factors, and calls the fused
    quantize+pack kernel.  Bit-exact vs ``quantize_groups`` (asserted in
    tests/test_backends.py), so caches built by either quantizer are
    interchangeable between backends.
    """
    def quant_fn(x, bits, group_size, alpha, fp8_meta):
        *lead, d = x.shape
        n = 1
        for s in lead:
            n *= s
        rows = x.reshape(n, d)
        a_rows = None
        if alpha is not None:
            g_total = n_meta_groups(d, bits, min(group_size, d))
            a_rows = jnp.broadcast_to(alpha, (*lead, g_total)).reshape(n, g_total)
        blk = min(128, n)
        while n % blk:
            blk -= 1
        qt = kv_quant_pallas(rows, bits, min(group_size, d), alpha=a_rows,
                             fp8_meta=fp8_meta, interpret=interpret,
                             block_t=blk)
        return {k: v.reshape(*lead, v.shape[-1]) for k, v in qt.items()}
    return quant_fn


def pallas_decode_attention(q, cache, policy: QuantPolicy, *, scale: float,
                            softcap: float = 0.0, window=None,
                            dtype=jnp.bfloat16, chunk: int = 0,
                            local_slice: int = 0, packed_override=None,
                            extra_kv=None, q_pos=None,
                            interpret: Optional[bool] = None,
                            block_s: int = BLOCK_S,
                            prune_blocks: bool = True):
    """Fused-kernel decode over the SKVQ cache.

    Interface mirrors the reference ``decode_attention_skvq`` (same cache
    dict, traced ``window`` scalar, ``local_slice``/``packed_override`` perf
    levers, pre-append ``extra_kv``/``q_pos``); GQA/MQA via the Gq axis.
    Per-slot aware: ``cache["length"]``/``q_pos`` may be ``(B,)`` — the
    kernel then takes a per-(slot, token) validity mask.  ``chunk`` is
    accepted for signature parity but ignored — the kernel always streams
    ``block_s``-token tiles with an online-softmax accumulator, so the
    dequantized cache never materializes.

    ``prune_blocks`` (DESIGN.md §4 "block pruning & bounds contract"): this
    wrapper — the host side of the kernel call — reduces the per-slot
    attendability mask to live block bounds ``[lo, hi)``
    (``segments.packed_block_bounds``: lower bound from the effective local
    window, upper bound from each slot's packed frontier) and scalar-
    prefetches them into the kernel, which neither fetches nor computes dead
    blocks.  Bit-identical to the unpruned walk — a dead block's flash
    contribution is exactly zero — so it defaults on; False keeps the
    capacity-proportional baseline (benchmarks compare the two).

    Pooled caches (DESIGN.md §9) take the fast path: the per-slot
    ``block_tbl`` scalar-prefetches into the kernel alongside the pruning
    bounds (in pages), and the kernel copies each live page of the table
    itself — no host-side gather, no recompiles as tables change.  Its
    tile is a compute block of ``decode_attn.pages_per_block`` pages
    (``BLOCK_S`` tokens at 16-token pages), so the flash merge order (hence
    bits) maps onto a striped run at ``block_s == P * block_tokens``; the
    ``block_s`` argument does not apply to it.  The ``local_slice``
    and ``packed_override`` levers pre-slice plane tensors, which has no
    pooled analogue — those calls fall back to the gathered striped view
    (``kv_cache.unpool_cache``), still bit-identical.

    ``interpret=None`` resolves compiled-on-TPU / interpreter-elsewhere
    (``REPRO_PALLAS_INTERPRET`` overriding; ``kernels._compat``).

    q: (B, 1, Hq, D) -> (B, 1, Hq, D).
    """
    pooled = "block_tbl" in cache
    if pooled and (packed_override is not None or local_slice):
        from ..core import kv_cache as kvc
        cache = kvc.unpool_cache(cache)
        pooled = False
    w, ns = policy.window, policy.n_sink
    b, _, hq, d = q.shape
    lens = kvc_slot_lengths(cache, b)
    t_now = lens - 1 if q_pos is None else jnp.broadcast_to(
        jnp.asarray(q_pos), (b,))
    weff = seg.effective_window(window)

    if policy.is_fp16:
        # fp16 baseline fallback: nothing is packed, so there is no fused
        # kernel to run — attend over the dense cache with the shared flash
        # partial (same math the reference backend uses).
        hkv = cache["k"].shape[2]
        qg = q.reshape(b, hkv, hq // hkv, d)
        pos = jnp.arange(cache["k"].shape[1])
        ok = seg.attend_ok(pos, pos[None, :] < lens[:, None], t_now, weff)
        part = seg.partial_attend(qg, cache["k"].astype(dtype),
                                  cache["v"].astype(dtype), ok, scale, softcap)
        return seg.finalize([part]).reshape(b, 1, hq, d).astype(q.dtype)

    hkv = (cache.get("win_k") if cache.get("win_k") is not None
           else cache["qk_codes_hi"]).shape[2]
    qg = q.reshape(b, hkv, hq // hkv, d)
    parts = []

    if pooled:
        bt = cache["qk_codes_hi"].shape[1]
        s_q = cache["block_tbl"].shape[-1] * bt
    else:
        s_q = cache["qk_codes_hi"].shape[1] if "qk_codes_hi" in cache else 0
    if pooled:
        # pooled fast path: planes stay pool-major; the kernel copies the
        # physical pages of the prefetched table itself.  The mask and the
        # page bounds run in logical coordinates exactly as striped.
        k_qt = {kk[3:]: vv for kk, vv in cache.items()
                if kk.startswith("qk_")}
        v_qt = {kk[3:]: vv for kk, vv in cache.items()
                if kk.startswith("qv_")}
        j = jnp.arange(s_q, dtype=jnp.int32)
        ok = _packed_ok(j, lens, t_now, weff, policy, b)       # (B, S_q)
        bounds = (seg.packed_block_bounds(ok, bt) if prune_blocks else None)
        num, m, l = decode_attn_pallas(qg, k_qt, v_qt, ok.astype(jnp.float32),
                                       policy, d, scale, interpret=interpret,
                                       block_s=bt, softcap=softcap,
                                       block_bounds=bounds,
                                       block_table=cache["block_tbl"])
        parts.append((num, m[..., 0], l[..., 0]))
    elif s_q > 0:
        qc = seg.quantized_count(lens, ns, w)  # (B,)
        if packed_override is not None:
            # pre-sliced (hoisted) local view: (k_qt, v_qt, j_positions)
            k_qt, v_qt, j = packed_override
        else:
            k_qt = {kk[3:]: vv for kk, vv in cache.items()
                    if kk.startswith("qk_")}
            v_qt = {kk[3:]: vv for kk, vv in cache.items()
                    if kk.startswith("qv_")}
            if local_slice and s_q > local_slice:
                # per-slot gather of each row's own last local_slice tokens
                start = jnp.clip(qc - local_slice, 0, s_q - local_slice)
                j = start[:, None] + jnp.arange(local_slice)     # (B, ls)
                tk = lambda a: jnp.take_along_axis(
                    a, j[:, :, None, None], axis=1)
                k_qt = {kk: tk(vv) for kk, vv in k_qt.items()}
                v_qt = {kk: tk(vv) for kk, vv in v_qt.items()}
            else:
                j = jnp.arange(k_qt["codes_hi"].shape[1])
        s_eff = k_qt["codes_hi"].shape[1]
        bs, s_pad = _block_pad(s_eff, block_s)
        k_qt = _pad_planes(k_qt, s_pad, policy.fp8_meta)
        v_qt = _pad_planes(v_qt, s_pad, policy.fp8_meta)
        j = jnp.asarray(j, jnp.int32)
        j = _pad_to(j, s_pad, axis=j.ndim - 1, fill=_FAR)
        ok = _packed_ok(j, lens, t_now, weff, policy, b)   # (B, S_pad)
        bounds = (seg.packed_block_bounds(ok, bs) if prune_blocks else None)
        num, m, l = decode_attn_pallas(qg, k_qt, v_qt, ok.astype(jnp.float32),
                                       policy, d, scale, interpret=interpret,
                                       block_s=bs, softcap=softcap,
                                       block_bounds=bounds)
        parts.append((num, m[..., 0], l[..., 0]))

    # fp segments: sinks + sliding-window ring (+ pre-append current token)
    ks, vs, pos, valid = [], [], [], []

    def push(p, stored):
        pos.append(seg.bcast_rows(p, b))
        valid.append(seg.bcast_rows(stored, b))

    if ns > 0 and "sink_k" in cache:
        ks.append(cache["sink_k"]); vs.append(cache["sink_v"])
        push(*seg.sink_segment(ns, lens))
    if w > 0 and "win_k" in cache:
        ks.append(cache["win_k"]); vs.append(cache["win_v"])
        push(*seg.window_segment(w, ns, lens))
    if extra_kv is not None:
        k1, v1, p1 = extra_kv
        ks.append(k1); vs.append(v1)
        push(jnp.asarray(p1).reshape(-1)[:, None], jnp.ones((1, 1), bool))
    if ks:
        kf = jnp.concatenate(ks, axis=1).astype(dtype)
        vf = jnp.concatenate(vs, axis=1).astype(dtype)
        ok = seg.attend_ok(jnp.concatenate(pos, axis=1),
                           jnp.concatenate(valid, axis=1), t_now, weff)
        parts.append(seg.partial_attend(qg, kf, vf, ok, scale, softcap))

    return seg.finalize(parts).reshape(b, 1, hq, d).astype(q.dtype)


def decode_block_report(cache, policy: QuantPolicy, head_dim: int, *,
                        window=None, q_pos=None, block_s: int = BLOCK_S):
    """Host-side pruning report for the default (non-sliced) packed walk.

    Computes the same per-slot attendability mask the wrapper feeds the
    kernel and reduces it to the pruning accounting the benchmarks track
    (DESIGN.md §4):

    ``bounds``      (B, 2) live block range [lo, hi) per slot
    ``visited``     (B,)   blocks in the live range (>= 1 per slot: the
                    blocks the pruned striped kernel DMAs)
    ``total``       int    capacity blocks the unpruned kernel walks
    ``bytes_per_block`` int packed-plane bytes one block moves (all kv heads)
    ``pages_per_block`` int blocks per kernel compute block: the pooled
                    kernel's ``P`` (its blocks are pool pages), 1 striped
    ``compute_blocks_visited`` (B,) compute blocks the kernel runs: live
                    ``P``-page blocks pooled (0 for an empty slot),
                    ``visited`` striped

    Estimated packed bytes/step = ``visited.sum() * bytes_per_block`` pruned
    vs ``B * total * bytes_per_block`` unpruned — the blocks-visited and
    bytes/step columns of the ragged-occupancy bench.
    """
    pooled = "block_tbl" in cache
    if pooled:
        # pooled layout (DESIGN.md §9): tile = pool block, logical capacity
        # from the table — planes are pool-major, not per-slot.
        s_q = cache["block_tbl"].shape[-1] * cache["qk_codes_hi"].shape[1]
    else:
        s_q = cache["qk_codes_hi"].shape[1] if "qk_codes_hi" in cache else 0
    lens = kvc_slot_lengths(cache)
    b = lens.shape[0]
    if s_q == 0 or policy.is_fp16:
        zeros = jnp.zeros((b,), jnp.int32)
        return {"bounds": jnp.zeros((b, 2), jnp.int32), "visited": zeros,
                "total": 0, "bytes_per_block": 0, "pages_per_block": 1,
                "compute_blocks_visited": zeros}
    t_now = lens - 1 if q_pos is None else jnp.broadcast_to(
        jnp.asarray(q_pos), (b,))
    weff = seg.effective_window(window)
    if pooled:
        bs, s_pad = cache["qk_codes_hi"].shape[1], s_q
    else:
        bs, s_pad = _block_pad(s_q, block_s)
    j = _pad_to(jnp.arange(s_q, dtype=jnp.int32), s_pad, axis=0, fill=_FAR)
    ok = _packed_ok(j, lens, t_now, weff, policy, b)
    bounds = seg.packed_block_bounds(ok, bs)
    hkv = cache["qk_codes_hi"].shape[2]
    gsz = min(policy.group_size, head_dim)
    per_tok = (packed_nbytes(head_dim, policy.bits_k, gsz,
                             policy.meta_dtype_bits) +
               packed_nbytes(head_dim, policy.bits_v, gsz,
                             policy.meta_dtype_bits))
    visited = seg.blocks_visited(bounds)
    ppb, compute_blocks = 1, visited
    if pooled:
        ppb = pages_per_block(bs, s_pad // bs)
        lo, hi = bounds[:, 0], bounds[:, 1]
        compute_blocks = jnp.where(hi > lo, -(-hi // ppb) - lo // ppb, 0)
    return {"bounds": bounds, "visited": visited, "total": s_pad // bs,
            "bytes_per_block": bs * hkv * per_tok, "pages_per_block": ppb,
            "compute_blocks_visited": compute_blocks}


@functools.partial(jax.jit, static_argnames=("policy", "head_dim", "scale",
                                             "window", "interpret", "block_s"))
def skvq_decode_attention(q, cache, policy: QuantPolicy, head_dim: int,
                          scale: float, window: int = 0,
                          interpret: Optional[bool] = None,
                          block_s: int = BLOCK_S):
    """Legacy jit'd entry point (pre-backend API).

    Prefer :func:`pallas_decode_attention` or the ``"pallas"`` backend in
    ``repro.models.backends``, which additionally thread softcap, GQA config
    and the pre-append decode protocol.
    """
    del head_dim  # derived from q
    return pallas_decode_attention(q, cache, policy, scale=scale,
                                   window=jnp.int32(window),
                                   dtype=jnp.float32, interpret=interpret,
                                   block_s=block_s)
