"""Request-level serving engine: per-slot admission, ragged continuous batching.

The paper's deployment story is long-context *serving* — SKVQ exists so a 7b
model can hold million-token contexts and decode ~7× faster.  Real serving
traffic is request-shaped, not array-shaped: prompts arrive with different
lengths, budgets and sampling settings, and a finished request should free
its slot immediately.  This module is the front door for that workload:

* :class:`Request` — one generation job (prompt, max_new, temperature,
  eos_id, seed).
* :class:`Engine` — ``submit() -> StreamHandle``, then ``step()``/``run()``.
  ``batch_slots`` fixed decode lanes share one jitted scanned-decode
  executable; admission prefills each queued request (requests with equal
  prompt lengths batch together) and **inserts it into a free slot only**
  (``kv_cache.insert_slot``) — no other slot is touched, no cross-slot
  padding.  Retirement zeroes the slot (``kv_cache.reset_slot``) and the
  next queued request takes it at the next step.
* :class:`StreamHandle` — tokens stream into ``handle.tokens`` after every
  sync; ``handle.finished``/``finish_reason`` and wall-clock latency marks
  (submit/first-token/finish) ride along for percentile reporting.

The enabler underneath is the **per-slot cache length**: ``cache["length"]``
is ``(B,)``, so every segment mask, RoPE position and decode-append scatter
is per-row (``repro.core``), and slots at wildly different positions decode
in one batched step.

Decode itself is the scanned multi-token step of DESIGN.md §6: a jitted
``lax.scan`` over ``steps_per_sync`` decode steps with on-device per-slot
sampling (greedy or per-slot temperature via vmapped
``jax.random.categorical``) and per-slot EOS pinning — one host sync per
chunk, ONE compiled executable regardless of each request's ``max_new``
(hosts discard the surplus tail of a chunk).

:class:`ServeSession` remains as a thin compatibility shim: the lock-step
array API expressed as ``batch_slots`` equal requests on an :class:`Engine`
(greedy streams are bit-identical to the pre-engine behavior; asserted in
tests/test_backends.py and tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core import kv_cache as kvc
from ..core import segments as seg
from ..core.block_pool import BlockPool, HostSpillTier, prefix_block_keys
from ..core.policy import QuantPolicy, PolicySchedule, as_schedule
from ..models.config import ArchConfig
from ..models import backends as bk
from ..models import transformer as T
from .host_loop import HostLoop, TokenDelivery
from .tracing import span
from .warmup import ExecutableCache, avatar


# ------------------------------------------------------------------ sampling

def sample_token(logits, temperature: float, key) -> jnp.ndarray:
    """logits (B, 1, V) -> (B, 1) int32, entirely on device (shared temp;
    the per-slot path of DESIGN.md §6 is :func:`sample_per_slot`)."""
    if temperature <= 0:
        return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits[:, -1] / temperature, axis=-1)[:, None].astype(jnp.int32)


def sample_per_slot(logits, temps, keys) -> jnp.ndarray:
    """Per-slot sampling (DESIGN.md §6): logits (B, V), temps (B,),
    keys (B, 2) -> (B,) i32.

    Rows with ``temps <= 0`` take the greedy argmax; others draw from the
    temperature-scaled categorical with their own PRNG key, so co-scheduled
    requests never share randomness.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(key, row, t):
        return jax.random.categorical(key, row / jnp.maximum(t, 1e-6), axis=-1)

    samp = jax.vmap(one)(keys, logits.astype(jnp.float32), temps)
    return jnp.where(temps > 0, samp.astype(jnp.int32), greedy)


def _split_keys(keys):
    """(B, 2) PRNG keys -> (new_keys, subkeys), each (B, 2)."""
    sp = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return sp[:, 0], sp[:, 1]


# ------------------------------------------------------------- jitted pieces

def make_prefill_fn(cfg: ArchConfig, policy, max_len: int,
                    calib=None, dtype=None, backend=None) -> Callable:
    """Jitted whole-prompt prefill ``(params, batch) -> (logits, caches)``.

    One executable compiles per distinct prompt length — fine for uniform
    traffic, the thing DESIGN.md §7's chunked prefill bounds for ragged
    traffic."""
    @jax.jit
    def prefill(params, batch):
        return T.prefill_model(params, cfg, batch, policy, calib=calib,
                               max_len=max_len, dtype=dtype, backend=backend)
    return prefill


def make_decode_fn(cfg: ArchConfig, policy, calib=None,
                   dtype=None, backend=None) -> Callable:
    """Single-token decode step (kept for tooling/tests; the engine's hot
    path is :func:`make_multi_decode_fn` — DESIGN.md §6)."""
    @jax.jit
    def decode(params, token, caches):
        return T.decode_step(params, cfg, token, caches, policy, calib=calib,
                             dtype=dtype, backend=backend)
    return decode


def make_prefill_chunk_fn(cfg: ArchConfig, policy, calib=None,
                          dtype=None, backend=None) -> Callable:
    """Jitted chunked-prefill step (DESIGN.md §7).

    ``(params, tokens (B, C), state, t0, n_valid) -> (logits (B, 1, V),
    state)``.  ``t0`` and ``n_valid`` are traced scalars, so the compiled
    executable is shared by every chunk offset and every prompt length — the
    engine keeps one of these per chunk *bucket* size ``C`` and nothing
    else, which is what bounds the prefill compile-shape set.  The state
    (growing caches + fp workspace) is donated: chunks update the job's
    buffers in place instead of copying the workspace every call.
    """
    @functools.partial(jax.jit, donate_argnums=(2,))
    def chunk(params, tokens, state, t0, n_valid):
        return T.prefill_chunk(params, cfg, tokens, state, policy, t0,
                               n_valid, calib=calib, dtype=dtype,
                               backend=backend)
    return chunk


def default_chunk_buckets(prefill_chunk: int) -> tuple:
    """Power-of-2 bucket ladder ``(…, C/4, C/2, C)`` down to 8 (DESIGN.md §7).

    Every prompt runs as full-``C`` chunks plus one tail chunk padded up to
    the smallest bucket that fits, so the ladder trades a handful of
    compiled shapes for at most 2x padding waste on the tail.
    """
    out, b = [], prefill_chunk
    while b >= 8:
        out.append(b)
        b //= 2
    if not out:
        out = [prefill_chunk]
    return tuple(sorted(out))


def make_multi_decode_fn(cfg: ArchConfig, policy, n_tokens: int,
                         calib=None, dtype=None, backend=None) -> Callable:
    """Jitted ``lax.scan`` over ``n_tokens`` decode steps, per-slot
    everything (the scanned multi-token decode of DESIGN.md §6).

    Signature: ``(params, token (B,1), caches, keys (B,2), done (B,),
    temps (B,), eos (B,)) -> (tokens (B, n), token, caches, keys, done,
    live (B,))`` — one host sync per call.  ``temps`` selects greedy vs
    categorical per slot, ``eos`` is the per-slot EOS id (< 0 disables EOS
    handling for that slot).  Slots that hit their EOS keep stepping (the
    scan is shape-static) but their emitted tokens are pinned to their
    ``eos`` id; the host-side engine discards whatever tail of the chunk a
    request does not need, so ONE compiled executable serves every
    ``max_new``.

    ``live`` counts the tokens each slot emitted *before* pinning — the
    EOS token itself included.  It is what lets the async host loop
    (DESIGN.md §10) decide eos/length finishes from tiny per-slot scalars
    while the big ``tokens`` array stays on device for the background
    consumer thread to materialize.

    ``nan_inject`` (B,) bool is the per-slot NaN guard's test hook
    (DESIGN.md §11): rows flagged True have their logits poisoned with NaN
    before sampling, exercising exactly the non-finite-logits path a
    numerically misbehaving model would hit.  Either way, a slot whose
    logits go non-finite raises its ``bad`` flag (returned (B,) bool),
    samples from zeroed safe logits (so co-scheduled slots are unaffected
    and the executable never traps), stops counting ``live`` tokens, and
    pins ``done`` — the host quarantines it ("shed").  With ``nan_inject``
    all-False and finite logits every ``where`` is the identity, so the
    guarded scan is bit-identical to the unguarded one.
    """
    @jax.jit
    def multi(params, token, caches, keys, done, temps, eos, nan_inject):
        def step(carry, _):
            tok, caches, keys, done, bad, live = carry
            logits, caches = T.decode_step(params, cfg, tok, caches, policy,
                                           calib=calib, dtype=dtype,
                                           backend=backend)
            row = logits[:, -1]
            row = jnp.where(nan_inject[:, None],
                            jnp.full_like(row, jnp.nan), row)
            bad = bad | ~jnp.isfinite(
                row.astype(jnp.float32)).all(axis=-1)
            safe = jnp.where(bad[:, None], jnp.zeros_like(row), row)
            keys, subs = _split_keys(keys)
            nxt = sample_per_slot(safe, temps, subs)
            has = eos >= 0
            nxt = jnp.where(done & has, eos, nxt)
            live = live + jnp.where(done | bad, 0, 1).astype(jnp.int32)
            done = done | (has & (nxt == eos)) | bad
            return (nxt[:, None], caches, keys, done, bad, live), nxt

        live0 = jnp.zeros(token.shape[:1], jnp.int32)
        bad0 = jnp.zeros(token.shape[:1], bool)
        carry, toks = jax.lax.scan(
            step, (token, caches, keys, done, bad0, live0),
            None, length=n_tokens)
        token, caches, keys, done, bad, live = carry
        return (jnp.swapaxes(toks, 0, 1), token, caches, keys, done, bad,
                live)

    return multi


# ------------------------------------------------------------------ requests

class FinishReason:
    """Structured stream-termination taxonomy (DESIGN.md §11).

    Every stream the engine ever returns terminates with exactly one
    *terminal* reason: ``OK`` (generic success, used by tooling), ``EOS``
    (hit its eos id), ``LENGTH`` (hit max_new), ``DEADLINE`` (its
    ``Request.deadline_ms`` expired, queued or running), ``CANCELLED``
    (``StreamHandle.cancel()``), or ``SHED`` (the engine dropped it: NaN
    quarantine or watchdog abort).  ``PREEMPTED`` is an *event*, not a
    terminal state — a preempted request requeues for
    recompute-from-prompt and still ends in a terminal reason; the event
    is recorded in ``StreamHandle.events``.  The no-hung-streams chaos
    invariant is exactly ":meth:`valid` for every handle" (gated in tests
    and the CI chaos smoke).
    """
    OK = "ok"
    EOS = "eos"
    LENGTH = "length"
    DEADLINE = "deadline"
    CANCELLED = "cancelled"
    PREEMPTED = "preempted-requeued"
    SHED = "shed"
    TERMINAL = frozenset({OK, EOS, LENGTH, DEADLINE, CANCELLED, SHED})

    @classmethod
    def valid(cls, reason) -> bool:
        """True iff ``reason`` is a terminal FinishReason (DESIGN.md §11) —
        the per-stream form of the no-hung-streams invariant."""
        return reason in cls.TERMINAL


@dataclasses.dataclass
class Request:
    """One generation job (the front-door unit of DESIGN.md §6).

    prompt: 1-D int32 token ids; max_new: generation budget (the stream
    always ends at ``max_new`` tokens or at the first ``eos_id``);
    temperature <= 0 means greedy; seed feeds this request's private PRNG
    stream (independent of co-scheduled requests).

    ``deadline_ms`` / ``priority`` are the degradation-ladder knobs of
    DESIGN.md §11: a request whose deadline (measured on the engine clock
    from submit) expires — queued or mid-decode — terminates with
    FinishReason ``deadline`` and frees its blocks immediately; under pool
    pressure the scheduler preempts active requests of *strictly lower*
    priority (larger = more important) to admit the head of the queue.
    """
    prompt: Sequence[int]
    max_new: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    deadline_ms: Optional[float] = None
    priority: int = 0


class StreamHandle:
    """Live view of one submitted request (DESIGN.md §6).

    ``tokens`` grows after every engine sync; ``finished`` flips when the
    request hits EOS ("eos") or its max_new budget ("length").  Wall-clock
    marks (``submit_time``/``admit_time``/``first_token_time``/
    ``finish_time``) support per-request latency percentiles in the serving
    CLI and the open-loop SLA accounting of DESIGN.md §10.  Under the async
    host loop, ``tokens``/``finished`` are written by the background
    consumer thread — poll ``done`` or call ``Engine.drain()`` before
    reading a final stream; the scheduler-side ``_sched_*`` fields mirror
    the finish decision without waiting for delivery.
    """

    def __init__(self, request: Request, rid: int,
                 now: Optional[float] = None):
        self.request = request
        self.rid = rid
        self.tokens: List[int] = []
        self.text = ""                     # grows when a detokenizer is set
        self.finished = False
        self.finish_reason: Optional[str] = None
        # stamped by Engine.submit from the injectable engine clock
        # (DESIGN.md §11) — marks are compared pairwise, never as epochs
        self.submit_time = now
        self.admit_time: Optional[float] = None
        self.first_token_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        self.preempted = 0                 # times evicted + requeued (§11)
        self.events: List[str] = []        # non-terminal lifecycle events
        self._sched_consumed = 0           # tokens the scheduler committed
        self._sched_fin: Optional[str] = None  # scheduler's finish verdict
        self._cancel = False               # set by cancel(); acted on in step
        self._t_submit: Optional[float] = None  # engine-clock submit stamp
        self._replay_len = 0               # delivered tokens to replay (§11)
        self._replay_cursor = 0

    @property
    def done(self) -> bool:
        """True once the request hit EOS or its max_new budget."""
        return self.finished

    def cancel(self) -> None:
        """Request cooperative cancellation (DESIGN.md §11): the engine
        terminates the stream with FinishReason ``cancelled`` at its next
        scheduler tick — queued requests never occupy a slot, running ones
        free their pool blocks immediately.  Idempotent; a no-op once the
        stream already finished."""
        self._cancel = True

    def result(self) -> np.ndarray:
        """The generated tokens so far as a 1-D int32 array."""
        return np.asarray(self.tokens, np.int32)

    def _absorb_replay(self, tokens) -> List[int]:
        """Replay filter (DESIGN.md §11): after a preemption the request is
        recomputed from its prompt, so the device re-generates tokens that
        were already delivered.  Those must byte-match what the stream
        already holds — asserted here, on both backends — and are dropped;
        only the genuinely new suffix is returned for delivery."""
        if self._replay_cursor >= self._replay_len:
            return [int(t) for t in tokens]
        fresh: List[int] = []
        for t in tokens:
            t = int(t)
            if self._replay_cursor < self._replay_len:
                want = self.tokens[self._replay_cursor]
                if t != want:
                    raise RuntimeError(
                        f"preemption replay diverged for rid={self.rid}: "
                        f"position {self._replay_cursor} regenerated {t} "
                        f"but {want} was already delivered — "
                        f"recompute-from-prompt must be bit-identical "
                        f"(DESIGN.md §11)")
                self._replay_cursor += 1
            else:
                fresh.append(t)
        return fresh

    def __repr__(self):
        state = self.finish_reason if self.finished else "running"
        return (f"StreamHandle(rid={self.rid}, tokens={len(self.tokens)}, "
                f"{state})")


# -------------------------------------------------------------------- engine

@dataclasses.dataclass
class _PrefillJob:
    """Per-slot chunked-prefill progress (DESIGN.md §7 scheduler state).

    ``handle`` is being prefilled into reserved slot ``slot``; ``pos``
    tokens of its prompt are already in ``state`` (the chunked-prefill
    caches + fp workspace).  One job exists at a time; the engine advances
    it by at most one chunk per :meth:`Engine.step`.
    """
    handle: StreamHandle
    slot: int
    pos: int
    state: Dict


class Engine:
    """Continuous-batching serving engine over ``batch_slots`` decode lanes
    (DESIGN.md §6).

    ``submit`` validates and queues a :class:`Request` and returns its
    :class:`StreamHandle`; ``step`` retires finished slots, admits queued
    requests into free slots (equal-length prompts prefill as one batch; a
    freed slot is refilled without touching any other slot), and runs one
    scanned decode chunk of ``steps_per_sync`` tokens; ``run`` steps until
    the given handles (default: everything submitted) finish.

    ``policy`` is anything :func:`repro.core.policy.as_schedule` accepts —
    a bare :class:`QuantPolicy` (uniform, bit-identical to the pre-schedule
    engine), a :class:`PolicySchedule`, or an unbound preset like
    ``PolicySchedule.first_last_fp16(PAPER_POLICY, 2)`` (materialized
    against ``cfg.n_layers`` here).  The resolved schedule is
    ``engine.schedule``; its per-layer avg-bits/bytes ride along in
    :attr:`backend_info` (DESIGN.md §8).

    ``backend`` selects the decode-attention implementation (None = host
    default: pallas on TPU, reference elsewhere).  ``max_len`` is the
    per-slot cache capacity — every admitted request must satisfy
    ``len(prompt) + max_new <= max_len`` (checked at submit time).

    ``prefill_chunk`` (DESIGN.md §7) switches admission from whole-prompt
    prefill (one compiled executable per distinct prompt length) to
    **chunked prefill under a bounded compile-shape set**: prompts stream
    through the SKVQ cache in chunks of at most ``prefill_chunk`` tokens,
    each padded to a ``chunk_buckets`` size (default: the halving ladder
    ``default_chunk_buckets``), and the scheduler runs at most one chunk
    per ``step()`` interleaved with the decode chunk — a long prompt no
    longer head-of-line-blocks decoding, ragged traffic compiles at most
    ``len(chunk_buckets)`` prefill executables, and greedy streams stay
    bit-identical to the whole-prompt path.

    ``pool_blocks`` (DESIGN.md §9) switches the packed quantized planes
    from per-slot stripes to a shared **paged block pool** of that many
    physical ``pool_block_tokens``-token blocks per quantized band, with
    per-slot block tables and content-addressed prefix sharing: admission
    accounts in free blocks rather than free slots (a request is admitted
    when every band's pool can cover its prompt blocks — minus resident
    prefix hits — plus a decode reservation), identical prompt prefixes
    quantize once and share blocks copy-on-write, and decode is
    bit-identical to the striped layout on both backends.  Memory then
    scales with *live* tokens across the batch instead of
    ``batch_slots * max_len`` — the multiplicative partner to the 2-bit
    quantization and block pruning.  Requires the dense family and that
    every quantized band's packed capacity (``max_len - n_sink - window``)
    is a multiple of ``pool_block_tokens``.  ``stats()`` reports occupancy,
    prefix hit rate and resident bytes.

    ``pool_memory_bytes`` sizes the pool from a device-memory budget
    instead of a block count (DESIGN.md §10): ``pool_blocks`` is the
    budget floor-divided by the per-block bytes summed across quantized
    bands (every band's pool holds the same number of blocks), warning
    when the division leaves unusable remainder.  An explicit
    ``pool_blocks=`` always overrides the budget.

    ``async_host`` moves detokenization and stream delivery onto a
    background host thread (DESIGN.md §10): the scheduler decides
    eos/length finishes from per-slot counters synced off the decode scan,
    while the chunk's token array rides a bounded queue (``host_queue``
    items) to the consumer, which materializes it, appends to
    ``handle.tokens``, applies ``detokenize`` (when given) to
    ``handle.text``, and stamps delivery times.  Token streams are
    bit-identical to the synchronous loop; call :meth:`drain` (or
    :meth:`run`, which drains) before reading final streams.
    ``detokenize`` is honored in the synchronous loop too.

    ``warmup()`` (DESIGN.md §10) AOT-compiles the engine's bounded
    executable set and rehearses the host path before traffic arrives, so
    serving triggers zero new XLA compiles afterwards; an un-warmed engine
    compiles lazily exactly as before.
    """

    def __init__(self, params, cfg: ArchConfig, policy, batch_slots: int,
                 max_len: int, calib=None, seed: int = 0,
                 backend=None, steps_per_sync: int = 8, dtype=None,
                 prefill_chunk: Optional[int] = None, chunk_buckets=None,
                 pool_blocks: Optional[int] = None,
                 pool_block_tokens: int = 16,
                 pool_memory_bytes: Optional[int] = None,
                 async_host: bool = False, host_queue: int = 8,
                 detokenize: Optional[Callable] = None,
                 host_spill_bytes: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 faults=None, step_timeout_s: Optional[float] = None,
                 watchdog_max_trips: int = 2):
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if prefill_chunk is None and chunk_buckets is not None:
            raise ValueError("chunk_buckets requires prefill_chunk to be set")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, "
                                 f"got {prefill_chunk}")
            T._check_chunkable(cfg)  # fail at build time, not mid-serve
            if chunk_buckets is None:
                chunk_buckets = default_chunk_buckets(prefill_chunk)
            chunk_buckets = tuple(sorted(int(b) for b in chunk_buckets))
            if not chunk_buckets or chunk_buckets[-1] != prefill_chunk:
                raise ValueError(
                    f"chunk_buckets {chunk_buckets} must be non-empty and "
                    f"its largest entry must equal prefill_chunk "
                    f"({prefill_chunk})")
            if chunk_buckets[0] < 1:
                raise ValueError(f"chunk_buckets entries must be >= 1, "
                                 f"got {chunk_buckets}")
        self.schedule = as_schedule(policy, cfg.n_layers)
        # bare-policy callers see their policy back; schedule callers see
        # the materialized schedule (the canonical currency — DESIGN.md §8)
        self.policy = policy if isinstance(policy, QuantPolicy) \
            else self.schedule
        self.params, self.cfg = params, cfg
        self.max_len = max_len
        self.calib = calib
        self.backend = backend
        self.dtype = dtype
        self.seed = seed
        self.steps_per_sync = max(1, steps_per_sync)
        self.batch_slots = batch_slots
        self.prefill_chunk = prefill_chunk
        self.chunk_buckets = chunk_buckets
        self.prefill_fn = make_prefill_fn(cfg, self.schedule, max_len, calib,
                                          dtype=dtype, backend=backend)
        self._multi: Optional[Callable] = None  # lazily-built scanned step
        self._chunk_fns: Dict[int, Callable] = {}   # bucket -> jitted chunk
        self._prefill_job: Optional[_PrefillJob] = None
        self._chunk_state = None   # recycled prefill buffers between jobs
        self._zero_caches: Optional[Callable] = None

        # host-side per-slot state (tiny; round-trips exactly)
        b = batch_slots
        self._slot_handle: List[Optional[StreamHandle]] = [None] * b
        self._tok = np.zeros((b, 1), np.int32)
        self._done = np.ones((b,), bool)          # free slots ride as "done"
        self._keys = np.zeros((b, 2), np.uint32)
        self._temps = np.zeros((b,), np.float32)
        self._eos = np.full((b,), -1, np.int32)
        self._queue: List[StreamHandle] = []
        self._caches = None                        # allocated at 1st admission
        self._insert = None
        self._reset = None
        self._next_rid = 0
        self.n_completed = 0   # callers keep their own handles for stats

        # ----- injectable clock (DESIGN.md §11) -----
        # ALL engine time — latency marks, deadlines, watchdog and warmup
        # timing, host-loop delivery stamps — flows through this one slot,
        # so a virtual TickClock makes every run bit-reproducible.  Hoisted
        # above the executable cache and host loop, which share it.
        self._clock = clock if clock is not None else time.monotonic

        # ----- warmup executable cache + async host loop (DESIGN.md §10) ----
        self._exec = ExecutableCache(clock=self._clock)
        self._detok = detokenize
        self._host: Optional[HostLoop] = HostLoop(
            self._finish, detokenize, max_queue=host_queue,
            fault_hook=getattr(faults, "on_consume", None),
            clock=self._clock) \
            if async_host else None
        self._rehearse_s: Optional[float] = None
        self._counters = {"admitted": 0,
                          "pool_exhausted_stalls": 0, "preemptions": 0,
                          "spilled_blocks": 0, "restored_blocks": 0,
                          "deadline_misses": 0, "cancelled": 0, "shed": 0,
                          "nan_quarantines": 0, "watchdog_trips": 0,
                          "decode_syncs": 0, "decode_call_s": 0.0}

        # ----- degradation ladder + fault model (DESIGN.md §11) -----
        if step_timeout_s is not None and step_timeout_s <= 0:
            raise ValueError(f"step_timeout_s must be > 0, "
                             f"got {step_timeout_s}")
        if watchdog_max_trips < 1:
            raise ValueError(f"watchdog_max_trips must be >= 1, "
                             f"got {watchdog_max_trips}")
        self._faults = faults
        self.step_timeout_s = step_timeout_s
        self.watchdog_max_trips = int(watchdog_max_trips)
        self._watchdog_consec = 0
        self._wedged = False
        self._tick = 0
        self._tick_prefill = 0          # prompt tokens prefilled this tick
        self._last_stall_tick = -1      # one stall increment per tick (§11)
        self._admit_seq = 0             # activation order, for victim policy
        self._slot_seq = np.zeros((b,), np.int64)
        self._nan_inject = np.zeros((b,), bool)
        self._pending_restore: Dict[int, dict] = {}  # slot -> band restores
        self._spill: Optional[HostSpillTier] = (
            HostSpillTier(host_spill_bytes) if host_spill_bytes else None)
        self._spill_fns: Dict[tuple, Callable] = {}

        # ----- paged block pool (DESIGN.md §9) -----
        self.pool_blocks = pool_blocks
        self.pool_block_tokens = int(pool_block_tokens)
        self._pools: Dict[tuple, BlockPool] = {}
        self._pool_bands: List[tuple] = []  # (group, bkey, bs, be, pol, nb)
        self._pool_insert_fns: Dict[tuple, Callable] = {}
        self._pool_copy_fn: Optional[Callable] = None
        self._pending_insert: Dict[int, dict] = {}   # slot -> band miss pairs
        self._pending_register: Dict[int, dict] = {} # slot -> band (key, phys)
        self._hostlen = np.zeros((b,), np.int64)     # device length mirror
        self._stall_reason: Optional[str] = None
        if pool_blocks is None and pool_memory_bytes is not None:
            self.pool_blocks = self._size_pool_blocks(pool_memory_bytes)
        elif pool_blocks is not None and pool_memory_bytes is not None:
            warnings.warn(
                f"explicit pool_blocks={pool_blocks} overrides "
                f"pool_memory_bytes={pool_memory_bytes}", stacklevel=2)
        if self.pool_blocks is not None:
            self._init_pool()
        if self._spill is not None:
            if not self._pools:
                raise ValueError(
                    "host_spill_bytes requires the paged block pool "
                    "(set pool_blocks or pool_memory_bytes): only pooled "
                    "packed blocks spill to host RAM — DESIGN.md §11")
            for (group, bkey), pool in self._pools.items():
                pool.on_evict = functools.partial(
                    self._spill_block, group, bkey)

    def _enumerate_pool_bands(self) -> List[tuple]:
        """Quantized bands with a packed region to pool, with per-band
        block bytes: ``(group, bkey, bs, be, pol, nb, nbytes)`` rows
        (shared by :meth:`_init_pool` and the ``pool_memory_bytes`` sizing
        of DESIGN.md §10 — validation happens once, here)."""
        cfg, bt = self.cfg, self.pool_block_tokens
        if bt < 8:
            raise ValueError(f"pool_block_tokens must be >= 8 (the pallas "
                             f"sublane tile minimum), got {bt}")
        if cfg.family != "dense":
            raise ValueError(
                f"the paged KV block pool supports the dense family only "
                f"(the scan-family recurrence has no packed planes to "
                f"pool), got family={cfg.family!r}")
        nf = cfg.first_dense
        rows: List[tuple] = []
        for group, g0, g1 in (("dense", 0, nf), ("scan", nf, cfg.n_layers)):
            if g1 == g0:
                continue
            for bs, be, pol in self.schedule.bands(g0, g1):
                if pol.is_fp16:
                    continue      # fp16 bands have no packed planes: striped
                sq = max(0, self.max_len - pol.n_sink - pol.window)
                if sq == 0:
                    continue      # window+sinks cover max_len: striped
                if sq % bt:
                    raise ValueError(
                        f"band L{bs:03d} packed capacity {sq} (max_len="
                        f"{self.max_len} - n_sink={pol.n_sink} - window="
                        f"{pol.window}) is not a multiple of "
                        f"pool_block_tokens={bt}; choose max_len so every "
                        f"quantized band's packed region tiles into whole "
                        f"pool blocks")
                nbytes = kvc.pool_block_nbytes(
                    cfg.n_kv_heads, cfg.head_dim, pol, bt) * (be - bs)
                rows.append((group, f"L{bs:03d}", bs, be, pol,
                             sq // bt, nbytes))
        if not rows:
            raise ValueError(
                "pool_blocks was set but no band has a packed region to "
                "pool (every band is fp16 or its window+sinks cover "
                "max_len); drop pool_blocks to serve striped")
        return rows

    def _size_pool_blocks(self, budget: int) -> int:
        """Blocks per band affordable under a ``pool_memory_bytes`` budget
        (DESIGN.md §10): floor-divide by the summed per-band block bytes
        (every band's pool holds the same block count), warning when the
        remainder is non-zero."""
        if budget < 1:
            raise ValueError(f"pool_memory_bytes must be >= 1, got {budget}")
        per_block = sum(r[6] for r in self._enumerate_pool_bands())
        blocks = budget // per_block
        if blocks < 1:
            raise ValueError(
                f"pool_memory_bytes={budget} cannot fit a single pool "
                f"block: one block across all quantized bands costs "
                f"{per_block} bytes; raise the budget or coarsen the "
                f"policy")
        waste = budget - blocks * per_block
        if waste:
            warnings.warn(
                f"pool_memory_bytes={budget} rounds down to "
                f"pool_blocks={blocks} ({per_block} bytes/block across "
                f"bands; {waste} bytes of the budget unusable)",
                stacklevel=3)
        return int(blocks)

    def _init_pool(self):
        if self.pool_blocks < 1:
            raise ValueError(f"pool_blocks must be >= 1, "
                             f"got {self.pool_blocks}")
        for group, bkey, bs, be, pol, nb, nbytes in \
                self._enumerate_pool_bands():
            self._pools[(group, bkey)] = BlockPool(
                self.pool_blocks, self.batch_slots, nb, block_nbytes=nbytes)
            self._pool_bands.append((group, bkey, bs, be, pol, nb))

    # ------------------------------------------------------------ public API

    def now(self) -> float:
        """Current engine time from the injectable clock (DESIGN.md §11).

        External drivers (the load generator, metrics recorders) must
        anchor on this — not on ``time.time()`` — so their timestamps are
        comparable with the handle marks the engine stamps."""
        return self._clock()

    def submit(self, request: Request) -> StreamHandle:
        """Validate + queue a request; returns its stream handle
        (DESIGN.md §6).

        Raises ``ValueError`` at submit time for inputs that would otherwise
        fail deep inside jit with opaque shape errors; each message names
        the offending :class:`Request` field and the violated limit (see
        README.md Troubleshooting).
        """
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("Request.prompt must be a non-empty 1-D "
                             "sequence of token ids")
        if request.max_new < 1:
            raise ValueError(f"Request.max_new must be >= 1, "
                             f"got {request.max_new}")
        if request.deadline_ms is not None and request.deadline_ms <= 0:
            raise ValueError(f"Request.deadline_ms must be > 0 (or None "
                             f"for no deadline), got {request.deadline_ms}")
        if request.priority != int(request.priority):
            raise ValueError(f"Request.priority must be an integer, "
                             f"got {request.priority!r}")
        if prompt.size + request.max_new > self.max_len:
            raise ValueError(
                f"Request.prompt length ({prompt.size}) + Request.max_new "
                f"({request.max_new}) = {prompt.size + request.max_new} "
                f"exceeds the engine's per-slot cache capacity "
                f"max_len={self.max_len}; shorten the prompt, lower "
                f"max_new, or build the Engine with a larger max_len")
        for group, bkey, bs, be, pol, nb in self._pool_bands:
            need = self._eventual_blocks(prompt.size, request.max_new,
                                         pol, nb)
            if need > self.pool_blocks:
                st = self._pools[(group, bkey)].stats()
                raise ValueError(
                    f"Request needs up to {need} pool blocks in band "
                    f"{bkey} ({group}) but the engine's pool only has "
                    f"pool_blocks={self.pool_blocks} "
                    f"({st['used']} used, {st['free']} free, "
                    f"{st['reserved']} reserved); raise pool_blocks or "
                    f"shorten the request — it could never be admitted")
        request = dataclasses.replace(request, prompt=prompt)
        handle = StreamHandle(request, self._next_rid, now=self._clock())
        handle._t_submit = handle.submit_time  # deadline epoch (engine clock)
        self._next_rid += 1
        self._queue.append(handle)
        return handle

    def step(self) -> bool:
        """One scheduler tick: faults -> lifecycle -> retire -> admit ->
        [one prefill chunk] -> one decode chunk (DESIGN.md §6–§7, §11).

        In chunked-prefill mode at most ONE prefill chunk runs per tick,
        interleaved with the decode chunk for every already-active slot, so
        a long prompt never head-of-line-blocks decoding.  Returns False
        when there is nothing left to do; a non-empty queue that cannot
        admit (pool pressure, chaos-seized blocks) keeps returning True so
        ``run`` never abandons queued work — the no-deadlock contract of
        DESIGN.md §11.

        Each phase runs inside a host span (``engine.lifecycle``,
        ``engine.retire``, ``engine.admit``, ``engine.prefill_chunk``,
        ``engine.cow``, ``engine.flush_tables``, ``engine.decode.dispatch``,
        ``engine.decode.wait``, ``engine.deliver``), all inside
        ``engine.step``, whose metadata is :meth:`gauges` at the tick's end
        plus ``tick`` and ``prefill_tokens`` (DESIGN.md §10)."""
        self._tick += 1
        self._tick_prefill = 0
        with span("engine.step", self._step_counters):
            return self._step()

    def _step_counters(self) -> dict:
        return dict(self.gauges(), tick=self._tick,
                    prefill_tokens=self._tick_prefill)

    def _step(self) -> bool:
        tick = getattr(self._clock, "tick", None)
        if callable(tick):
            tick()                       # deterministic virtual clocks
        if self._faults is not None:
            self._faults.on_tick(self)
        with span("engine.lifecycle"):
            self._lifecycle()
        with span("engine.retire"):
            self._retire()
        with span("engine.admit"):
            self._admit()
        self._prefill_tick()
        active = [i for i in range(self.batch_slots)
                  if self._slot_handle[i] is not None]
        if not active:
            return self._prefill_job is not None or bool(self._queue)
        # a request can finish at admission (max_new=1 or instant EOS) —
        # only spin the decode chunk when someone still needs tokens
        if any(not self._h_done(self._slot_handle[i]) for i in active):
            self._decode_chunk()
            if self._wedged:
                self._shed_all()          # watchdog abort: terminate clean
                return False
        with span("engine.retire"):
            self._retire()
        return True

    def run(self, handles: Optional[List[StreamHandle]] = None) -> None:
        """Step until the given handles (default: all submitted) finish,
        then drain the async host loop so every returned stream is final
        (DESIGN.md §6, §10)."""
        def pending():
            if handles is not None:
                return any(not self._h_done(h) for h in handles)
            return (bool(self._queue) or self._prefill_job is not None
                    or any(h is not None for h in self._slot_handle))

        while pending():
            if not self.step():
                break
        self.drain()

    def drain(self) -> None:
        """Block until the async host loop has delivered every enqueued
        chunk (no-op for the synchronous engine) — the graceful-drain
        contract of DESIGN.md §10."""
        if self._host is not None:
            self._host.drain()

    def close(self, drain: bool = True) -> None:
        """Shut down the async host loop thread, draining first by default
        (DESIGN.md §10).  The engine stays usable: the next async delivery
        restarts the thread."""
        if self._host is not None:
            self._host.close(drain=drain)

    @property
    def queue_depth(self) -> int:
        """Requests admitted yet (DESIGN.md §10 metrics gauge)."""
        return len(self._queue)

    @property
    def active_slots(self) -> int:
        """Decode lanes currently occupied (DESIGN.md §10 metrics gauge)."""
        return sum(h is not None for h in self._slot_handle)

    def gauges(self) -> dict:
        """Scheduler and pool gauges at this instant (DESIGN.md §10):
        queued requests, occupied decode lanes, undelivered host-loop
        items, and the paged pool's used, reserved and total blocks summed
        over bands (all 0 for a striped engine).  The ``engine.step`` span
        carries them as metadata; ``MetricsRecorder.on_step`` samples
        them."""
        pools = self._pools.values()
        return {"queue_depth": len(self._queue),
                "active_slots": self.active_slots,
                "host_queue_depth": (self._host.queue_depth
                                     if self._host is not None else 0),
                "pool_used": sum(p.used() for p in pools),
                "pool_reserved": sum(p.reserved() for p in pools),
                "pool_blocks": sum(p.n_blocks for p in pools)}

    # ------------------------------------------------- warmup (DESIGN.md §10)

    def warmup(self, prompt_lens: Optional[Sequence[int]] = None,
               rehearse: bool = True) -> dict:
        """AOT-compile the engine's bounded executable set before traffic
        (DESIGN.md §10) and return :meth:`warmup_report`.

        Enumerates every jitted function the steady state can reach — the
        scanned decode step, one chunked-prefill executable per
        ``chunk_buckets`` entry (plus slot insert / reset / chunk-state
        zeroing), and the pool's block-insert / CoW-copy executables per
        band — lowers each against ``jax.ShapeDtypeStruct`` avatars (no
        buffers allocated beyond the engine cache itself, which warmup
        allocates exactly as first admission would), compiles, and stores
        the executables in the shape-keyed cache that serve-time call
        sites dispatch through.  In whole-prompt mode, ``prompt_lens``
        lists the batch-of-1 prompt lengths to pre-compile (chunked mode
        ignores it: the bucket ladder is the compile-shape set).

        ``rehearse`` then pushes one throwaway request per chunk bucket
        through the real scheduler (restoring all counters afterwards) to
        warm the *eager* host-path ops (admission sampling, key folding,
        table broadcasts) that AOT lowering cannot reach — after that, a
        mixed ragged workload triggers zero new XLA compiles (asserted
        with the jax compile counter in tests/test_serving_harness.py and
        gated in CI smoke).
        """
        params_av = avatar(self.params)
        dtype = self.dtype or self.params["embed"].dtype
        plen0 = min(8, self.max_len)
        # cache template: the structure prefill returns, batch-of-1 —
        # eval_shape is abstract, so nothing compiles or allocates here
        template = jax.eval_shape(
            self.prefill_fn, params_av,
            {"tokens": jax.ShapeDtypeStruct((1, plen0), jnp.int32)})[1]
        if self._caches is None:
            self._caches = (self._alloc_pooled() if self._pools
                            else self._alloc_like(template))
        cache_av = avatar(self._caches)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        b = self.batch_slots

        self._exec.warm(
            "multi", self._multi_fn(), params_av,
            jax.ShapeDtypeStruct((b, 1), jnp.int32), cache_av,
            jax.ShapeDtypeStruct((b, 2), jnp.uint32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_))
        self._exec.warm("insert", self._insert_fn(), cache_av, template,
                        i32, i32)
        self._exec.warm("reset", self._reset_fn(), cache_av, i32)
        if self.prefill_chunk is not None:
            state_av = jax.eval_shape(functools.partial(
                T.prefill_chunk_init, self.cfg, self.schedule, self.max_len,
                self.max_len, batch=1, dtype=dtype))
            for bucket in self.chunk_buckets:
                self._exec.warm(
                    f"chunk_{bucket}", self._chunk_fn(bucket), params_av,
                    jax.ShapeDtypeStruct((1, bucket), jnp.int32), state_av,
                    i32, i32)
            self._exec.warm("zero_caches", self._zero_fn(),
                            avatar(state_av["caches"]))
        elif prompt_lens:
            for plen in prompt_lens:
                self._exec.warm(
                    "prefill", self.prefill_fn, params_av,
                    {"tokens": jax.ShapeDtypeStruct((1, int(plen)),
                                                    jnp.int32)})
        for group, bkey, bs, be, pol, nb in self._pool_bands:
            band_av = avatar(self._band_cache_ref(group, bkey))
            src_av = self._band_cache_src(template, group, bkey)
            self._exec.warm(
                f"pool_insert:{group}:{bkey}",
                self._pool_insert_fn(group, bkey), band_av, src_av,
                jax.ShapeDtypeStruct((nb, 2), jnp.int32), i32)
            self._exec.warm(
                "pool_copy", self._pool_copy(), band_av,
                jax.ShapeDtypeStruct((self._cow_cap(), 2), jnp.int32))
            if self._spill is not None:
                # spill read/restore executables (§11): warmed so host-tier
                # traffic never triggers a post-warmup compile
                blk_av = jax.eval_shape(
                    functools.partial(kvc.pool_read_block, pool_axis=1),
                    band_av, jax.ShapeDtypeStruct((), jnp.int32))
                self._exec.warm(f"spill_read:{group}:{bkey}",
                                self._spill_read_fn(group, bkey),
                                band_av, i32)
                self._exec.warm(f"spill_write:{group}:{bkey}",
                                self._spill_write_fn(group, bkey),
                                band_av, blk_av, i32)
        if rehearse:
            t0 = self._clock()
            faults, self._faults = self._faults, None   # no chaos in warmup
            try:
                self._rehearse()
            finally:
                self._faults = faults
            self._rehearse_s = self._clock() - t0
        self._exec.warmed = True
        return self.warmup_report()

    def warmup_report(self) -> dict:
        """Warmup accounting (DESIGN.md §10): executables compiled, AOT
        compile seconds, rehearsal seconds, and ``post_warmup_compiles`` —
        the count of cold compiles that hit serving traffic after
        :meth:`warmup`, whose contract is that it stays 0 (CI-gated)."""
        out = self._exec.report()
        out["rehearse_s"] = self._rehearse_s
        return out

    def _rehearse(self):
        """Run one tiny scripted request per compile family through the
        real scheduler, then restore every counter — warms eager host-path
        ops that AOT lowering can't reach (DESIGN.md §10)."""
        if self.chunk_buckets is not None:
            lens = [bkt for bkt in self.chunk_buckets
                    if bkt + 2 <= self.max_len]
        else:
            lens = [p for p in (min(8, self.max_len - 2),) if p >= 1]
        handles = []
        for i, plen in enumerate(lens):
            prompt = (np.arange(plen, dtype=np.int32) % 17) + 1
            try:
                handles.append(self.submit(Request(
                    prompt=prompt, max_new=2, seed=0x7FFF0000 + i)))
            except ValueError:
                continue           # e.g. tight pools: skip, smaller lens warm
        if handles:
            self.run(handles)
        self.n_completed = 0
        self._next_rid = 0
        self._stall_reason = None
        self._tick = 0
        self._last_stall_tick = -1
        self._watchdog_consec = 0
        self._wedged = False
        if self._spill is not None:          # rehearsal spills don't count
            self._spill = HostSpillTier(self._spill.budget_bytes)
        for k, v in self._counters.items():
            self._counters[k] = type(v)()
        for pool in self._pools.values():
            pool.hits = pool.misses = pool.cow_copies = 0
            pool.peak_used = pool.used()
        if self._host is not None:
            self._host.enqueued = self._host.delivered = 0
            self._host.backpressure_waits = 0
            self._host.backpressure_s = 0.0
            self._host.max_depth = 0

    @property
    def backend_info(self) -> dict:
        """Resolved decode-backend facts (DESIGN.md §4) + the policy
        schedule's accounting (DESIGN.md §8): backend name, the interpret
        mode that will actually run (explicit arg >
        ``REPRO_PALLAS_INTERPRET`` > host auto-detect), the block-pruning
        state, the schedule-weighted ``avg_bits``, the per-layer
        ``layer_avg_bits`` breakdown, and per-layer/total cache bytes at
        this engine's ``max_len`` capacity.  Benchmarks record this next to
        their latency rows so a number in the JSON artifact says which mode
        and which schedule produced it."""
        info = dict(bk.resolve_backend(self.backend).info())
        cfg, sched = self.cfg, self.schedule
        layer_bytes = kvc.schedule_cache_nbytes(
            sched, cfg.n_layers, self.max_len, cfg.n_kv_heads, cfg.head_dim,
            dtype=self.dtype or self.params["embed"].dtype)
        info.update({
            "schedule_uniform": sched.is_uniform,
            "n_policies": len(sched.distinct()),
            "avg_bits": round(sched.avg_bits(cfg.head_dim), 4),
            "layer_avg_bits": sched.layer_avg_bits(cfg.head_dim),
            "layer_cache_bytes": layer_bytes,
            "cache_bytes_per_slot": sum(layer_bytes),
        })
        if self._pools:
            info.update({
                "pooled": True,
                "pool_blocks": self.pool_blocks,
                "pool_block_tokens": self.pool_block_tokens,
                "pool_bands": {
                    f"{g}/{k}": self._pools[(g, k)].block_nbytes
                    for g, k, *_ in self._pool_bands},
                "pool_bytes": sum(self.pool_blocks * p.block_nbytes
                                  for p in self._pools.values()),
            })
        else:
            info["pooled"] = False
        return info

    def stats(self) -> dict:
        """Pool occupancy + sharing counters (DESIGN.md §9).

        Per band and aggregated: blocks used/free/reserved, prefix hit
        rate, copy-on-write copies, resident *packed* bytes, and the
        striped worst case (``batch_slots`` full stripes) those bytes
        replace.  ``admission_stall`` carries the most recent reason the
        FIFO head could not be admitted, for queue diagnostics.

        ``counters`` (DESIGN.md §10) are cumulative since engine build (or
        since :meth:`warmup`, which restores them): requests admitted,
        ticks the FIFO head stalled on an exhausted pool, CoW copies,
        ``decode_syncs`` (decode calls) and ``decode_call_s`` (the engine
        clock's seconds from each call's dispatch until its outputs reached
        the host, summed); ``host`` carries the async host loop's
        delivery/backpressure counters when enabled."""
        out: dict = {"pooled": bool(self._pools),
                     "queue_depth": len(self._queue),
                     "active_slots": self.active_slots,
                     "counters": dict(
                         self._counters,
                         cow_copies=sum(p.cow_copies
                                        for p in self._pools.values()))}
        if self._host is not None:
            out["host"] = self._host.stats()
        if self._spill is not None:
            out["host_spill"] = self._spill.stats()
        if not self._pools:
            return out
        bands = {}
        agg = {k: 0 for k in ("blocks", "used", "free", "reserved",
                              "peak_used", "prefix_hits", "prefix_misses",
                              "cow_copies", "resident_bytes")}
        striped_worst = peak_bytes = 0
        for group, bkey, bs, be, pol, nb in self._pool_bands:
            pool = self._pools[(group, bkey)]
            st = pool.stats()
            st["n_table"] = nb
            bands[f"{group}/{bkey}"] = st
            for k in agg:
                agg[k] += st[k]
            striped_worst += self.batch_slots * nb * pool.block_nbytes
            peak_bytes += pool.peak_used * pool.block_nbytes
        h, m = agg["prefix_hits"], agg["prefix_misses"]
        out.update(agg)
        out.update({
            "prefix_hit_rate": h / (h + m) if h + m else 0.0,
            "peak_resident_bytes": peak_bytes,
            "striped_worst_case_bytes": striped_worst,
            "pool_blocks": self.pool_blocks,
            "pool_block_tokens": self.pool_block_tokens,
            "bands": bands,
            "queue_depth": len(self._queue),
        })
        if self._stall_reason:
            out["admission_stall"] = self._stall_reason
        return out

    def check_invariants(self) -> dict:
        """Post-run leak/consistency audit (DESIGN.md §11): every band
        pool's refcount/free-list/table audit
        (:meth:`~repro.core.block_pool.BlockPool.check_invariants`) plus
        the host spill tier's byte accounting.  Raises ``RuntimeError`` on
        the first violation; returns per-band summaries for the chaos
        bench and CLI gates.  Run it after draining — mid-flight state
        (reserved blocks, pending inserts) is legitimately unbalanced."""
        out: dict = {}
        for (group, bkey), pool in self._pools.items():
            out[f"{group}/{bkey}"] = pool.check_invariants()
        if self._spill is not None:
            self._spill.check_invariants()
            out["host_spill"] = self._spill.stats()
        return out

    @property
    def prefill_shapes(self) -> tuple:
        """Chunk bucket sizes compiled so far (chunked-prefill mode only) —
        the bounded compile-shape set of DESIGN.md §7.  Always a subset of
        ``chunk_buckets``, regardless of how ragged the served traffic is
        (asserted in tests/test_prefill_chunk.py)."""
        return tuple(sorted(self._chunk_fns))

    # --------------------------------------------------------------- details

    def _multi_fn(self) -> Callable:
        # ONE compiled executable of scan length steps_per_sync, reused for
        # every request mix — per-slot temps/eos are traced arrays, so a
        # varied serving process never recompiles the decode step.
        if self._multi is None:
            self._multi = make_multi_decode_fn(
                self.cfg, self.schedule, self.steps_per_sync,
                calib=self.calib, dtype=self.dtype, backend=self.backend)
        return self._multi

    def _call(self, name: str, jitfn: Callable, *args):
        # every jitted call site dispatches through the executable cache:
        # warmed signatures hit the AOT-compiled executable, everything
        # else falls back to the plain jitted function (an un-warmed
        # engine behaves exactly as before warmup existed — DESIGN.md §10)
        return self._exec.call(name, jitfn, *args)

    def _h_done(self, h: StreamHandle) -> bool:
        # async: the scheduler's verdict stands in for h.finished, which
        # the consumer thread sets later, at delivery (DESIGN.md §10)
        if self._host is not None:
            return h._sched_fin is not None
        return h.finished

    def _insert_fn(self) -> Callable:
        if self._insert is None:
            self._insert = jax.jit(
                lambda dst, src, j, row: kvc.insert_slot(
                    dst, j, src, src_slot=row, batch_axis=1),
                donate_argnums=0)
        return self._insert

    def _reset_fn(self) -> Callable:
        if self._reset is None:
            self._reset = jax.jit(
                lambda c, j: kvc.reset_slot(c, j, batch_axis=1),
                donate_argnums=0)
        return self._reset

    def _zero_fn(self) -> Callable:
        if self._zero_caches is None:
            self._zero_caches = jax.jit(
                lambda c: jax.tree.map(jnp.zeros_like, c), donate_argnums=0)
        return self._zero_caches

    def _pool_copy(self) -> Callable:
        if self._pool_copy_fn is None:
            self._pool_copy_fn = jax.jit(
                lambda c, p: kvc.pool_copy_block(c, p, pool_axis=1),
                donate_argnums=0)
        return self._pool_copy_fn

    def _cow_cap(self) -> int:
        # a span of sps tokens touches at most ceil((sps-1)/bt)+1 blocks
        # per slot; fixed capacity -> one compiled CoW-copy shape
        sps, bt = self.steps_per_sync, self.pool_block_tokens
        return self.batch_slots * ((sps - 1 + bt - 1) // bt + 1)

    def _retire(self):
        for i, h in enumerate(self._slot_handle):
            if h is not None and self._h_done(h):
                self._release_slot(i)

    def _release_slot(self, i: int):
        """Free decode lane ``i``: pool blocks deref (cold registered blocks
        spill to the host tier when enabled — DESIGN.md §11), pending
        insert/register/restore state drops, the device row zeroes, and the
        host mirrors clear.  Shared by retirement, preemption, cancellation,
        deadline expiry and the watchdog abort."""
        self._slot_handle[i] = None
        self._done[i] = True
        self._eos[i] = -1
        self._nan_inject[i] = False
        self._pending_insert.pop(i, None)
        self._pending_register.pop(i, None)
        for (group, bkey), rest in self._pending_restore.pop(i, {}).items():
            for phys, key, arrays in rest:
                if self._spill is not None:
                    # un-applied restores go back to the tier, not the floor
                    self._spill.put(key, arrays,
                                    sum(a.nbytes for a in arrays.values()))
        for pool in self._pools.values():
            pool.release_slot(i)   # deref blocks; shared ones live on
        self._hostlen[i] = 0
        if self._caches is not None:
            self._caches = self._call(
                "reset", self._reset_fn(), self._caches, jnp.int32(i))

    # ------------------------------------- lifecycle + degradation (§11)

    def _expired(self, h: StreamHandle, now: float) -> bool:
        dl = h.request.deadline_ms
        return (dl is not None and h._t_submit is not None
                and (now - h._t_submit) * 1e3 > dl)

    def _finish_now(self, h: StreamHandle, reason: str):
        """Terminate a stream outside the token path (deadline, cancel,
        shed — DESIGN.md §11).  Async engines route the verdict through the
        host-loop queue as a zero-token delivery so stream finalization
        keeps its single writer (the consumer thread) and FIFO order."""
        if self._host is not None:
            h._sched_fin = reason
            self._host.put(TokenDelivery(
                handles=[h], rows=[0], counts=[0], reasons=[reason],
                tokens=np.zeros((1, 1), np.int32)))
        else:
            self._finish(h, reason)

    def _lifecycle(self):
        """Deadline/cancel pass, once per tick (DESIGN.md §11): cancelled
        or deadline-expired requests terminate with their structured
        FinishReason and free their pool blocks immediately — queued ones
        never occupy a slot, running ones release mid-stream."""
        now = self._clock()
        keep = []
        for h in self._queue:
            if h._cancel:
                self._counters["cancelled"] += 1
                self._finish_now(h, FinishReason.CANCELLED)
            elif self._expired(h, now):
                self._counters["deadline_misses"] += 1
                self._finish_now(h, FinishReason.DEADLINE)
            else:
                keep.append(h)
        self._queue = keep
        job = self._prefill_job
        if job is not None and (job.handle._cancel
                                or self._expired(job.handle, now)):
            h = job.handle
            if h._cancel:
                self._counters["cancelled"] += 1
                self._finish_now(h, FinishReason.CANCELLED)
            else:
                self._counters["deadline_misses"] += 1
                self._finish_now(h, FinishReason.DEADLINE)
            self._prefill_job = None
            self._chunk_state = job.state   # recycle the prefill buffers
            self._release_slot(job.slot)
        for i, h in enumerate(self._slot_handle):
            if h is None or self._h_done(h):
                continue
            if h._cancel:
                self._counters["cancelled"] += 1
                self._finish_now(h, FinishReason.CANCELLED)
                self._release_slot(i)
            elif self._expired(h, now):
                self._counters["deadline_misses"] += 1
                self._finish_now(h, FinishReason.DEADLINE)
                self._release_slot(i)

    def _pick_victim(self, req: Request) -> Optional[int]:
        """Victim policy (DESIGN.md §11): only slots of *strictly lower*
        priority than the admission candidate are preemptible — equal
        priorities stall FIFO instead, since mutual eviction would
        livelock — and among victims, lowest priority first, last-admitted
        first within a priority (the least sunk work)."""
        best = None
        for i, h in enumerate(self._slot_handle):
            if h is None or self._h_done(h):
                continue
            if h.request.priority >= req.priority:
                continue
            rank = (h.request.priority, -int(self._slot_seq[i]))
            if best is None or rank < best[0]:
                best = (rank, i)
        return None if best is None else best[1]

    def _preempt_slot(self, i: int):
        """Evict slot ``i`` back to the queue for recompute-from-prompt
        (DESIGN.md §11).  Already-delivered tokens stay on the handle; the
        resumed stream regenerates them deterministically (same fold-in
        PRNG keys) and the replay filter asserts the prefix byte-matches
        before appending anything new.  The slot's registered blocks spill
        to the host tier (when enabled) on release, so resume often
        restores the prompt's packed content instead of re-quantizing."""
        h = self._slot_handle[i]
        committed = (h._sched_consumed if self._host is not None
                     else len(h.tokens))
        h._replay_len = max(h._replay_len, committed)
        h._replay_cursor = 0
        h._sched_consumed = 0
        h._sched_fin = None
        h.preempted += 1
        h.events.append(FinishReason.PREEMPTED)
        self._counters["preemptions"] += 1
        self._release_slot(i)
        self._queue.append(h)   # re-sorted by (-priority, rid) at admission

    def _plan_with_preemption(self, req: Request, slot: int):
        """Admission plan for the queue head, evicting strictly-lower
        priority victims one at a time until the plan fits or no victim
        remains (DESIGN.md §11)."""
        plan = self._plan_pool_admission(req, slot)
        while plan is None:
            victim = self._pick_victim(req)
            if victim is None:
                return None
            self._preempt_slot(victim)
            plan = self._plan_pool_admission(req, slot)
        return plan

    def _note_stall(self):
        """Single accounting site for pool-exhaustion stalls: one stalled
        scheduler tick increments ``pool_exhausted_stalls`` exactly once,
        however many admission branches observe it (regression-tested in
        tests/test_degradation.py)."""
        if self._last_stall_tick != self._tick:
            self._last_stall_tick = self._tick
            self._counters["pool_exhausted_stalls"] += 1

    def _shed_all(self):
        """Watchdog abort (DESIGN.md §11): the device step is declared
        wedged, so every queued and active stream terminates as ``shed``
        (a valid FinishReason — ``run()`` returns instead of hanging) and
        all pool state frees."""
        job = self._prefill_job
        if job is not None:
            self._prefill_job = None
            self._chunk_state = job.state
            self._counters["shed"] += 1
            self._finish_now(job.handle, FinishReason.SHED)
            self._release_slot(job.slot)
        for i, h in enumerate(self._slot_handle):
            if h is None:
                continue
            if not self._h_done(h):
                self._counters["shed"] += 1
                self._finish_now(h, FinishReason.SHED)
            self._release_slot(i)
        for h in self._queue:
            self._counters["shed"] += 1
            self._finish_now(h, FinishReason.SHED)
        self._queue = []

    def _admit(self):
        """Move queued requests toward decode slots (DESIGN.md §6 admission).

        Whole-prompt mode prefills groups of equal-length prompts in one
        batch; chunked mode instead *reserves* a free slot and opens a
        :class:`_PrefillJob` that :meth:`_prefill_tick` advances one chunk
        per step.  The queue orders by (priority desc, rid asc) — FIFO
        within a priority class — and under pool pressure the head may
        preempt strictly-lower-priority active slots (DESIGN.md §11)."""
        if len(self._queue) > 1:
            self._queue.sort(key=lambda h: (-h.request.priority, h.rid))
        free = [i for i in range(self.batch_slots)
                if self._slot_handle[i] is None
                and not (self._prefill_job is not None
                         and self._prefill_job.slot == i)]
        if not free or not self._queue:
            return
        if self.prefill_chunk is not None:
            if self._prefill_job is None:
                if self._pools:
                    plan = self._plan_with_preemption(
                        self._queue[0].request, free[0])
                    if plan is None:
                        # FIFO: head waits for free blocks
                        self._note_stall()
                        return
                    handle = self._queue.pop(0)
                    # content lands at _finish_prefill: defer registration
                    self._commit_pool_admission(handle, free[0], plan,
                                                register=False)
                else:
                    handle = self._queue.pop(0)
                handle.admit_time = self._clock()
                self._prefill_job = _PrefillJob(
                    handle=handle, slot=free[0], pos=0,
                    state=self._take_chunk_state())
            return
        if self._pools:
            # pooled admission is FIFO in *blocks*: the head request is
            # admitted only when every band's pool covers its prompt blocks
            # (minus resident prefix hits) plus its decode reservation
            taken: List[tuple] = []
            self._stall_reason = None
            while self._queue and len(taken) < len(free):
                slot = free[len(taken)]
                plan = self._plan_with_preemption(self._queue[0].request,
                                                  slot)
                if plan is None:
                    self._note_stall()
                    break
                h = self._queue.pop(0)
                self._commit_pool_admission(h, slot, plan)
                taken.append((h, slot))
            if not taken:
                return
            pgroups: Dict[int, List[tuple]] = {}
            for h, slot in taken:
                pgroups.setdefault(len(h.request.prompt), []).append((h, slot))
            for plen, pairs in pgroups.items():
                self._admit_group([h for h, _ in pairs],
                                  [s for _, s in pairs])
            return
        take, rest = self._queue[:len(free)], self._queue[len(free):]
        self._queue = rest
        # group equal-length prompts into one batched prefill (a uniform
        # ServeSession wave compiles/executes exactly like the legacy
        # lock-step path); distinct lengths prefill batch-of-1 — no
        # cross-slot padding ever enters the model.
        groups: Dict[int, List[StreamHandle]] = {}
        for h in take:
            groups.setdefault(len(h.request.prompt), []).append(h)
        it = iter(free)
        for plen, hs in groups.items():
            self._admit_group(hs, [next(it) for _ in hs])

    # ----------------------------------------------- paged block pool details

    def _eventual_blocks(self, plen: int, max_new: int, pol, nb: int) -> int:
        """Worst-case pool blocks a request will ever hold in one band:
        every packed position its stream can reach, including up to
        ``steps_per_sync - 1`` clipped overshoot writes past max_len, all
        landing inside the nb-block table."""
        bt = self.pool_block_tokens
        qc_end = min(max(0, plen + max_new + self.steps_per_sync
                         - pol.n_sink - pol.window), nb * bt)
        return -(-qc_end // bt)

    def _plan_pool_admission(self, req: Request, slot: int):
        """Dry-run admission for one request: per band, the prefix-key
        lookups and the block budget.  Returns None (setting
        ``_stall_reason``) if any band lacks free blocks — nothing is
        allocated until :meth:`_commit_pool_admission`."""
        plen = len(req.prompt)
        plans = {}
        for group, bkey, bs, be, pol, nb in self._pool_bands:
            pool = self._pools[(group, bkey)]
            full_keys, tail_key = prefix_block_keys(
                req.prompt.tolist(), pol.n_sink, pol.window,
                self.pool_block_tokens, seed=f"{group}:{bkey}:{pol}")
            hits = [(lb, key, pool.lookup(key))
                    for lb, key in enumerate(full_keys)]
            n_hit = sum(1 for _, _, p in hits if p is not None)
            eventual = self._eventual_blocks(plen, req.max_new, pol, nb)
            if eventual - n_hit > pool.available():
                st = pool.stats()
                self._stall_reason = (
                    f"queued: the head request needs "
                    f"{eventual - n_hit} blocks in band {bkey} ({group}) "
                    f"but only {pool.available()} are uncommitted "
                    f"({st['used']}/{st['blocks']} used, "
                    f"{st['reserved']} reserved for in-flight decodes, "
                    f"{st['resident_bytes']} resident bytes)")
                return None
            tail_phys = pool.lookup(tail_key) if tail_key else None
            plans[(group, bkey)] = (hits, tail_key, tail_phys,
                                    eventual, n_hit)
        return plans

    def _commit_pool_admission(self, h: StreamHandle, slot: int, plans,
                               register: bool = True):
        """Apply a planned admission: ref prefix hits, alloc misses into the
        slot's table, reserve the remaining decode blocks, and record which
        blocks still need their quantized content inserted after prefill.

        Misses first consult the host spill tier (DESIGN.md §11): a block
        whose content-hash key was spilled restores its exact packed bytes
        into a fresh physical block instead of re-quantizing from the
        prompt — it counts as a prefix hit and is excluded from the
        post-prefill insert list.  The host arrays are popped here (the LRU
        could evict them before activation) and written back to the device
        at :meth:`_apply_pool_insert`."""
        pend, pend_reg, pend_res = {}, {}, {}
        for (group, bkey), (hits, tail_key, tail_phys, eventual,
                            n_hit) in plans.items():
            pool = self._pools[(group, bkey)]
            miss_pairs, reg, restores, now = [], [], [], 0

            def take(lb, key, pool=pool, slot=slot, miss_pairs=miss_pairs,
                     reg=reg, restores=restores):
                fresh = pool.alloc(slot)
                pool.assign(slot, lb, fresh)
                arrays = (self._spill.pop(key)
                          if self._spill is not None else None)
                if arrays is not None:
                    pool.hits += 1
                    restores.append((fresh, key, arrays))
                    self._counters["restored_blocks"] += 1
                else:
                    pool.misses += 1
                    miss_pairs.append((lb, fresh))
                reg.append((key, fresh))

            for lb, key, phys in hits:
                if phys is not None:
                    pool.ref(phys)
                    pool.assign(slot, lb, phys)
                    pool.hits += 1
                else:
                    take(lb, key)
                    now += 1
            if tail_key is not None:
                if tail_phys is not None:
                    pool.ref(tail_phys)
                    pool.assign(slot, len(hits), tail_phys)
                    pool.hits += 1
                else:
                    take(len(hits), tail_key)
                    now += 1
            # decode still needs (eventual - full hits - allocated-now)
            # blocks; a shared tail counts — its first write goes CoW
            pool.set_reservation(slot, max(0, eventual - n_hit - now))
            if register:
                for key, phys in reg:
                    pool.register(key, phys)
            else:
                pend_reg[(group, bkey)] = reg
            pend[(group, bkey)] = miss_pairs
            if restores:
                pend_res[(group, bkey)] = restores
        self._pending_insert[slot] = pend
        if pend_res:
            self._pending_restore[slot] = pend_res
        if not register:
            self._pending_register[slot] = pend_reg

    def _pool_insert_fn(self, group: str, bkey: str) -> Callable:
        key = (group, bkey)
        if key not in self._pool_insert_fns:
            self._pool_insert_fns[key] = jax.jit(
                lambda d, s, p, r: kvc.pool_insert_blocks(
                    d, s, p, src_slot=r, pool_axis=1),
                donate_argnums=0)
        return self._pool_insert_fns[key]

    # --------------------------------------------- host spill tier (§11)

    def _spill_read_fn(self, group: str, bkey: str) -> Callable:
        key = ("read", group, bkey)
        if key not in self._spill_fns:
            self._spill_fns[key] = jax.jit(
                lambda c, p: kvc.pool_read_block(c, p, pool_axis=1))
        return self._spill_fns[key]

    def _spill_write_fn(self, group: str, bkey: str) -> Callable:
        key = ("write", group, bkey)
        if key not in self._spill_fns:
            self._spill_fns[key] = jax.jit(
                lambda c, blk, p: kvc.pool_write_block(c, blk, p,
                                                       pool_axis=1),
                donate_argnums=0)
        return self._spill_fns[key]

    def _spill_block(self, group: str, bkey: str, key: str, phys: int):
        """``BlockPool.on_evict`` hook (DESIGN.md §11): a hash-registered
        block just hit refcount 0 — read its packed planes off the device
        and park them in the LRU host tier instead of losing the content.
        Block keys are band-salted by :func:`prefix_block_keys`, so one
        shared tier serves every band without collisions."""
        if self._spill is None or self._caches is None:
            return
        blk = self._call(f"spill_read:{group}:{bkey}",
                         self._spill_read_fn(group, bkey),
                         self._band_cache_ref(group, bkey), jnp.int32(phys))
        arrays = {k: np.asarray(v) for k, v in blk.items()}
        if self._spill.put(key, arrays,
                           sum(a.nbytes for a in arrays.values())):
            self._counters["spilled_blocks"] += 1

    def _band_cache_ref(self, group: str, bkey: str):
        g = self._caches[group]
        return g if "length" in g else g[bkey]

    def _set_band_cache(self, group: str, bkey: str, cache):
        g = self._caches[group]
        if "length" in g:
            self._caches[group] = cache
        else:
            g[bkey] = cache

    @staticmethod
    def _band_cache_src(caches, group: str, bkey: str):
        g = caches[group]
        return g if "length" in g else g[bkey]

    def _apply_pool_insert(self, slot: int, src_caches, row: int):
        """Quantize-once commit: copy the slot's *miss* blocks from its
        freshly-prefilled striped cache into the pool (hits are already
        resident and are never re-inserted), then register any deferred
        prefix keys now that the content is on device."""
        pend = self._pending_insert.pop(slot, None)
        pend_reg = self._pending_register.pop(slot, {})
        for (group, bkey), rest in self._pending_restore.pop(
                slot, {}).items():
            for phys, key, arrays in rest:
                out = self._call(
                    f"spill_write:{group}:{bkey}",
                    self._spill_write_fn(group, bkey),
                    self._band_cache_ref(group, bkey),
                    {k: jnp.asarray(v) for k, v in arrays.items()},
                    jnp.int32(phys))
                self._set_band_cache(group, bkey, out)
        if pend is None:
            return
        for (group, bkey), miss_pairs in pend.items():
            if miss_pairs:
                pool = self._pools[(group, bkey)]
                pairs = np.zeros((pool.n_table, 2), np.int32)
                pairs[:len(miss_pairs)] = miss_pairs
                out = self._call(
                    f"pool_insert:{group}:{bkey}",
                    self._pool_insert_fn(group, bkey),
                    self._band_cache_ref(group, bkey),
                    self._band_cache_src(src_caches, group, bkey),
                    jnp.asarray(pairs), jnp.int32(row))
                self._set_band_cache(group, bkey, out)
            for key, phys in pend_reg.get((group, bkey), ()):
                self._pools[(group, bkey)].register(key, phys)

    def _pool_prewrite(self):
        """Copy-on-write pass before a decode chunk: every packed block the
        next ``steps_per_sync`` ring-evictions can touch must be privately
        owned by its slot.  Shared blocks are copied to fresh physical ids
        (consuming the slot's reservation); exclusively-held blocks merely
        drop their prefix-hash registration — they are about to diverge
        from the content the hash names."""
        sps, bt = self.steps_per_sync, self.pool_block_tokens
        copies = 0
        with span("engine.cow", lambda: {"copies": copies}):
            for group, bkey, bs, be, pol, nb in self._pool_bands:
                pool = self._pools[(group, bkey)]
                pairs = []
                for i in range(self.batch_slots):
                    if self._slot_handle[i] is None:
                        continue
                    u_lo = int(self._hostlen[i]) - pol.n_sink - pol.window
                    for lb in seg.blocks_spanned(u_lo, u_lo + sps, bt, nb):
                        work = pool.ensure_writable(i, lb)
                        if work is not None and work[0] == "copy":
                            pairs.append((work[1], work[2]))
                if pairs:
                    copies += len(pairs)
                    arr = np.zeros((self._cow_cap(), 2), np.int32)
                    arr[:len(pairs)] = pairs
                    self._set_band_cache(
                        group, bkey,
                        self._call("pool_copy", self._pool_copy(),
                                   self._band_cache_ref(group, bkey),
                                   jnp.asarray(arr)))

    def _flush_tables(self):
        """Push dirty host block tables to the device caches.  Rows of
        slots with no active handle are masked to the null block so a
        freewheeling (retired or mid-chunked-prefill) device row can never
        write into committed pool blocks."""
        live = np.array([h is not None for h in self._slot_handle],
                        np.int32)
        for group, bkey, bs, be, pol, nb in self._pool_bands:
            pool = self._pools[(group, bkey)]
            if not pool.dirty:
                continue
            tbl = jnp.asarray(pool.tables * live[:, None])
            cache = self._band_cache_ref(group, bkey)
            cache["block_tbl"] = jnp.broadcast_to(
                tbl[None], (be - bs,) + tbl.shape)
            pool.dirty = False

    def _admit_group(self, handles: List[StreamHandle], slots: List[int]):
        prompts = np.stack([h.request.prompt for h in handles])
        self._tick_prefill += prompts.size
        logits, caches = self._call(
            "prefill", self.prefill_fn, self.params,
            {"tokens": jnp.asarray(prompts, jnp.int32)})
        # per-request stream = engine seed folded with the request seed:
        # replayable per request, perturbable per engine
        keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                             h.request.seed)
                          for h in handles])
        keys, subs = _split_keys(keys)
        temps = jnp.asarray([h.request.temperature for h in handles],
                            jnp.float32)
        first = np.asarray(sample_per_slot(logits[:, -1], temps, subs))
        keys = np.asarray(keys)

        if self._caches is None:
            self._caches = (self._alloc_pooled() if self._pools
                            else self._alloc_like(caches))
        now = self._clock()
        self._counters["admitted"] += len(handles)
        for row, (h, slot) in enumerate(zip(handles, slots)):
            self._caches = self._call(
                "insert", self._insert_fn(), self._caches, caches,
                jnp.int32(slot), jnp.int32(row))
            if self._pools:
                self._apply_pool_insert(slot, caches, row)
                self._hostlen[slot] = len(h.request.prompt)
            req = h.request
            self._slot_handle[slot] = h
            self._slot_seq[slot] = self._admit_seq   # victim order (§11)
            self._admit_seq += 1
            self._tok[slot, 0] = first[row]
            self._keys[slot] = keys[row]
            self._temps[slot] = max(req.temperature, 0.0)
            self._eos[slot] = -1 if req.eos_id is None else req.eos_id
            self._done[slot] = (req.eos_id is not None
                                and int(first[row]) == req.eos_id)
            if h.admit_time is None:
                h.admit_time = now
            self._admit_deliver(slot, h, int(first[row]))

    def _prefill_tick(self):
        """Advance the in-flight chunked prefill by one chunk (DESIGN.md §7).

        Picks the smallest ``chunk_buckets`` entry covering the remaining
        tokens (capped at ``prefill_chunk``), pads the chunk up to it, and
        runs the jitted chunk step at offset ``job.pos`` — one executable
        per bucket ever compiles, whatever the traffic looks like.  When the
        last chunk lands, the finished cache is inserted into the reserved
        slot and the first token is sampled from the final-chunk logits,
        exactly as whole-prompt admission would have done."""
        job = self._prefill_job
        if job is None:
            return
        prompt = job.handle.request.prompt
        n = min(self.prefill_chunk, len(prompt) - job.pos)
        bucket = next(b for b in self.chunk_buckets if b >= n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt[job.pos:job.pos + n]
        with span("engine.prefill_chunk", lambda: {"bucket": bucket, "n": n}):
            logits, job.state = self._call(
                f"chunk_{bucket}", self._chunk_fn(bucket),
                self.params, jnp.asarray(toks), job.state,
                jnp.int32(job.pos), jnp.int32(n))
        self._tick_prefill += n
        job.pos += n
        if job.pos >= len(prompt):
            self._prefill_job = None
            self._finish_prefill(job, logits)

    def _take_chunk_state(self) -> Dict:
        """Prefill state for a new job, recycling the previous job's buffers.

        Only one job runs at a time, so the engine keeps a single state
        (caches + the big fp workspace) alive.  The caches are zeroed for
        the new prompt; the workspace is reused dirty — every read of it is
        masked to positions the new prompt has already written (causality
        against ``pos_q``), so stale rows from the previous prompt are
        unreachable (DESIGN.md §7)."""
        st, self._chunk_state = self._chunk_state, None
        if st is None:
            return T.prefill_chunk_init(
                self.cfg, self.schedule, self.max_len, self.max_len, batch=1,
                dtype=self.dtype or self.params["embed"].dtype)
        st["caches"] = self._call("zero_caches", self._zero_fn(),
                                  st["caches"])
        return st

    def _chunk_fn(self, bucket: int) -> Callable:
        if bucket not in self._chunk_fns:
            self._chunk_fns[bucket] = make_prefill_chunk_fn(
                self.cfg, self.schedule, calib=self.calib, dtype=self.dtype,
                backend=self.backend)
        return self._chunk_fns[bucket]

    def _finish_prefill(self, job: _PrefillJob, logits):
        """Activate the reserved slot from a completed chunked prefill."""
        h, slot = job.handle, job.slot
        caches = job.state["caches"]      # (L, 1, ...) groups; ws is dropped
        keys = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                  h.request.seed)[None]
        keys, subs = _split_keys(keys)
        temps = jnp.asarray([h.request.temperature], jnp.float32)
        first = int(np.asarray(sample_per_slot(logits[:, -1], temps, subs))[0])

        if self._caches is None:
            self._caches = (self._alloc_pooled() if self._pools
                            else self._alloc_like(caches))
        self._caches = self._call(
            "insert", self._insert_fn(), self._caches, caches,
            jnp.int32(slot), jnp.int32(0))
        if self._pools:
            self._apply_pool_insert(slot, caches, 0)
            self._hostlen[slot] = len(h.request.prompt)
        self._chunk_state = job.state    # recycle buffers for the next job
        self._counters["admitted"] += 1
        req = h.request
        self._slot_handle[slot] = h
        self._slot_seq[slot] = self._admit_seq       # victim order (§11)
        self._admit_seq += 1
        self._tok[slot, 0] = first
        self._keys[slot] = np.asarray(keys)[0]
        self._temps[slot] = max(req.temperature, 0.0)
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        self._done[slot] = req.eos_id is not None and first == req.eos_id
        self._admit_deliver(slot, h, first)

    def _alloc_like(self, caches):
        """Zeroed engine cache: the prefilled group's structure with the
        batch axis (axis 1 of every layer-stacked leaf) widened to
        batch_slots."""
        def widen(x):
            shape = (x.shape[0], self.batch_slots) + x.shape[2:]
            return jnp.zeros(shape, x.dtype)
        return jax.tree.map(widen, caches)

    def _alloc_pooled(self):
        """Zeroed engine cache for pooled mode, built from shapes directly:
        `_alloc_like` widens axis 1 of every leaf, but a pooled plane's
        axis 1 is the physical pool axis, not the batch axis.  Pooled bands
        get pool-major planes + per-slot tables; fp16 / fully-windowed
        bands keep their striped layout."""
        cfg = self.cfg
        dtype = self.dtype or self.params["embed"].dtype
        nf = cfg.first_dense
        caches = {}
        for group, g0, g1 in (("dense", 0, nf), ("scan", nf, cfg.n_layers)):
            if g1 == g0:
                continue
            bands = self.schedule.bands(g0, g1)
            couts = {}
            for bs, be, pol in bands:
                if (group, f"L{bs:03d}") in self._pools:
                    shapes = kvc.pooled_cache_shapes(
                        self.batch_slots, self.max_len, cfg.n_kv_heads,
                        cfg.head_dim, pol, self.pool_blocks,
                        self.pool_block_tokens, dtype)
                else:
                    shapes = kvc.cache_shapes(
                        self.batch_slots, self.max_len, cfg.n_kv_heads,
                        cfg.head_dim, pol, dtype)
                couts[f"L{bs:03d}"] = {k: jnp.zeros((be - bs,) + s, d)
                                       for k, (s, d) in shapes.items()}
            caches[group] = T._band_out(couts, bands, g0)
        return caches

    def _decode_chunk(self):
        if self._pools:
            self._pool_prewrite()
            with span("engine.flush_tables"):
                self._flush_tables()
        t0 = self._clock()
        with span("engine.decode.dispatch"):
            toks, tok, caches, keys, done, bad, live = self._call(
                "multi", self._multi_fn(),
                self.params, jnp.asarray(self._tok), self._caches,
                jnp.asarray(self._keys), jnp.asarray(self._done),
                jnp.asarray(self._temps), jnp.asarray(self._eos),
                jnp.asarray(self._nan_inject))
        self._caches = caches
        with span("engine.decode.wait"):
            # np.array copies: jax->numpy views are read-only and the
            # scheduler mutates these in place at retire/admit time
            self._tok = np.array(tok)
            self._keys = np.array(keys)
            done_np = self._done = np.array(done)
            bad_np = np.asarray(bad)
            if self._host is not None:
                live = np.asarray(live)
        # one-shot injections reset only AFTER the outputs above forced the
        # computation: jnp.asarray(self._nan_inject) may alias the numpy
        # buffer on CPU, so zeroing before the sync races the device read
        self._nan_inject[:] = False
        dt = self._clock() - t0
        self._counters["decode_syncs"] += 1
        self._counters["decode_call_s"] += dt
        self._watchdog(dt)
        with span("engine.deliver"):
            if self._host is not None:
                self._enqueue_chunk(toks, done_np, bad_np, live)
                return
            toks = np.asarray(toks)                 # ONE sync per chunk
            for i in range(self.batch_slots):
                h = self._slot_handle[i]
                if h is None:
                    continue
                self._hostlen[i] += self.steps_per_sync
                if bool(bad_np[i]) and not h.finished:
                    self._counters["nan_quarantines"] += 1
                    self._finish(h, FinishReason.SHED)  # retire frees the slot
                    continue
                self._deliver(i, toks[i].tolist())

    def _enqueue_chunk(self, toks, done_np, bad_np, live):
        """Async delivery of one decode chunk (DESIGN.md §10): decide
        finishes from the tiny per-slot live counts; the big token array
        stays on device and the consumer thread materializes it off the
        scheduler's critical path."""
        handles, rows, counts, reasons = [], [], [], []
        for i in range(self.batch_slots):
            h = self._slot_handle[i]
            if h is None or h._sched_fin is not None:
                continue
            self._hostlen[i] += self.steps_per_sync
            if bool(bad_np[i]):
                # NaN quarantine (§11): the slot's logits went
                # non-finite — drop the chunk, shed the stream
                self._counters["nan_quarantines"] += 1
                h._sched_fin = FinishReason.SHED
                handles.append(h)
                rows.append(i)
                counts.append(0)
                reasons.append(FinishReason.SHED)
                continue
            left = h.request.max_new - h._sched_consumed
            n_live = int(live[i])
            if bool(done_np[i]) and n_live <= left:
                consumed, reason = n_live, FinishReason.EOS
            elif left <= n_live:
                consumed, reason = left, FinishReason.LENGTH
            else:
                consumed, reason = n_live, None
            h._sched_consumed += consumed
            h._sched_fin = reason
            handles.append(h)
            rows.append(i)
            counts.append(consumed)
            reasons.append(reason)
        if handles:
            self._host.put(TokenDelivery(
                handles=handles, rows=rows, counts=counts,
                reasons=reasons, tokens=toks))

    def _watchdog(self, dt: float):
        """Device-step watchdog (DESIGN.md §11): a decode chunk exceeding
        ``step_timeout_s`` (wall time plus any fault-injected deterministic
        delay) is a trip; ``watchdog_max_trips`` *consecutive* trips
        declare the device wedged, and :meth:`step` sheds all work rather
        than hanging.  A healthy chunk resets the streak."""
        extra = (self._faults.take_step_delay()
                 if self._faults is not None else 0.0)
        if self.step_timeout_s is None:
            return
        if dt + extra > self.step_timeout_s:
            self._counters["watchdog_trips"] += 1
            self._watchdog_consec += 1
            if self._watchdog_consec >= self.watchdog_max_trips:
                self._wedged = True
        else:
            self._watchdog_consec = 0

    def _admit_deliver(self, slot: int, h: StreamHandle, first: int):
        """Deliver a request's first (admission-sampled) token: directly in
        the synchronous loop, via the host-loop queue in async mode — the
        same transport every decode chunk takes (DESIGN.md §10)."""
        if self._host is None:
            if h.first_token_time is None:   # preserved across preemptions
                h.first_token_time = self._clock()
            self._deliver(slot, [first])
            return
        req = h.request
        if req.eos_id is not None and first == req.eos_id:
            reason = FinishReason.EOS
        elif req.max_new <= 1:
            reason = FinishReason.LENGTH
        else:
            reason = None
        h._sched_consumed = 1
        h._sched_fin = reason
        self._host.put(TokenDelivery(
            handles=[h], rows=[0], counts=[1], reasons=[reason],
            tokens=np.asarray([[first]], np.int32)))

    def _deliver(self, slot: int, tokens: List[int]):
        """Append chunk tokens to a slot's handle, honoring eos/max_new.
        Post-preemption residencies run the replay filter first
        (DESIGN.md §11): regenerated tokens the stream already delivered
        are asserted equal and dropped."""
        h = self._slot_handle[slot]
        if h.finished:
            return
        req = h.request
        taken: List[int] = []
        for t in h._absorb_replay(tokens):
            if h.finished:
                break
            h.tokens.append(t)
            taken.append(t)
            if req.eos_id is not None and t == req.eos_id:
                self._finish(h, FinishReason.EOS)
            elif len(h.tokens) >= req.max_new:
                self._finish(h, FinishReason.LENGTH)
        if self._detok is not None and taken:
            h.text += self._detok(taken)

    def _finish(self, h: StreamHandle, reason: str):
        h.finished = True
        h.finish_reason = reason
        h.finish_time = self._clock()
        self.n_completed += 1


# ------------------------------------------------------- compatibility shim

class ServeSession:
    """Lock-step array API over :class:`Engine` (compatibility shim;
    DESIGN.md §6 "Compatibility").

    ``generate(prompts (B, S), max_new)`` submits one equal request per
    batch slot and runs the engine to completion; the B requests share a
    prompt length, so admission is a single batched prefill and the greedy
    token streams are bit-identical to the pre-engine lock-step path
    (asserted in tests).  New code should talk to :class:`Engine` directly —
    it also admits ragged prompts and per-request budgets.
    """

    def __init__(self, params, cfg: ArchConfig, policy,
                 batch_slots: int, max_len: int, calib=None, temperature=0.0,
                 seed: int = 0, backend=None, steps_per_sync: int = 8,
                 eos_id: Optional[int] = None,
                 prefill_chunk: Optional[int] = None, chunk_buckets=None,
                 pool_blocks: Optional[int] = None,
                 pool_block_tokens: int = 16):
        self.engine = Engine(params, cfg, policy, batch_slots=batch_slots,
                             max_len=max_len, calib=calib, seed=seed,
                             backend=backend, steps_per_sync=steps_per_sync,
                             prefill_chunk=prefill_chunk,
                             chunk_buckets=chunk_buckets,
                             pool_blocks=pool_blocks,
                             pool_block_tokens=pool_block_tokens)
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.temperature = temperature
        self.eos_id = eos_id
        self.seed = seed

    def generate(self, prompts: np.ndarray, max_new: int = 16) -> np.ndarray:
        """prompts: (B, S) int32 (B == batch_slots). Returns (B, max_new);
        post-EOS positions are padded with ``eos_id`` (DESIGN.md §6)."""
        prompts = np.asarray(prompts)
        if prompts.ndim != 2:
            raise ValueError(f"prompts must be (B, S), got {prompts.shape}")
        b = prompts.shape[0]
        if b != self.batch_slots:
            raise ValueError(
                f"prompts batch ({b}) != batch_slots ({self.batch_slots}); "
                f"ServeSession is the lock-step shim — submit to Engine "
                f"directly for ragged batches")
        if prompts.shape[1] + max_new > self.max_len:
            raise ValueError(
                f"prompt_len ({prompts.shape[1]}) + max_new ({max_new}) "
                f"exceeds max_len ({self.max_len})")
        handles = [self.engine.submit(Request(
            prompt=prompts[i], max_new=max_new, temperature=self.temperature,
            eos_id=self.eos_id, seed=self.seed + i)) for i in range(b)]
        self.engine.run(handles)
        out = np.full((b, max_new),
                      self.eos_id if self.eos_id is not None else 0, np.int32)
        lengths = np.zeros((b,), np.int32)
        for i, h in enumerate(handles):
            toks = h.result()
            out[i, :len(toks)] = toks     # tail keeps the eos_id fill
            ne = toks != self.eos_id if self.eos_id is not None else \
                np.ones(len(toks), bool)
            lengths[i] = int(ne.argmin()) if not ne.all() else len(toks)
        self.lengths = lengths            # per-slot generated-token counts
        return out
