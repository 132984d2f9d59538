"""One-chip smoke run of the SKVQ serving path on a TPU.

    python chip_smoke.py

Builds ``llama3p2_1b`` at its published widths (16 layers, d_model 2048,
32 query / 8 KV heads, head_dim 64, vocab 128256) with bf16 weights drawn
from a seed, and runs two phases in this one process:

Phase A  prefills one slot with a 2,048-token prompt through the K2/V1.5
         cache, then runs one decode step with the compiled ``pallas``
         backend and one with the jnp ``reference`` backend and compares
         their logits.  It also compares one layer's decode attention
         between the two backends, and the compiled quantize-and-pack
         kernel against the jnp quantizer run on the host CPU.
Phase B  serves 8 greedy requests (prompt lengths 768-1280 drawn from the
         seed, 32 new tokens each) on 4 slots through ``Engine``, built as
         ``launch/serve.py`` builds it (chunked prefill, paged block pool,
         8 tokens per host sync), after ``Engine.warmup()``.

Times printed are smoke figures from one run, not benchmark results.  The
script exits non-zero, and prints no result line, unless JAX's first device
is a TPU and every check passes.  The last line of standard output is the
JSON result.  The persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama3p2_1b"
SEED = 0
PROMPT_LEN_A = 2048
SLOTS, N_REQUESTS, MAX_NEW = 4, 8, 32
PROMPT_LENS_B = (768, 1280)
PREFILL_CHUNK, STEPS_PER_SYNC = 256, 8
POOL_BLOCK_TOKENS, POOL_MEMORY_BYTES = 16, 64 << 20
# The repo's backend-parity gate (benchmarks/kernel_bench.py) is 2e-2 on
# the f32 logits of the smoke model, which are O(1).  Here the weights and
# logits are bf16: 8 significant bits, so one rounding step of a logit
# near 4 is already 2**-5 = 0.031.  The gate therefore scales with the
# largest reference logit: 2e-2 * max(1, max|logit|).  Attention outputs
# (f32 on both backends) are held to the same relative bound.
REL_TOL = 2e-2
# Share of bytes on which the compiled quantize kernel may differ from the
# jnp quantizer run on the host CPU (IEEE f32 division).  With bf16 inputs
# and fp8 scale/zero, (x - zero) / scale often lands exactly on a .5 tie,
# and a quotient one ulp off breaks the tie the other way.  A wrong packed
# layout disagrees on most codes.  (XLA's own division on the TPU is not
# the reference: it is approximate and flips about a sixth of the codes.)
QUANT_MISMATCH_TOL = 1e-3


def _policy(cfg):
    from repro.core.policy import QuantPolicy
    # launch/serve.py's defaults: K2/V1.5, group 64, window 32, 5 sinks
    return QuantPolicy(bits_k=2.0, bits_v=1.5,
                       group_size=min(64, cfg.head_dim), window=32, n_sink=5)


def phase_a(cfg, params, prompt_len, seed, failures):
    """Kernel on chip: pallas vs reference decode over one prefilled slot."""
    import jax
    import jax.numpy as jnp
    from repro.core.quant import n_meta_groups, quantize_groups
    from repro.kernels.kv_quant import kv_quant_pallas
    from repro.models import backends as bk
    from repro.models import transformer as T

    pol = _policy(cfg)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, prompt_len)),
                       jnp.int32)
    max_len = prompt_len + 64
    t0 = time.perf_counter()
    logits, caches = jax.jit(lambda p, t: T.prefill_model(
        p, cfg, {"tokens": t}, pol, max_len=max_len,
        backend="reference"))(params, toks)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    nxt.block_until_ready()
    print(f"phase A: prefill {prompt_len} tokens (compile + run) "
          f"{time.perf_counter() - t0:.1f}s")

    out = {}
    for name in ("pallas", "reference"):
        step = jax.jit(lambda p, t, c, _n=name: T.decode_step(
            p, cfg, t, c, pol, backend=_n)[0])
        out[name] = np.asarray(step(params, nxt, caches), np.float32)
    ref = out["reference"]
    diff = float(np.abs(out["pallas"] - ref).max())
    scale = max(1.0, float(np.abs(ref).max()))
    print(f"phase A: decode logits max|pallas - reference| = {diff:.6g} "
          f"(max|logit| {scale:.4g}, gate {REL_TOL * scale:.4g}; the f32 "
          f"smoke gate 2e-2 {'held' if diff <= 2e-2 else 'did not hold'})")
    if not (np.isfinite(out["pallas"]).all() and np.isfinite(ref).all()):
        failures.append("phase A: non-finite decode logits")
    if not diff <= REL_TOL * scale:
        failures.append(f"phase A: logit difference {diff:.4g} > "
                        f"{REL_TOL * scale:.4g}")

    # one layer's attention over the prefilled cache, f32 on both backends
    layer0 = jax.tree.map(lambda x: x[0], caches["scan"])
    q = jnp.asarray(rng.standard_normal((1, 1, cfg.n_heads, cfg.head_dim)),
                    jnp.float32)
    att = {name: np.asarray(jax.jit(
        lambda q, c, _b=bk.get_backend(name): _b.attend(
            q, c, cfg, pol, dtype=jnp.float32))(q, layer0))
        for name in ("pallas", "reference")}
    adiff = float(np.abs(att["pallas"] - att["reference"]).max())
    ascale = max(1.0, float(np.abs(att["reference"]).max()))
    print(f"phase A: layer-0 attention max|pallas - reference| = "
          f"{adiff:.6g} (gate {REL_TOL * ascale:.4g})")
    if not adiff <= REL_TOL * ascale:
        failures.append(f"phase A: attention difference {adiff:.4g}")

    # the quantize-and-pack kernel against the jnp quantizer on the host
    cpu = jax.devices("cpu")[0]
    x = jnp.asarray(rng.standard_normal((4096, cfg.head_dim)), jnp.bfloat16)
    for bits in (pol.bits_k, pol.bits_v):
        g = n_meta_groups(cfg.head_dim, bits, pol.group_size)
        alpha = jnp.asarray(rng.uniform(0.8, 1.0, (4096, g)), jnp.float32)
        got = jax.jit(lambda x, a, _b=bits: kv_quant_pallas(
            x, _b, pol.group_size, alpha=a))(x, alpha)
        want = jax.jit(lambda x, a, _b=bits: quantize_groups(
            x, _b, pol.group_size, a))(jax.device_put(x, cpu),
                                       jax.device_put(alpha, cpu))
        worst = max(float(np.mean(np.asarray(got[k]) != np.asarray(want[k])))
                    for k in want)
        print(f"phase A: kv_quant {bits}-bit: largest share of bytes "
              f"differing from the jnp quantizer on the host {worst:.3g}")
        if not worst <= QUANT_MISMATCH_TOL:
            failures.append(f"phase A: kv_quant {bits}-bit mismatch {worst:.3g}")


def phase_b(cfg, params, seed, failures):
    """The normal serving path: warm the engine, serve, audit.  Returns the
    engine's ``backend_info``, which says whether the kernels ran compiled."""
    from repro.core.policy import as_schedule
    from repro.launch.serve import pool_tiled_max_len
    from repro.serving import Engine, FinishReason, Request
    from repro.testing import count_compiles

    schedule = as_schedule(_policy(cfg), cfg.n_layers)
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS_B
    lens = rng.integers(lo, hi + 1, N_REQUESTS)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new=MAX_NEW, seed=i) for i, n in enumerate(lens)]
    # launch/serve.py's capacity rule: longest prompt + budget + one sync
    max_len = pool_tiled_max_len(hi + MAX_NEW + STEPS_PER_SYNC, schedule,
                                 POOL_BLOCK_TOKENS)
    eng = Engine(params, cfg, schedule, batch_slots=SLOTS, max_len=max_len,
                 backend="pallas", steps_per_sync=STEPS_PER_SYNC,
                 prefill_chunk=PREFILL_CHUNK,
                 pool_block_tokens=POOL_BLOCK_TOKENS,
                 pool_memory_bytes=POOL_MEMORY_BYTES)
    info = eng.backend_info
    rep = eng.warmup()
    print(f"phase B: warmup {rep['n_executables']} executables, compile "
          f"{rep['compile_s']:.1f}s, rehearsal {rep['rehearse_s']:.1f}s")

    with count_compiles() as n_compiles:
        t0 = eng.now()
        handles = [eng.submit(r) for r in reqs]
        eng.run(handles)
        eng.drain()
        dt = eng.now() - t0
    n_tok = sum(len(h.tokens) for h in handles)
    ttft = [h.first_token_time - h.submit_time for h in handles
            if h.first_token_time is not None]
    print(f"phase B (smoke figures, one run, not a benchmark): "
          f"{N_REQUESTS} requests, prompts {sorted(int(n) for n in lens)}, "
          f"{n_tok} tokens in {dt:.2f}s = {n_tok / dt:.1f} tok/s; "
          f"TTFT p50 {1e3 * float(np.median(ttft)) if ttft else float('nan'):.0f} ms "
          f"(all submitted at t=0 on {SLOTS} slots)")

    counters = eng.stats()["counters"]
    print(f"phase B: counters {counters}")
    reasons = [h.finish_reason for h in handles]
    post = eng.warmup_report()["post_warmup_compiles"]
    checks = {
        "zero post-warmup compiles": post == 0 and n_compiles() == 0,
        "every request finished on length": all(
            r == FinishReason.LENGTH for r in reasons),
        "every request got max_new tokens": all(
            len(h.tokens) == MAX_NEW for h in handles),
        "no NaN quarantine, shed or watchdog trip": all(
            counters[k] == 0 for k in ("nan_quarantines", "shed",
                                       "watchdog_trips")),
    }
    try:
        eng.check_invariants()
        checks["pool audit"] = True
    except RuntimeError as e:
        print(f"phase B: pool audit: {e}", file=sys.stderr)
        checks["pool audit"] = False
    eng.close()
    for name, ok in checks.items():
        print(f"phase B: {name}: {'pass' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"phase B: {name}")
    if post or n_compiles():
        print(f"phase B: post-warmup compiles engine={post} "
              f"jax={n_compiles()} {eng.warmup_report()['cold_names']}")
    print(f"phase B: finish reasons {reasons}")
    return info


def _guarded(failures, phase, *args):
    """Run one phase; an exception fails the run once the other phase ran."""
    try:
        return phase(*args)
    except Exception as e:
        traceback.print_exc()
        failures.append(f"{phase.__name__}: {type(e).__name__}: {e}")
        return None


def main() -> int:
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this run needs one",
              file=sys.stderr)
        return 2

    import jax.numpy as jnp
    from repro import configs
    from repro.kernels._compat import interpret_mode_info
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T

    print(f"compile cache: {enable_compile_cache()}")
    mode = interpret_mode_info()
    print(f"pallas mode: {mode}")
    failures = []
    if mode["interpret"] or mode["source"] != "auto":
        failures.append(f"pallas kernels would not run compiled: {mode}")

    cfg = configs.get(ARCH)
    params = jax.jit(lambda k: T.init_params(cfg, k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(SEED))
    print(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"vocab={cfg.vocab_size} params={T.count_params(params)} bf16")

    _guarded(failures, phase_a, cfg, params, PROMPT_LEN_A, SEED, failures)
    info = _guarded(failures, phase_b, cfg, params, SEED, failures)
    if info is not None:
        print(f"phase B: backend {info['name']} interpret={info['interpret']} "
              f"({info['source']})")
        if (info["name"], info["interpret"], info["source"]) != (
                "pallas", False, "auto"):
            failures.append(f"phase B: backend did not run compiled: {info}")
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
