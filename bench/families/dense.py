"""The dense family: a pre-norm decoder of identical blocks.

Each block: RMSNorm, q/k/v projections (with bias where
``attention_bias``), rotate-half RoPE from one table for every layer,
grouped-query SKVQ attention with scale ``head_dim ** -0.5`` over the whole
causal context, the output projection, RMSNorm and a SwiGLU MLP of
``intermediate_size``; then a final RMSNorm and the head (tied or not).

A family file gives the harness these, and nothing else
(``bench.spec.family``): ``dims``, ``arch``, ``shapes``, ``tables``,
``layer``, ``head``, ``weight_flops_per_token``, ``attn_flops`` and
``attended_lengths``.  ``arch`` alone imports the serving program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as ref

DIM_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "rope_theta", "rms_norm_eps", "hidden_act",
            "attention_bias", "tie_word_embeddings")


def dims(config: dict, smoke: bool = False) -> dict:
    """The sizes as run (the smoke block overrides them in tests), under
    the model's published key names, and ``family``; every value hashable."""
    out = {k: config[k] for k in DIM_KEYS}
    if smoke:
        out.update(config["smoke"])
    out["family"] = config["family"]
    return out


def arch(d: dict):
    """The program's ``ArchConfig`` for these sizes."""
    from repro.models.config import ArchConfig
    return ArchConfig(
        name="bench", family="dense", n_layers=d["num_hidden_layers"],
        d_model=d["hidden_size"], n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"], head_dim=d["head_dim"],
        d_ff=d["intermediate_size"], vocab_size=d["vocab_size"],
        rope_theta=float(d["rope_theta"]), qkv_bias=bool(d["attention_bias"]),
        mlp_act=d["hidden_act"], tie_embeddings=bool(d["tie_word_embeddings"]),
        norm_eps=float(d["rms_norm_eps"]))


def shapes(dims: dict) -> dict:
    """``{path: (shape, std)}`` for every leaf.  Norm gains and q/k/v biases
    are drawn non-zero, so the comparison with the reference covers them."""
    n, d = dims["num_hidden_layers"], dims["hidden_size"]
    hq, hkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    f, v = dims["intermediate_size"], dims["vocab_size"]
    out = {
        "embed": ((v, d), 0.02),
        "final_norm/w": ((d,), 0.1),
        "layers/norm1/w": ((n, d), 0.1),
        "layers/norm2/w": ((n, d), 0.1),
        "layers/attn/wq": ((n, d, hq * hd), d ** -0.5),
        "layers/attn/wk": ((n, d, hkv * hd), d ** -0.5),
        "layers/attn/wv": ((n, d, hkv * hd), d ** -0.5),
        "layers/attn/wo_attn": ((n, hq * hd, d), (hq * hd) ** -0.5),
        "layers/mlp/wi_gate": ((n, d, f), d ** -0.5),
        "layers/mlp/wi_up": ((n, d, f), d ** -0.5),
        "layers/mlp/wo": ((n, f, d), f ** -0.5),
    }
    if dims["attention_bias"]:
        out["layers/attn/bq"] = ((n, hq * hd), 0.1)
        out["layers/attn/bk"] = ((n, hkv * hd), 0.1)
        out["layers/attn/bv"] = ((n, hkv * hd), 0.1)
    if not dims["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), d ** -0.5)
    return out


def tables(dims: dict, s: int):
    """One RoPE table for positions ``0 .. s - 1``, shared by every layer."""
    return ref.rope_table(s, dims["head_dim"], dims["rope_theta"])


def layer(h, lw, i, tables, n_prompt, dims, pol, prec):
    """One block over the whole sequence ``h`` (S, d) in fp32; every layer
    alike, so ``i`` is not read."""
    s = h.shape[0]
    hq, hkv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                   dims["head_dim"])
    eps = dims["rms_norm_eps"]
    a = lw["attn"]
    x = ref.rms(h, lw["norm1"]["w"], eps)
    q = ref.mm(x, a["wq"], prec)
    k = ref.mm(x, a["wk"], prec)
    v = ref.mm(x, a["wv"], prec)
    if "bq" in a:
        q, k, v = (q + a["bq"].astype(jnp.float32),
                   k + a["bk"].astype(jnp.float32),
                   v + a["bv"].astype(jnp.float32))
    q = ref.rope(q.reshape(s, hq, hd), tables)
    k = ref.rope(k.reshape(s, hkv, hd), tables)
    v = v.reshape(s, hkv, hd)
    if prec == "fp8":
        q, k, v = (ref.fp8_scaled(t, -1) for t in (q, k, v))
    gs = min(pol["group_size"], hd)
    kq = ref.fake_quant(k, pol["bits_k"], gs, pol["fp8_meta"])
    vq = ref.fake_quant(v, pol["bits_v"], gs, pol["fp8_meta"])
    o = ref.attend(q, k, v, kq, vq, n_prompt, pol).reshape(s, hq * hd)
    h = h + ref.mm(o, a["wo_attn"], prec)
    m = lw["mlp"]

    def mlp(xr):
        xn = ref.rms(xr, lw["norm2"]["w"], eps)
        return xr + ref.mm(jax.nn.silu(ref.mm(xn, m["wi_gate"], prec))
                           * ref.mm(xn, m["wi_up"], prec), m["wo"], prec)

    rb = min(ref.ROW_BLOCK, s)
    return jax.lax.map(mlp, h.reshape(s // rb, rb, -1)).reshape(h.shape)


def head(h, params, rows, dims, prec):
    """Final RMSNorm and the head at the scored ``rows``."""
    x = ref.rms(h[rows], params["final_norm"]["w"], dims["rms_norm_eps"])
    w = (params["embed"].T if dims["tie_word_embeddings"]
         else params["lm_head"])
    return ref.mm(x, w, prec)


def weight_flops_per_token(dims: dict) -> int:
    """2 x multiply-adds of the matrices one token passes through."""
    d, f = dims["hidden_size"], dims["intermediate_size"]
    q = dims["num_attention_heads"] * dims["head_dim"]
    kv = dims["num_key_value_heads"] * dims["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return 2 * (dims["num_hidden_layers"] * layer + d * dims["vocab_size"])


def attn_flops(dims: dict, length: int) -> int:
    """Scores and weighted values of one query over ``length`` keys, in all
    layers."""
    q = dims["num_attention_heads"] * dims["head_dim"]
    return 4 * q * length * dims["num_hidden_layers"]


def attended_lengths(dims: dict, length: int) -> list:
    """The cache length each layer attends over at this length: every layer
    sees the whole cache."""
    return [length] * dims["num_hidden_layers"]
