"""Seeded bf16 weights for a dense decoder, made on the device in one call.

The tree has the layout the serving program consumes (layer-stacked leaves
under ``layers``), and is also what :mod:`bench.reference` reads, so this
module imports nothing of the program.  Norm gains and q/k/v biases are drawn
non-zero, so the comparison with the reference covers them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number, beyond 32 bits too."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(dims: dict) -> dict:
    """``{path: (shape, std)}`` for every leaf; ``dims`` uses the model's
    published key names (``hidden_size``, ``num_hidden_layers``, ...)."""
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


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, last = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(dims: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights, drawn on the default device in one jitted call."""
    spec = shapes(dims)

    @jax.jit
    def draw(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dtype)
        return _nest(flat)

    return draw(seed_key(seed))
