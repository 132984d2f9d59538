"""Operations and bytes the decode path needs, from shapes alone.

Kept with the benchmark so a change to the program cannot change the
yardstick; ``tests/bench`` checks the byte counts against the program's
own accounting (``kv_cache.pool_block_nbytes``, ``ops.decode_block_report``).
What depends on the block's equations (operations a token passes through,
how much of the cache each layer attends over) is the family's
(``bench/families/<family>.py``); bytes per token and the sums over steps
are here.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Tuple

from . import spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _planes(head_dim: int, bits: float):
    """(width, bits) of each packed plane: a fractional width splits the
    channels into a higher-bit half and a lower-bit half, each a multiple
    of 8 channels."""
    split = {1.5: (2, 1), 3.0: (4, 2)}
    if bits not in split:
        return [(head_dim, int(bits))]
    hi_bits, lo_bits = split[bits]
    d_hi = max(head_dim // 2 - (head_dim // 2) % 8, 8)
    return [(d_hi, hi_bits), (head_dim - d_hi, lo_bits)]


def packed_bytes(head_dim: int, bits: float, group_size: int,
                 meta_bytes: int = 1) -> int:
    """Bytes of one token of one KV head in the packed cache: codes plus a
    scale and a zero point per group of each plane."""
    total = 0
    for width, b in _planes(head_dim, bits):
        gs = min(group_size, width)
        total += width * b // 8 + 2 * (width // gs) * meta_bytes
    return total


def kv_bytes_per_token_layer(dims: dict, pol: dict) -> int:
    """Packed K and V bytes of one token in one layer (all KV heads)."""
    hd, g = dims["head_dim"], min(pol["group_size"], dims["head_dim"])
    meta = 1 if pol["fp8_meta"] else 2
    return dims["num_key_value_heads"] * (
        packed_bytes(hd, pol["bits_k"], g, meta)
        + packed_bytes(hd, pol["bits_v"], g, meta))


def decode_steps(contexts: Iterable[Tuple[int, int, int]]):
    """Cache lengths (after the step's append) of every decode step of every
    slot: a slot with prompt ``p`` and ``n0`` -> ``n1`` delivered tokens ran
    steps at lengths ``p + n0 ... p + n1 - 1``."""
    for p, n0, n1 in contexts:
        for k in range(n1 - n0):
            yield p + n0 + k


def live_packed_tokens(length: int, pol: dict) -> int:
    """Tokens in the packed (quantized) region at a cache length."""
    return max(0, length - pol["n_sink"] - pol["window"])


def decode_attn_bytes(contexts, dims: dict, pol: dict) -> int:
    """Packed bytes the decode kernel must read over these steps: each
    layer reads every live packed token of the length it attends over
    (the family's ``attended_lengths``) once."""
    fam = spec.family(dims["family"])
    tokens = sum(live_packed_tokens(n, pol) for s in decode_steps(contexts)
                 for n in fam.attended_lengths(dims, s))
    return kv_bytes_per_token_layer(dims, pol) * tokens


def decode_flops(contexts, dims: dict) -> int:
    """Operations of every decode step in ``contexts`` (one token each):
    the family's weight and attention counts."""
    fam = spec.family(dims["family"])
    w = fam.weight_flops_per_token(dims)
    return sum(w + fam.attn_flops(dims, n) for n in decode_steps(contexts))
