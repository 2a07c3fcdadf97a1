"""Operations and bytes of one MP-MRF decode filter call (one layer,
one decode step), as the algorithm defines them.

Every slot's query group scores every live key row of its cache from
the bits the two rounds need: with Fig. 7 result reuse the query's 4-bit
plane meets the key's top 2 bits, then its next 2, so a key element
costs 4 bits read and two int8 multiply-adds per query head of its
group. The query plane (one byte an element) and one float32 scale per
live page are read too.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple


def cost(cfg: Dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """``(int8 ops, bytes)`` for slots whose caches hold ``lengths``
    rows when the kernel runs (idle slots included, as it scores them)."""
    e = cfg["energon"]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    g = cfg["num_attention_heads"] // kv
    bits = e["round_bits"][-1]
    rows = float(sum(lengths))
    pages = float(sum(math.ceil(n / e["decode_key_block"]) for n in lengths))
    ops = 2 * 2 * rows * kv * g * hd
    nbytes = rows * kv * hd * bits / 8 + len(lengths) * kv * g * hd \
        + pages * kv * 4
    return ops, nbytes
