"""Operations and bytes of one dense-layer prefill wave (one layer, one
chunk wave), as the algorithm defines them.

Each live slot's chunk rows attend causally: the token at position p
attends p + 1 keys, and each (token, key) pair costs two multiply-adds
(QK and PV) per query head and head dimension. The slot's K and V rows
up to its written extent are read once in bfloat16, and its live query
rows are read and its output rows written once, in bfloat16, for every
query head.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def cost(cfg: Dict, wave: Sequence[Tuple[int, int]]) -> Tuple[float, float]:
    """``(bf16 flops, bytes)`` for a wave whose live slots each start at
    ``start`` with ``rows`` live query rows."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    pairs = sum(rows * start + rows * (rows + 1) // 2
                for start, rows in wave)
    keys = sum(start + rows for start, rows in wave)
    queries = sum(rows for _, rows in wave)
    return (4.0 * h * hd * pairs,
            keys * kv * hd * 2 * 2 + queries * h * hd * 2 * 2)
