"""Operations and bytes of one MP-MRF decode gather call (one layer,
one decode step), as the algorithm defines them.

Each slot attends the pages its selection keeps: the live budget
ceil(ceil(len / page) / rho) blocks, at least the pinned sink and newest
blocks, and no more than the cache holds. The newest page counts only
its written rows. K and V rows are read once in bfloat16; each kept row
costs two multiply-adds (QK and PV) per query head and head dimension.
"""

from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple


def kept_rows(cfg: Dict, n: int) -> int:
    """Rows one slot with ``n`` cached rows attends."""
    e = cfg["energon"]
    ps = e["decode_key_block"]
    blocks = math.ceil(n / ps)
    pinned = len({0, blocks - 1} if e["keep_first"] else {blocks - 1}) \
        if e["keep_diagonal"] else int(e["keep_first"])
    budget = max(1, math.ceil(blocks / e["pruning_ratio"]))
    kept = min(max(budget, pinned), blocks)
    return (kept - 1) * ps + (n - (blocks - 1) * ps)


def cost(cfg: Dict, lengths: Sequence[int]) -> Tuple[float, float]:
    """``(bf16 flops, bytes)`` for slots holding ``lengths`` rows."""
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    h = cfg["num_attention_heads"]
    rows = float(sum(kept_rows(cfg, n) for n in lengths))
    return 4 * h * rows * hd, rows * kv * hd * 2 * 2
