"""Model FLOPs of one decode step: what the served tokens require.

Per active token, 2 FLOPs per weight it multiplies (every layer's
attention and MLP matrices and the output head; the embedding is a
lookup), plus attention as MP-MRF defines it: QK and PV over every
cached row in the dense leading layers and over the kept rows
(``mpmrf_decode_gather.kept_rows``) in the pruned ones. The filter's
integer scoring is counted by its own kernel, not here.
"""

from __future__ import annotations

from typing import Dict, Sequence

from bench.costs.mpmrf_decode_gather import kept_rows


def weights_per_token(cfg: Dict) -> float:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    layer = d * hd * (2 * h + 2 * kv) + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def flops(cfg: Dict, lengths: Sequence[int]) -> float:
    """FLOPs of the tokens of active slots with ``lengths`` cached rows
    (the new token's row included)."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    dense = cfg["energon"]["min_prune_layer"]
    sparse = cfg["num_hidden_layers"] - dense
    total = 0.0
    for n in lengths:
        total += 2 * weights_per_token(cfg)
        total += 4 * h * hd * (dense * n + sparse * kept_rows(cfg, n))
    return total
