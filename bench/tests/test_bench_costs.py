"""``bench/costs`` against counts made by hand at a small shape."""

import pytest

from bench.costs import decode_step, mpmrf_decode_filter, \
    mpmrf_decode_gather

CFG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256,
    "energon": {"round_bits": [2, 4], "decode_key_block": 64,
                "pruning_ratio": 4.0, "keep_first": True,
                "keep_diagonal": True, "min_prune_layer": 2},
}


def test_filter_by_hand():
    # two slots of 100 and 300 rows; KV 2, G 2, hd 16, 4 bits
    ops, nbytes = mpmrf_decode_filter.cost(CFG, [100, 300])
    assert ops == 2 * 2 * 400 * 2 * 2 * 16
    codes = 400 * 2 * 16 * 4 / 8
    qplane = 2 * 2 * 2 * 16
    scales = (2 + 5) * 2 * 4
    assert nbytes == codes + qplane + scales


@pytest.mark.parametrize("n,rows", [
    (1, 1),            # one row: the newest block is the sink
    (64, 64),          # one full block
    (100, 100),        # 2 blocks, both pinned: 64 + 36
    (300, 64 + 44),    # 5 blocks, budget 2: sink + newest (44 rows)
    (640, 64 * 2 + 64),    # 10 blocks, budget 3: 2 full + newest full
    (650, 64 * 2 + 10),    # 11 blocks, budget 3: newest holds 10 rows
])
def test_gather_kept_rows(n, rows):
    assert mpmrf_decode_gather.kept_rows(CFG, n) == rows


def test_gather_cost_by_hand():
    flops, nbytes = mpmrf_decode_gather.cost(CFG, [300])
    rows = 108
    assert flops == 4 * 4 * rows * 16
    assert nbytes == rows * 2 * 16 * 2 * 2


def test_decode_step_flops_by_hand():
    layer = 64 * 16 * (2 * 4 + 2 * 2) + 3 * 64 * 128
    per_token = 2 * (4 * layer + 64 * 256)
    attn = 4 * 4 * 16 * (2 * 300 + 2 * 108)
    assert decode_step.flops(CFG, [300]) == per_token + attn
