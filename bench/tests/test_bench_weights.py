"""Weights made by the benchmark: one layer drawn alone equals the same
layer in the stacked tree, on any 64-bit seed."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

CFG = {
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "vocab_size": 64, "torch_dtype": "bfloat16", "initializer_range": 0.02,
    "qk_norm": True, "tie_word_embeddings": False,
}


def test_layer_alone_equals_stacked():
    seed = 2 ** 40 + 3
    params = W.make_params(CFG, seed)
    key = W.seed_key(seed)
    for i in range(CFG["num_hidden_layers"]):
        alone = W.layer_weights(CFG, key, i)
        assert np.array_equal(np.asarray(alone["wq"]),
                              np.asarray(params["blocks"]["attn"]["wq"][i]))
        assert np.array_equal(np.asarray(alone["w_down"]),
                              np.asarray(params["blocks"]["mlp"]["w_down"][i]))
    assert params["blocks"]["attn"]["wq"].dtype == jnp.bfloat16
    std = float(jnp.std(params["blocks"]["mlp"]["w_up"].astype(jnp.float32)))
    assert abs(std - 0.02) < 0.002


def test_seeds_differ_and_repeat():
    a = W.embedding_table(CFG, W.seed_key(5))
    b = W.embedding_table(CFG, W.seed_key(5))
    c = W.embedding_table(CFG, W.seed_key(5 + 2 ** 32))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert W.seed_key(2 ** 63).shape == jax.random.PRNGKey(0).shape
