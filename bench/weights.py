"""Seeded weights of a configuration, made by the benchmark.

The benchmark, not the system under test, owns the weights: the served
program gets the whole tree from :func:`make_params` (one jitted call on
the device, in bfloat16), and the plain reference rebuilds each layer
alone with :func:`layer_weights` from the same seed. Each leaf of layer
``i`` is drawn from its own key, ``fold_in(fold_in(seed_key, leaf), i)``,
so a layer drawn alone equals the same layer inside the stacked tree.

Weights are N(0, initializer_range^2), the published initializer of
both configurations, cast to the served dtype; norm gains are 1.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

# stable leaf ids: changing one changes every weight drawn for it
_LEAF_IDS = {
    "embed": 1, "lm_head": 2,
    "wq": 10, "wk": 11, "wv": 12, "wo": 13,
    "w_up": 20, "w_gate": 21, "w_down": 22,
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def served_dtype(cfg: Dict[str, Any]):
    return jnp.dtype(cfg["torch_dtype"])


def block_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    return {
        "wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
        "wo": (h, hd, d),
        "w_up": (d, ff), "w_gate": (d, ff), "w_down": (ff, d),
    }


def _leaf(key, name: str, index: int, shape, std: float, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_IDS[name]), index)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def layer_weights(cfg: Dict[str, Any], key, index: int) -> Dict[str, Any]:
    """Layer ``index``'s matrices, flat, in the served dtype."""
    std = cfg["initializer_range"]
    dt = served_dtype(cfg)
    return {name: _leaf(key, name, index, shape, std, dt)
            for name, shape in block_shapes(cfg).items()}


def embedding_table(cfg: Dict[str, Any], key) -> jax.Array:
    return _leaf(key, "embed", 0, (cfg["vocab_size"], cfg["hidden_size"]),
                 cfg["initializer_range"], served_dtype(cfg))


def head_matrix(cfg: Dict[str, Any], key) -> jax.Array:
    return _leaf(key, "lm_head", 0, (cfg["hidden_size"], cfg["vocab_size"]),
                 cfg["initializer_range"], served_dtype(cfg))


def _params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    n = cfg["num_hidden_layers"]
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    dt = served_dtype(cfg)
    flat = jax.vmap(lambda i: layer_weights(cfg, key, i))(jnp.arange(n))
    ones = lambda *s: jnp.ones(s, dt)  # noqa: E731
    attn = {k: flat[k] for k in ("wq", "wk", "wv", "wo")}
    if cfg["qk_norm"]:
        attn["q_norm"] = {"scale": ones(n, hd)}
        attn["k_norm"] = {"scale": ones(n, hd)}
    blocks = {
        "norm_attn": {"scale": ones(n, d)},
        "attn": attn,
        "norm_mlp": {"scale": ones(n, d)},
        "mlp": {k: flat[k] for k in ("w_up", "w_gate", "w_down")},
    }
    params = {
        "embed": {"table": embedding_table(cfg, key)},
        "final_norm": {"scale": ones(d)},
        "blocks": blocks,
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = {"w": head_matrix(cfg, key)}
    return params


def make_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The whole served tree, built on the default device in one call."""
    return jax.jit(lambda k: _params(cfg, k))(seed_key(seed))


def params_shapes(cfg: Dict[str, Any]):
    return jax.eval_shape(lambda k: _params(cfg, k), seed_key(0))
