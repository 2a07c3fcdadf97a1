"""The system under test, built from a configuration file.

This is the one module of the benchmark that imports the program
(``src/repro``): it turns a configuration dict into the program's model
config, builds ``ServeLoop`` over the benchmark's weights, and names
the program's step functions, counters and kernels that the benchmark
reads. Nothing here measures or judges.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402

from bench import weights as W  # noqa: E402

#: kernel names as the program's Pallas calls declare them
DECODE_KERNELS = ("mpmrf_decode_filter", "mpmrf_decode_gather")
#: the jitted decode step, as its module is named in a device trace
DECODE_MODULE = "jit_decode_step"


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration dict."""
    from repro.configs.base import ModelConfig
    from repro.core import EnergonConfig

    e = cfg["energon"]
    energon = EnergonConfig(
        impl=e["impl"],
        round_bits=tuple(e["round_bits"]),
        alphas=tuple(float(a) for a in e["alphas"]),
        query_block=e["query_block"],
        key_block=e["key_block"],
        pruning_ratio=float(e["pruning_ratio"]),
        decode_key_block=e["decode_key_block"],
        keep_first=e["keep_first"],
        keep_diagonal=e["keep_diagonal"],
        reuse_partial=e["reuse_partial"],
        min_prune_layer=e["min_prune_layer"],
        filter_cache_min_len=e.get("filter_cache_min_len"),
        **({"chunk_threshold": e["chunk_threshold"]}
           if "chunk_threshold" in e else {}),
    )
    if cfg["hidden_act"] != "silu":
        raise ValueError("only SwiGLU MLPs are configured")
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        use_qk_norm=bool(cfg["qk_norm"]),
        activation="swiglu",
        norm="rmsnorm",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"],
        remat="none",
        energon=energon,
    )


def build_engine(cfg: Dict[str, Any], engine_args: Dict[str, Any],
                 seed: int):
    """``ServeLoop`` over the benchmark's weights, with the pool, slots,
    ``max_len`` and chunk the cell names."""
    from repro.models import LMModel
    from repro.runtime import ServeLoop

    model = LMModel(model_config(cfg))
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), W.params_shapes(cfg))
    if want != have:
        raise ValueError("the benchmark's weight tree does not match the "
                         "program's parameter layout")
    params = W.make_params(cfg, seed)
    engine = ServeLoop(
        model, params,
        batch_slots=engine_args["batch_slots"],
        max_len=engine_args["max_len"],
        prefill_chunk=engine_args["prefill_chunk"],
        num_pages=cfg["pool_pages"],
        eos_token=-1,                # sampled ids are >= 0: never stops early
        rng=W.seed_key(seed),
    )
    if engine.max_len != engine_args["max_len"]:
        raise ValueError(f"max_len {engine_args['max_len']} is not whole "
                         f"pages (engine rounds to {engine.max_len})")
    return engine


def request(uid: int, prompt, max_new_tokens: int, on_token):
    from repro.runtime import Request

    return Request(uid=uid, prompt=list(prompt),
                   max_new_tokens=max_new_tokens, temperature=0.0,
                   on_token=on_token)


def engine_counters(engine) -> Dict[str, float]:
    """The scheduler's own counters (``EngineMetrics``)."""
    m = engine.metrics
    return {
        "prefill_tokens": m.prefill_tokens,
        "prefill_tokens_skipped": m.prefill_tokens_skipped,
        "prefix_hits": m.prefix_hits,
        "prefix_lookups": m.prefix_lookups,
        "decode_tokens": m.decode_tokens,
        "prefill_dispatches": m.prefill_dispatches,
        "decode_dispatches": m.decode_dispatches,
        "preemptions": m.preemptions,
        "ticks": m.ticks,
        "prefill_time": m.prefill_time,
        "decode_time": m.decode_time,
        "peak_pages_in_use": m.peak_pages_in_use,
    }


def decode_lengths(engine):
    """Host mirror of each slot's written rows before a decode step."""
    return engine._lengths.copy()


def fused_paths(engine) -> bool:
    """True when the engine serves through the paged pool with resident
    filter planes, prefix sharing and the fused kernels selected."""
    return (engine.paged and engine.sharing and "k_codes" in engine.cache
            and engine.model.cfg.energon.impl == "pallas")
