"""Decoder transformer block + scan-over-layers stack.

Layer parameters are stacked on a leading ``layers`` axis and the stack
runs as one ``jax.lax.scan`` so HLO size (and compile time) is O(1) in
depth — essential for lowering 94-layer configs against a 512-device
mesh. Per-layer heterogeneity (gemma's 5:1 local:global pattern, MoE
placement) is expressed as *data*: scanned per-layer arrays (window
sizes, flags), not per-layer Python code.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import EnergonConfig
from repro.distributed import sharding as shd
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib


def init_block(
    key,
    *,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    d_ff: int,
    activation: str,
    norm: str,
    use_qk_norm: bool,
    moe_cfg: Optional[moe_lib.MoEConfig] = None,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    k_a, k_m = jax.random.split(key)
    params = {
        "norm_attn": L.init_norm(norm, d_model, dtype),
        "attn": attn.init_attention(
            k_a, d_model, num_heads, num_kv_heads, head_dim,
            use_qk_norm=use_qk_norm, dtype=dtype,
        ),
        "norm_mlp": L.init_norm(norm, d_model, dtype),
    }
    if moe_cfg is not None:
        params["moe"] = moe_lib.init_moe(k_m, moe_cfg, dtype)
    else:
        params["mlp"] = L.init_mlp(k_m, d_model, d_ff, activation, dtype)
    return params


def apply_block(
    params,
    x: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float,
    use_qk_norm: bool,
    activation: str,
    norm: str,
    window: Optional[jax.Array] = None,
    layer_index: int = 10**9,
    moe_cfg: Optional[moe_lib.MoEConfig] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm decoder block. Returns (x, aux_loss)."""
    h = attn.attention_block(
        params["attn"],
        L.apply_norm(norm, params["norm_attn"], x),
        energon,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        rope_theta=rope_theta,
        use_qk_norm=use_qk_norm,
        window=window,
        layer_index=layer_index,
    )
    x = x + h
    h_in = L.apply_norm(norm, params["norm_mlp"], x)
    if moe_cfg is not None:
        h, metrics = moe_lib.apply_moe(params["moe"], h_in, moe_cfg)
        aux = metrics["moe_aux_loss"]
    else:
        h = L.apply_mlp(params["mlp"], h_in, activation)
        aux = jnp.zeros((), jnp.float32)
    return x + h, aux


def init_stack(
    key,
    num_layers: int,
    init_one,
) -> Dict[str, Any]:
    """Stack ``num_layers`` copies of ``init_one(key)`` on a leading axis."""
    keys = jax.random.split(key, num_layers)
    return jax.vmap(init_one)(keys)


def _tree_slice(tree, lo: int, hi: Optional[int]):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def _scan_factors(n: int) -> Tuple[int, int]:
    """(outer, inner) factorization minimizing outer+inner (≈2√n).

    Used for the two-level rematerialized layer scan: the backward saves
    ``outer`` group-entry carries plus ``inner`` within-group carries
    instead of all ``n`` — sqrt-style activation checkpointing across
    depth. (1, n) when n is prime or tiny.
    """
    if n < 6:
        return 1, n
    best = (1, n)
    for a in range(2, int(n ** 0.5) + 1):
        if n % a == 0:
            best = (n // a, a)
    # prefer more outer steps than inner (outer carries dominate savings)
    outer, inner = best
    if outer < inner:
        outer, inner = inner, outer
    return (outer, inner) if outer * inner == n and outer > 1 else (1, n)


def apply_stack(
    params_stacked,
    x: jax.Array,
    windows: Optional[jax.Array],
    block_fn,
    *,
    remat: str = "none",
    prefix_layers: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Scan ``block_fn(params, x, window, layer_idx) -> (x, aux)`` over
    the stacked layer axis. ``windows``: optional int32 ``[L]`` per-layer
    sliding-window sizes (0 ⇒ full causal).

    ``prefix_layers`` — the paper never prunes the first blocks (§III-A);
    Energon's layer gate is *static*, so the stack runs as two scans: the
    prefix with ``layer_idx=0`` (dense attention) and the rest with
    ``layer_idx=prefix_layers`` (MP-MRF active). HLO stays O(1) in depth.
    """
    num_layers = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    if windows is None:
        windows = jnp.zeros((num_layers,), jnp.int32)
    prefix_layers = min(prefix_layers, num_layers)

    def make_body(static_layer_idx: int):
        def body(carry, xs):
            x, aux = carry
            # Barrier: the first op of every block upcasts x (norm in
            # f32). Without this, XLA batch-converts the WHOLE stacked
            # residual buffer to f32 outside the backward loop — an
            # L × activation-size f32 copy (11.8 GB/chip on the 94-layer
            # MoE). The barrier pins the convert inside the loop body.
            x = jax.lax.optimization_barrier(x)
            layer_params, window = xs
            fn = block_fn
            if remat == "full":
                fn = jax.checkpoint(block_fn, static_argnums=(3,))
            elif remat == "dots":
                fn = jax.checkpoint(
                    block_fn,
                    policy=(
                        jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable
                    ),
                    static_argnums=(3,),
                )
            x, a = fn(layer_params, x, window, static_layer_idx)
            # keep remat-saved residuals batch-sharded inside the scan
            x = shd.constrain(x, ("dp", None, None))
            return (x, aux + a), None

        return body

    def run_scan(carry, params_slice, windows_slice, layer_idx: int):
        """Two-level √L scan: outer scan over rematted layer groups."""
        n = jax.tree_util.tree_leaves(params_slice)[0].shape[0]
        outer, inner = _scan_factors(n)
        body = make_body(layer_idx)
        if outer == 1:
            carry, _ = jax.lax.scan(body, carry, (params_slice, windows_slice))
            return carry

        regroup = lambda a: a.reshape((outer, inner) + a.shape[1:])
        params_2l = jax.tree.map(regroup, params_slice)
        windows_2l = windows_slice.reshape(outer, inner)

        def group(carry, xs):
            carry, _ = jax.lax.scan(body, carry, xs)
            return carry

        def outer_body(carry, xs):
            fn = group
            if remat != "none":
                fn = jax.checkpoint(group)
            return fn(carry, xs), None

        carry, _ = jax.lax.scan(outer_body, carry, (params_2l, windows_2l))
        return carry

    carry = (x, jnp.zeros((), jnp.float32))
    if prefix_layers > 0:
        carry = run_scan(
            carry,
            _tree_slice(params_stacked, 0, prefix_layers),
            windows[:prefix_layers], 0,
        )
    if prefix_layers < num_layers:
        carry = run_scan(
            carry,
            _tree_slice(params_stacked, prefix_layers, None),
            windows[prefix_layers:], prefix_layers,
        )
    return carry


def apply_stack_decode(
    params_stacked,
    x: jax.Array,
    caches,
    windows: Optional[jax.Array],
    step_fn,
    *,
    prefix_layers: int = 0,
    telemetry: bool = False,
):
    """Scan a decode step over layers, threading per-layer caches.

    ``step_fn(params, x, cache, window, layer_idx) -> (x, new_cache)``
    with ``layer_idx`` static (see :func:`apply_stack`). With
    ``telemetry`` the step returns ``(x, new_cache, stats [B, 4])``;
    per-layer stats ride the scan's stacked outputs and come back as
    int32 ``[L, B, 4]``.

    The scans run over layer *indices*: each step reads its layer's
    weights and cache in place from the stacked arrays and writes the
    cache back into the carried stack. Slicing the stacks per scan
    (prefix / rest) would copy every weight and cache leaf outside the
    loop — at full width that doubles the weights' footprint and does
    not fit the device.
    """
    num_layers = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    if windows is None:
        windows = jnp.zeros((num_layers,), jnp.int32)
    prefix_layers = min(prefix_layers, num_layers)

    def layer(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree,
        )

    def make_body(static_layer_idx: int):
        def body(carry, xs):
            x, caches = carry
            i, window = xs
            out = step_fn(
                layer(params_stacked, i), x, layer(caches, i), window,
                static_layer_idx,
            )
            x, new_cache = out[0], out[1]
            caches = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, u.astype(a.dtype), i, 0
                ),
                caches, new_cache,
            )
            x = shd.constrain(x, ("dp", None, None))
            return (x, caches), (out[2] if telemetry else None)

        return body

    stats_parts = []
    for lo, hi in ((0, prefix_layers), (prefix_layers, num_layers)):
        if lo < hi:
            (x, caches), stats = jax.lax.scan(
                make_body(lo), (x, caches),
                (jnp.arange(lo, hi), windows[lo:hi]),
            )
            stats_parts.append(stats)
    if telemetry:
        stats = (
            stats_parts[0] if len(stats_parts) == 1
            else jnp.concatenate(stats_parts, axis=0)
        )
        return x, caches, stats
    return x, caches
