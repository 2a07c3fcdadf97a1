"""Error-feedback int8 gradient compression for the DP all-reduce.

Standard 1-bit-Adam-style trick adapted to int8: quantize (grad + error
carryover) per-tensor to int8, synchronize the *compressed* values
(all-gather int8 + local sum — 4× less wire traffic than an f32
all-reduce), and carry the quantization residual into the next step so
the compression bias telescopes away.

Exposed two ways:
  * `compress/decompress` — pure functions used by the optimizer wrapper
    and unit tests;
  * `compressed_psum_shard_map` — the shard_map collective that replaces
    `psum(grads)` in the train step when `grad_compression=True`.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

INT8_MAX = 127.0


def compress(g: jax.Array, err: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(grad, error) → (int8 codes, scale, new error)."""
    corrected = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(corrected)), 1e-12) / INT8_MAX
    codes = jnp.clip(
        jnp.round(corrected / scale), -INT8_MAX, INT8_MAX
    ).astype(jnp.int8)
    new_err = corrected - codes.astype(jnp.float32) * scale
    return codes, scale, new_err


def decompress(codes: jax.Array, scale: jax.Array) -> jax.Array:
    return codes.astype(jnp.float32) * scale


def init_error_state(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _psum_compressed_leaf(g, err, axis_names):
    codes, scale, new_err = compress(g, err)
    # Wire format: int8 codes (+1 f32 scale) per shard. all_gather moves
    # int8; the sum happens locally in f32.
    gathered = jax.lax.all_gather(codes, axis_names, tiled=False)
    scales = jax.lax.all_gather(scale, axis_names, tiled=False)
    flat = gathered.reshape((-1,) + g.shape)
    fscales = scales.reshape((-1,) + (1,) * g.ndim)
    summed = jnp.sum(flat.astype(jnp.float32) * fscales, axis=0)
    return summed, new_err


def compressed_psum(grads: Any, err_state: Any, axis_names) -> Tuple[Any, Any]:
    """Sum gradients over ``axis_names`` with int8 error-feedback.

    Must run inside shard_map / with named axes in scope. Returns
    (summed grads, new error state).
    """
    flat_g, tree = jax.tree_util.tree_flatten(grads)
    flat_e = tree.flatten_up_to(err_state)
    out, errs = [], []
    for g, e in zip(flat_g, flat_e):
        s, ne = _psum_compressed_leaf(g, e, axis_names)
        out.append(s)
        errs.append(ne)
    return tree.unflatten(out), tree.unflatten(errs)


def compressed_psum_shard_map(
    grads: Any, err_state: Any, mesh: Mesh, axis_names: Tuple[str, ...]
):
    """Wrap :func:`compressed_psum` in shard_map over replicated grads.

    Used when the train step computes per-DP-shard gradients manually
    (shard_map data parallelism) rather than via pjit auto-reduction.
    """

    def body(g, e):
        return compressed_psum(g, e, axis_names)

    specs_g = jax.tree.map(lambda _: P(), grads)
    specs_e = jax.tree.map(lambda _: P(), err_state)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs_g, specs_e),
        out_specs=(specs_g, specs_e),
        check_vma=False,
    )(grads, err_state)
