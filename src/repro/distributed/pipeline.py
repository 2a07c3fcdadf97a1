"""GPipe-style pipeline parallelism over the ``pod`` axis.

`shard_map` + `collective_permute` implementation: stage s holds the
parameters of layer-slab s (leading dim of the stacked params is sharded
over the pipeline axis). Microbatches stream through the classic GPipe
schedule — ``num_micro + num_stages - 1`` ticks, each tick running every
stage in parallel on its current microbatch and rotating activations to
the next stage.

This is the optional multi-pod alternative to pure pod-DP: with 2 pods,
stage 0 = layers [0, L/2) on pod 0, stage 1 = layers [L/2, L) on pod 1,
and ICI traffic between pods is one activation tensor per tick instead
of a full gradient all-reduce.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run ``stage_fn`` as a pipeline over ``axis``.

    Args:
      stage_fn: ``(params_for_one_stage, x) -> x`` applied by each stage.
      stage_params: pytree whose leaves have leading dim = num_stages
        (sharded over ``axis`` by shard_map).
      microbatches: ``[num_micro, micro_batch, ...]`` activations,
        replicated across ``axis``.
      mesh: mesh containing ``axis``.

    Returns:
      ``[num_micro, micro_batch, ...]`` outputs of the final stage.
    """
    num_stages = mesh.shape[axis]
    num_micro = microbatches.shape[0]
    total_ticks = num_micro + num_stages - 1

    def per_stage(params, mb):
        # Inside shard_map: params leaves have leading dim 1 (this
        # stage's slab); mb is the full microbatch array (replicated).
        params = jax.tree.map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)

        state = jnp.zeros_like(mb[0])  # current activation at this stage

        def tick(carry, t):
            state, outputs = carry
            # Stage 0 ingests microbatch t (when in range).
            mb_idx = jnp.clip(t, 0, num_micro - 1)
            inject = jnp.where(
                jnp.logical_and(stage_id == 0, t < num_micro),
                mb[mb_idx],
                state,
            )
            out = stage_fn(params, inject)
            # Rotate stage outputs forward: stage s -> s+1.
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            rotated = jax.lax.ppermute(out, axis, perm)
            # Last stage emits microbatch t - (num_stages - 1).
            emit_idx = t - (num_stages - 1)
            should_emit = jnp.logical_and(
                stage_id == num_stages - 1, emit_idx >= 0
            )
            # Every device stores into the same slot; only the last
            # stage's value is kept after the psum-gather below.
            safe_idx = jnp.clip(emit_idx, 0, num_micro - 1)
            outputs = outputs.at[safe_idx].set(
                jnp.where(should_emit, out, outputs[safe_idx])
            )
            return (rotated, outputs), None

        outputs0 = jnp.zeros_like(mb)
        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs0), jnp.arange(total_ticks)
        )
        # Only the final stage holds real outputs; broadcast them.
        is_last = (stage_id == num_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * is_last, axis)

    in_spec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(in_spec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def split_layers_to_stages(params_stacked: Any, num_stages: int) -> Any:
    """``[L, ...]`` stacked layer params → ``[S, L/S, ...]`` stage slabs."""

    def reshape(a):
        l = a.shape[0]
        if l % num_stages:
            raise ValueError(f"{l} layers not divisible by {num_stages} stages")
        return a.reshape(num_stages, l // num_stages, *a.shape[1:])

    return jax.tree.map(reshape, params_stacked)
