"""Shared neural layers: norms, RoPE, MLPs, embeddings.

Pure-functional style: ``init_*`` builds a param pytree (dicts of
jnp arrays) tagged with logical sharding axes via
``repro.distributed.sharding.logical`` metadata; ``apply_*`` consumes it.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, std: float, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def init_linear(key, d_in: int, d_out: int, dtype=jnp.float32):
    return trunc_normal(key, (d_in, d_out), std=d_in ** -0.5, dtype=dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 statistics but input-dtype application.

    The second moment is a *self-dot with f32 accumulation* rather than
    ``square(x.astype(f32))``: an explicit convert as the block's first
    op gets batch-hoisted by XLA out of the backward layer loop,
    materializing an f32 copy of the whole stacked residual buffer
    (L × activations of HBM). A dot accumulates in f32 on the MXU with
    no hoistable convert, identical numerics.
    """
    d = x.shape[-1]
    var = (
        jnp.einsum(
            "...d,...d->...", x, x, preferred_element_type=jnp.float32
        )[..., None]
        / d
    )
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * params["scale"].astype(x.dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """LayerNorm, f32 statistics / input-dtype application (see rmsnorm)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = (x - mu.astype(x.dtype)) * inv
    return out * params["scale"].astype(x.dtype) + params["bias"].astype(
        x.dtype
    )


def apply_norm(kind: str, params, x: jax.Array) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(params, x)
    if kind == "layernorm":
        return layernorm(params, x)
    raise ValueError(f"unknown norm {kind}")


def init_norm(kind: str, d: int, dtype=jnp.float32):
    if kind == "rmsnorm":
        return init_rmsnorm(d, dtype)
    if kind == "layernorm":
        return init_layernorm(d, dtype)
    raise ValueError(f"unknown norm {kind}")


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 10000.0,
) -> jax.Array:
    """Rotary embedding. x ``[..., n, num_heads, head_dim]`` (head-last),
    positions ``[..., n]`` int32 (broadcastable to x's batch+seq dims)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., n, hd/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "w_up": init_linear(k1, d_model, d_ff, dtype),
        "w_down": init_linear(k2, d_ff, d_model, dtype),
    }
    if activation in ("swiglu", "geglu"):
        params["w_gate"] = init_linear(k3, d_model, d_ff, dtype)
    return params


def apply_mlp(params, x: jax.Array, activation: str) -> jax.Array:
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if activation == "swiglu":
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        h = jax.nn.silu(gate) * up
    elif activation == "geglu":
        gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
        h = jax.nn.gelu(gate) * up
    elif activation == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown activation {activation}")
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ---------------------------------------------------------------------------
# embeddings / LM head
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": trunc_normal(key, (vocab, d_model), std=1.0, dtype=dtype)}


def _sharded_embed_lookup(table: jax.Array, tokens: jax.Array, mesh):
    """Distributed embedding gather over a vocab-sharded table.

    Each 'model' shard gathers the rows it owns (masked) and the shards
    psum — the standard TP embedding pattern. XLA's auto-partitioner
    cannot do this for us (it replicates the table, or worse).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    dp = shd.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_axis = dp if (tokens.shape[0] % dp_size == 0
                        and tokens.shape[0] > 1) else None

    def local(table_shard, tokens_local):
        shard_id = jax.lax.axis_index("model")
        vocab_per = table_shard.shape[0]
        local_idx = tokens_local - shard_id * vocab_per
        ok = jnp.logical_and(local_idx >= 0, local_idx < vocab_per)
        safe = jnp.clip(local_idx, 0, vocab_per - 1)
        out = jnp.take(table_shard, safe, axis=0)
        out = jnp.where(ok[..., None], out, 0)
        return jax.lax.psum(out, "model")

    token_spec = P(batch_axis, *([None] * (tokens.ndim - 1)))
    out_spec = P(batch_axis, *([None] * tokens.ndim))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), token_spec),
        out_specs=out_spec, check_vma=False,
    )(table, tokens)


def embed_tokens(params, tokens: jax.Array) -> jax.Array:
    from repro.distributed import sharding as shd

    table = params["table"]
    mesh = shd.get_active_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and table.shape[0] % mesh.shape["model"] == 0):
        return _sharded_embed_lookup(table, tokens, mesh)
    return jnp.take(table, tokens, axis=0)


def init_lm_head(key, d_model: int, vocab: int, dtype=jnp.float32):
    return {"w": init_linear(key, d_model, vocab, dtype)}


def lm_logits(params, x: jax.Array) -> jax.Array:
    return jnp.einsum(
        "...d,dv->...v", x, params["w"],
        preferred_element_type=jnp.float32,
    )


def tied_lm_logits(embed_params, x: jax.Array) -> jax.Array:
    return jnp.einsum(
        "...d,vd->...v", x, embed_params["table"],
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over valid positions. Returns (loss, #valid_tokens).

    The gold logit is selected with an iota-compare-reduce rather than a
    gather: on a vocab-sharded logits tensor this lowers to a local
    masked reduction + psum instead of a cross-shard gather (which the
    SPMD partitioner can only realize by replicating the logits).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1
    )
    gold = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / total, total
