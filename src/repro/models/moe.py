"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Expert-parallel friendly: expert weights carry an ``experts`` leading dim
(sharded over the ``model`` mesh axis); tokens are scattered into
``[experts, capacity, d]`` buffers, so the token→expert reshard lowers to
all-to-all style collectives under GSPMD. Overflowing tokens are dropped
(capacity factor), matching standard production MoE (Switch/GShard);
the router uses softmax-then-top-k with normalized combine weights as in
OLMoE / Qwen3-MoE.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    experts_per_token: int
    d_model: int
    d_ff: int                      # per-expert hidden dim
    activation: str = "swiglu"
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # int8-compress the ZeRO expert-weight all-gathers (halves the
    # dominant collective of large-MoE training). Forward uses the
    # quantized weights (per-expert-row scales); the backward
    # reduce-scatters exact f32 cotangents (custom VJP) — the standard
    # quantized-gather trick.
    quantized_weight_gather: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _quantized_all_gather(w: jax.Array, axis_name: str, gather_axis: int):
    """all_gather with int8 wire format; exact-gradient reduce-scatter."""
    scale = jnp.max(jnp.abs(w), axis=gather_axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    codes = jnp.clip(
        jnp.round(w / scale), -127, 127
    ).astype(jnp.int8)
    codes_g = jax.lax.all_gather(
        codes, axis_name, axis=gather_axis, tiled=True
    )
    # scales are tiny (keepdims over the gathered axis): one scale per
    # shard — broadcast each back over its shard's slice.
    scale_g = jax.lax.all_gather(
        scale, axis_name, axis=gather_axis, tiled=True
    )
    scale_rep = jnp.repeat(
        scale_g, w.shape[gather_axis], axis=gather_axis
    )
    return codes_g.astype(w.dtype) * scale_rep.astype(w.dtype)


def _qag_fwd(w, axis_name, gather_axis):
    return _quantized_all_gather(w, axis_name, gather_axis), w.shape

def _qag_bwd(axis_name, gather_axis, shape, g):
    # exact cotangent: this shard's slice of the (already summed-by-use)
    # gathered-weight gradient — psum_scatter over the gather axis.
    gs = jax.lax.psum_scatter(
        g.astype(jnp.float32), axis_name, scatter_dimension=gather_axis,
        tiled=True,
    )
    return (gs.astype(g.dtype),)


_quantized_all_gather.defvjp(_qag_fwd, _qag_bwd)


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32) -> Dict[str, Any]:
    k_r, k_1, k_2, k_3 = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    params = {
        "router": L.trunc_normal(k_r, (d, e), std=d ** -0.5),  # router in f32
        "w_up": L.trunc_normal(k_1, (e, d, f), std=d ** -0.5, dtype=dtype),
        "w_down": L.trunc_normal(k_2, (e, f, d), std=f ** -0.5, dtype=dtype),
    }
    if cfg.activation in ("swiglu", "geglu"):
        params["w_gate"] = L.trunc_normal(
            k_3, (e, d, f), std=d ** -0.5, dtype=dtype
        )
    return params


def _expert_ffn(params, buf: jax.Array, activation: str) -> jax.Array:
    """buf ``[E, C, d]`` → ``[E, C, d]`` batched over experts."""
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    if activation in ("swiglu", "geglu"):
        gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        act = jax.nn.silu(gate) if activation == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    elif activation == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown activation {activation}")
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def apply_moe(
    params, x: jax.Array, cfg: MoEConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x ``[B, n, d]`` → (out ``[B, n, d]``, metrics incl. aux loss).

    Dispatches to the expert-parallel shard_map implementation when a
    production mesh is active (XLA's auto-partitioner replicates the
    dispatch/combine scatters — measured 9.5 TB/chip of collectives on
    the 235B config); falls back to the single-device reference path
    otherwise.
    """
    from repro.distributed import sharding as shd

    mesh = shd.get_active_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and cfg.num_experts % mesh.shape["model"] == 0):
        if shd.get_rules_profile() == "serve" and x.shape[0] * x.shape[1] <= 4096:
            # decode: tokens are few — replicate them across the mesh and
            # 2D-shard the experts (experts→model × d_ff→data); one tiny
            # psum instead of per-step ZeRO weight gathers.
            return _apply_moe_serve_2d(params, x, cfg, mesh)
        return _apply_moe_sharded(params, x, cfg, mesh)
    return _apply_moe_reference(params, x, cfg)


def _apply_moe_reference(
    params, x: jax.Array, cfg: MoEConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-device scatter-based reference (also the test oracle)."""
    batch, n, d = x.shape
    t = batch * n
    xt = x.reshape(t, d)
    e, k = cfg.num_experts, cfg.experts_per_token

    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32), params["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)               # [T, K]
    top_p = top_p / jnp.maximum(
        jnp.sum(top_p, axis=-1, keepdims=True), 1e-9
    )

    # Position of each (token, slot) within its expert's capacity buffer:
    # cumsum over the flattened (T·K) assignment order.
    flat_e = top_e.reshape(-1)                           # [T*K]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [T*K, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                 # position per expert
    flat_pos = jnp.sum(pos * onehot, axis=-1)            # [T*K]
    capacity = max(1, int(t * k / e * cfg.capacity_factor))
    keep = flat_pos < capacity

    tok_idx = jnp.repeat(jnp.arange(t), k)
    safe_pos = jnp.where(keep, flat_pos, 0)
    # Dispatch: scatter token features into [E, C, d] buffers.
    buf = jnp.zeros((e, capacity, d), xt.dtype)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
    buf = buf.at[flat_e, safe_pos].add(contrib, mode="drop")

    out_buf = _expert_ffn(params, buf, cfg.activation)

    # Combine: gather each slot's output, weight by router prob, sum K.
    gathered = out_buf[flat_e, safe_pos]                 # [T*K, d]
    w = (top_p.reshape(-1) * keep.astype(jnp.float32))[:, None]
    combined = gathered.astype(jnp.float32) * w
    out = jnp.zeros((t, d), jnp.float32).at[tok_idx].add(combined)

    # Load-balancing auxiliary loss (Switch-style).
    me = jnp.mean(probs, axis=0)                                  # [E]
    ce = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0
    )
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)
    metrics = {
        "moe_aux_loss": aux,
        "moe_drop_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return out.reshape(batch, n, d).astype(x.dtype), metrics


def _apply_moe_sharded(
    params, x: jax.Array, cfg: MoEConfig, mesh
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE under shard_map.

    Layout (matches `repro.distributed.sharding` rules):
      router  — replicated (tiny);
      experts — sharded over 'model' (EP) with their d_model dim
                ZeRO-3-sharded over 'data' (all-gathered per layer);
      tokens  — sharded over the data axes, replicated over 'model'.

    Every model shard routes the (identical, replicated) local tokens,
    keeps only assignments to ITS experts, scatters into a local
    [E_local, C, d] buffer (device-local scatter — the op XLA cannot be
    trusted to partition), runs its experts, combines locally and psums
    partial token outputs over 'model'. Collectives per layer: one
    weight all-gather over 'data' (ZeRO) + one activation psum over
    'model' — nothing else.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    batch, n, d = x.shape
    e = cfg.num_experts
    k = cfg.experts_per_token
    model_size = mesh.shape["model"]
    e_local = e // model_size
    dp = shd.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_axis = dp if (batch % dp_size == 0 and batch > 1) else None
    t_local = (batch // dp_size if batch_axis else batch) * n
    capacity = max(1, int(t_local * k / e * cfg.capacity_factor))

    has_gate = "w_gate" in params
    serve_layout = shd.get_rules_profile() == "serve"
    zero_sharded = "data" in mesh.axis_names and mesh.shape["data"] > 1 \
        and params["w_up"].shape[1] % mesh.shape["data"] == 0
    # which weight axis is data-sharded depends on the rules profile:
    # train ZeRO shards d_model (axis 1 of w_up); serve 2D-shards d_ff
    # (axis 2 of w_up).
    up_gather_axis = 2 if serve_layout else 1
    down_gather_axis = 1 if serve_layout else 2

    def local_moe(router, w_up, w_gate, w_down, x_l):
        # reassemble this shard's experts' full weights
        if zero_sharded:
            if cfg.quantized_weight_gather:
                gather_up = lambda w: _quantized_all_gather(
                    w, "data", up_gather_axis)
                gather_down = lambda w: _quantized_all_gather(
                    w, "data", down_gather_axis)
            else:
                gather_up = lambda w: jax.lax.all_gather(
                    w, "data", axis=up_gather_axis, tiled=True)
                gather_down = lambda w: jax.lax.all_gather(
                    w, "data", axis=down_gather_axis, tiled=True)
            w_up = gather_up(w_up)
            w_down = gather_down(w_down)
            if has_gate:
                w_gate = gather_up(w_gate)
        xt = x_l.reshape(-1, d)                       # [T_l, d]
        logits = jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), router
        )
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9
        )

        shard = jax.lax.axis_index("model")
        lo = shard * e_local
        local_e = top_e - lo                          # [T, K]
        mine = jnp.logical_and(local_e >= 0, local_e < e_local)

        flat_e = jnp.where(mine, local_e, e_local).reshape(-1)  # E_local = trash
        onehot = jax.nn.one_hot(flat_e, e_local + 1, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        flat_pos = jnp.sum(pos * onehot, axis=-1)
        keep = jnp.logical_and(flat_e < e_local, flat_pos < capacity)
        safe_e = jnp.where(keep, flat_e, 0)
        safe_pos = jnp.where(keep, flat_pos, 0)
        tok_idx = jnp.repeat(jnp.arange(xt.shape[0]), k)

        buf = jnp.zeros((e_local, capacity, d), xt.dtype)
        contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
        buf = buf.at[safe_e, safe_pos].add(contrib, mode="drop")

        p_local = {"w_up": w_up, "w_down": w_down}
        if has_gate:
            p_local["w_gate"] = w_gate
        out_buf = _expert_ffn(p_local, buf, cfg.activation)

        gathered = out_buf[safe_e, safe_pos]
        w = (top_p.reshape(-1) * keep.astype(jnp.float32))[:, None]
        out = jnp.zeros((xt.shape[0], d), jnp.float32).at[tok_idx].add(
            gathered.astype(jnp.float32) * w
        )
        # combine accumulates locally in f32; the cross-shard sum rides
        # the wire in bf16 (each token has ≤k expert contributions from
        # ≤k shards — negligible precision impact, half the bytes)
        out = jax.lax.psum(out.astype(x_l.dtype), "model")

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(
            jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0
        )
        aux = cfg.router_aux_weight * e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp) if dp else aux
        kept = jax.lax.psum(
            jnp.mean(keep.astype(jnp.float32)), "model"
        )  # each shard holds 1/model of the assignments
        drop = 1.0 - (jax.lax.pmean(kept, dp) if dp else kept)
        return out.reshape(x_l.shape).astype(x_l.dtype), aux, drop

    x_spec = P(batch_axis, None, None)
    if serve_layout:
        up_spec = P("model", None, "data" if zero_sharded else None)
        down_spec = P("model", "data" if zero_sharded else None, None)
    else:
        up_spec = P("model", "data" if zero_sharded else None, None)
        down_spec = P("model", None, "data" if zero_sharded else None)
    out, aux, drop = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(P(), up_spec,
                  up_spec if has_gate else P(), down_spec, x_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(
        params["router"],
        params["w_up"],
        params.get("w_gate", params["router"]),
        params["w_down"],
        x,
    )
    return out, {"moe_aux_loss": aux, "moe_drop_fraction": drop}


def _apply_moe_serve_2d(
    params, x: jax.Array, cfg: MoEConfig, mesh
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Decode-time MoE: replicated tokens × 2D-sharded experts.

    Serving layout (`sharding.set_rules_profile("serve")`): expert
    weights are sharded experts→'model' × d_ff→'data' and stay fully
    resident (no ZeRO gathers). The per-step token set is tiny, so each
    chip computes its (expert-slice × d_ff-slice) partial for ALL tokens
    and one psum over the whole mesh assembles the output. Collectives
    per layer: one token all-gather (≤1 MB) + one output psum (≤2 MB) —
    versus ~300 MB of weight gathers in the training layout.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    batch, n, d = x.shape
    e = cfg.num_experts
    k = cfg.experts_per_token
    model_size = mesh.shape["model"]
    e_local = e // model_size
    dp = shd.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_axis = dp if (batch % dp_size == 0 and batch > 1) else None
    t_global = batch * n
    capacity = max(1, int(t_global * k / e * cfg.capacity_factor))
    has_gate = "w_gate" in params

    def local_moe(router, w_up, w_gate, w_down, x_l):
        if batch_axis is not None:
            x_full = jax.lax.all_gather(x_l, dp, axis=0, tiled=True)
        else:
            x_full = x_l
        xt = x_full.reshape(-1, d)                    # [T_global, d]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, axis=-1, keepdims=True), 1e-9
        )

        shard = jax.lax.axis_index("model")
        lo = shard * e_local
        local_e = top_e - lo
        mine = jnp.logical_and(local_e >= 0, local_e < e_local)
        flat_e = jnp.where(mine, local_e, e_local).reshape(-1)
        onehot = jax.nn.one_hot(flat_e, e_local + 1, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        flat_pos = jnp.sum(pos * onehot, axis=-1)
        keep = jnp.logical_and(flat_e < e_local, flat_pos < capacity)
        safe_e = jnp.where(keep, flat_e, 0)
        safe_pos = jnp.where(keep, flat_pos, 0)
        tok_idx = jnp.repeat(jnp.arange(xt.shape[0]), k)

        buf = jnp.zeros((e_local, capacity, d), xt.dtype)
        contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
        buf = buf.at[safe_e, safe_pos].add(contrib, mode="drop")

        # expert FFN with d_ff sharded over 'data': partial down-proj
        up = jnp.einsum("ecd,edf->ecf", buf, w_up)
        if has_gate:
            act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) \
                if cfg.activation == "swiglu" else \
                jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
            h = act * up
        else:
            h = jax.nn.gelu(up)
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)  # partial over f

        gathered = out_buf[safe_e, safe_pos]
        w = (top_p.reshape(-1) * keep.astype(jnp.float32))[:, None]
        out = jnp.zeros((xt.shape[0], d), jnp.float32).at[tok_idx].add(
            gathered.astype(jnp.float32) * w
        )
        # one psum assembles expert (model) and d_ff (data) partials
        out = jax.lax.psum(out, ("model",) + tuple(dp))
        out = out.reshape(x_full.shape).astype(x_l.dtype)
        if batch_axis is not None:
            local_b = x_l.shape[0]
            start = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
                jax.lax.axis_index(dp[0]) * mesh.shape[dp[1]]
                + jax.lax.axis_index(dp[1])
            )
            out = jax.lax.dynamic_slice_in_dim(out, start * local_b, local_b, 0)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), 0)
        aux = cfg.router_aux_weight * e * jnp.sum(me * ce)
        kept = jax.lax.psum(jnp.mean(keep.astype(jnp.float32)), "model")
        return out, aux, 1.0 - kept

    x_spec = P(batch_axis, None, None)
    up_spec = P("model", None, "data")
    down_spec = P("model", "data", None)
    out, aux, drop = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(P(), up_spec, up_spec if has_gate else P(), down_spec,
                  x_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(
        params["router"], params["w_up"],
        params.get("w_gate", params["router"]), params["w_down"], x,
    )
    return out, {"moe_aux_loss": aux, "moe_drop_fraction": drop}
