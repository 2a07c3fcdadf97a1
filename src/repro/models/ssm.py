"""State-space / recurrent blocks: mLSTM + sLSTM (xLSTM) and Mamba2 (SSD).

Each block family exposes:
  init_*          — parameter pytree
  *_seq           — parallel full-sequence form (training / prefill)
  *_init_state    — recurrent state for decode
  *_step          — O(1)-per-token decode step (the long_500k path)

The training forms are TPU-friendly: mLSTM uses the stabilized quadratic
(gated-attention) formulation; Mamba2 uses the chunked SSD algorithm
(intra-chunk quadratic + inter-chunk scan) so activation memory is
O(n·L) not O(n·d_state·d_head). sLSTM is inherently sequential
(recurrent gate connections) and runs as a lax.scan.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


def _logsigmoid(x):
    return -jax.nn.softplus(-x)


# ===========================================================================
# mLSTM (xLSTM's matrix-memory cell)
# ===========================================================================


def init_mlstm(key, d_model: int, num_heads: int, dtype=jnp.float32):
    """mLSTM block: up-proj (2x), causal conv4, qkv, gates, down-proj."""
    d_in = 2 * d_model
    head_dim = d_in // num_heads
    ks = jax.random.split(key, 8)
    std = d_model ** -0.5
    return {
        "w_up": L.trunc_normal(ks[0], (d_model, 2 * d_in), std, dtype),
        "conv": L.trunc_normal(ks[1], (4, d_in), 0.3, dtype),
        "wq": L.trunc_normal(ks[2], (d_in, num_heads, head_dim), d_in ** -0.5, dtype),
        "wk": L.trunc_normal(ks[3], (d_in, num_heads, head_dim), d_in ** -0.5, dtype),
        "wv": L.trunc_normal(ks[4], (d_in, num_heads, head_dim), d_in ** -0.5, dtype),
        "w_if": L.trunc_normal(ks[5], (d_in, 2 * num_heads), d_in ** -0.5, dtype),
        "b_if": jnp.concatenate(
            [jnp.zeros((num_heads,), dtype),
             jnp.full((num_heads,), 3.0, dtype)]  # forget-gate bias high
        ),
        "out_norm": L.init_rmsnorm(d_in, dtype),
        "w_down": L.trunc_normal(ks[6], (d_in, d_model), d_in ** -0.5, dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, state=None):
    """Depthwise causal conv, width W. x ``[B, n, C]``, w ``[W, C]``."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:-2] + (width - 1, x.shape[-1]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=-2)
    out = sum(
        xp[..., i:i + x.shape[-2], :] * w[i] for i in range(width)
    )
    new_state = xp[..., -(width - 1):, :]
    return out, new_state


def mlstm_seq(params, x: jax.Array, num_heads: int) -> jax.Array:
    """Parallel mLSTM over a full sequence. x ``[B, n, d_model]``."""
    from repro.distributed import sharding as shd

    batch, n, _ = x.shape
    up = jnp.einsum("bnd,de->bne", x, params["w_up"])
    z, h_in = jnp.split(up, 2, axis=-1)
    h_in, _ = _causal_conv(h_in, params["conv"])
    h_in = jax.nn.silu(h_in)

    q = jnp.einsum("bne,ehk->bhnk", h_in, params["wq"])
    k = jnp.einsum("bne,ehk->bhnk", h_in, params["wk"])
    v = jnp.einsum("bne,ehk->bhnk", h_in, params["wv"])
    # Head-shard the quadratic-form operands (padded for small H): the
    # [B,H,n,n] gated score matrix must not contract over a sharded
    # head_dim — that all-reduces ~0.5 GB per layer per µbatch.
    q = shd.constrain(q, ("dp", "model", None, None), allow_uneven=True)
    k = shd.constrain(k, ("dp", "model", None, None), allow_uneven=True)
    v = shd.constrain(v, ("dp", "model", None, None), allow_uneven=True)
    head_dim = q.shape[-1]

    gates = jnp.einsum("bne,eg->bng", h_in, params["w_if"]) + params["b_if"]
    i_pre, f_pre = jnp.split(gates, 2, axis=-1)      # [B, n, H]
    log_i = i_pre.astype(jnp.float32).transpose(0, 2, 1)       # [B, H, n]
    log_f = _logsigmoid(f_pre.astype(jnp.float32)).transpose(0, 2, 1)
    # gates feed the [B,H,n,n] decay matrix — keep them head-sharded
    # alongside q/k/v or the quadratic form gets resharded per layer
    log_i = shd.constrain(log_i, ("dp", "model", None), allow_uneven=True)
    log_f = shd.constrain(log_f, ("dp", "model", None), allow_uneven=True)

    # Stabilized gated score matrix D (xLSTM eq. 25-27).
    f_cum = jnp.cumsum(log_f, axis=-1)               # F[t]
    log_d = (
        f_cum[..., :, None] - f_cum[..., None, :] + log_i[..., None, :]
    )  # [B, H, n(t), n(s)]
    causal = jnp.tril(jnp.ones((n, n), bool))
    log_d = jnp.where(causal, log_d, NEG_INF)
    m = jnp.max(log_d, axis=-1, keepdims=True)       # row stabilizer
    d_mat = jnp.exp(log_d - m)

    s = jnp.einsum(
        "bhtk,bhsk->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (head_dim ** -0.5)
    w_mat = s * d_mat
    norm = jnp.maximum(
        jnp.abs(jnp.sum(w_mat, axis=-1, keepdims=True)), jnp.exp(-m)
    )
    h = jnp.einsum("bhts,bhsk->bhtk", w_mat / norm, v.astype(jnp.float32))

    h = h.transpose(0, 2, 1, 3).reshape(batch, n, -1).astype(x.dtype)
    h = L.rmsnorm(params["out_norm"], h)
    h = h * jax.nn.silu(z)
    return jnp.einsum("bne,ed->bnd", h, params["w_down"])


def mlstm_init_state(batch: int, d_model: int, num_heads: int, dtype):
    d_in = 2 * d_model
    head_dim = d_in // num_heads
    return {
        "c": jnp.zeros((batch, num_heads, head_dim, head_dim), jnp.float32),
        "n": jnp.zeros((batch, num_heads, head_dim), jnp.float32),
        "m": jnp.full((batch, num_heads), 0.0, jnp.float32),
        "conv": jnp.zeros((batch, 3, d_in), dtype),
    }


def mlstm_step(params, x: jax.Array, state, num_heads: int):
    """One decode step. x ``[B, 1, d_model]`` → (y, new_state)."""
    up = jnp.einsum("bnd,de->bne", x, params["w_up"])
    z, h_in = jnp.split(up, 2, axis=-1)
    h_in, conv_state = _causal_conv(h_in, params["conv"], state["conv"])
    h_in = jax.nn.silu(h_in)

    q = jnp.einsum("be,ehk->bhk", h_in[:, 0], params["wq"])
    k = jnp.einsum("be,ehk->bhk", h_in[:, 0], params["wk"])
    v = jnp.einsum("be,ehk->bhk", h_in[:, 0], params["wv"])
    head_dim = q.shape[-1]
    gates = jnp.einsum("be,eg->bg", h_in[:, 0], params["w_if"]) + params["b_if"]
    i_pre, f_pre = jnp.split(gates.astype(jnp.float32), 2, axis=-1)  # [B, H]
    log_i = i_pre
    log_f = _logsigmoid(f_pre)

    m_new = jnp.maximum(log_f + state["m"], log_i)
    i_g = jnp.exp(log_i - m_new)[..., None]
    f_g = jnp.exp(log_f + state["m"] - m_new)[..., None]

    kf = k.astype(jnp.float32) * (head_dim ** -0.5)
    c_new = f_g[..., None] * state["c"] + i_g[..., None] * (
        kf[..., :, None] * v.astype(jnp.float32)[..., None, :]
    )
    n_new = f_g * state["n"] + i_g * kf
    num = jnp.einsum("bhk,bhkp->bhp", q.astype(jnp.float32), c_new)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhk,bhk->bh", q.astype(jnp.float32), n_new)),
        jnp.exp(-m_new),
    )[..., None]
    h = (num / den).reshape(x.shape[0], 1, -1).astype(x.dtype)
    h = L.rmsnorm(params["out_norm"], h)
    h = h * jax.nn.silu(z)
    y = jnp.einsum("bne,ed->bnd", h, params["w_down"])
    return y, {"c": c_new, "n": n_new, "m": m_new, "conv": conv_state}


# ===========================================================================
# sLSTM (xLSTM's scalar cell with recurrent gate connections)
# ===========================================================================


def init_slstm(key, d_model: int, num_heads: int, dtype=jnp.float32):
    head_dim = d_model // num_heads
    ks = jax.random.split(key, 4)
    std = d_model ** -0.5
    return {
        # input weights for 4 gates (i, f, z, o)
        "w_x": L.trunc_normal(ks[0], (d_model, 4 * d_model), std, dtype),
        # block-diagonal recurrent weights, one [hd, hd] per head per gate
        "r_h": L.trunc_normal(
            ks[1], (4, num_heads, head_dim, head_dim), head_dim ** -0.5, dtype
        ),
        "bias": jnp.concatenate(
            [jnp.zeros((d_model,), dtype),
             jnp.full((d_model,), 3.0, dtype),      # forget bias
             jnp.zeros((2 * d_model,), dtype)]
        ),
        "out_norm": L.init_rmsnorm(d_model, dtype),
        "w_out": L.trunc_normal(ks[2], (d_model, d_model), std, dtype),
    }


def _slstm_cell(params, xt, state, num_heads: int):
    """xt ``[B, d]``; state dict of ``[B, d]`` (+ stabilizer m)."""
    batch, d = xt.shape
    hd = d // num_heads
    h_prev = state["h"].reshape(batch, num_heads, hd)
    rec = jnp.einsum(
        "bhk,ghkl->bghl", h_prev.astype(jnp.float32),
        params["r_h"].astype(jnp.float32),
    ).reshape(batch, 4 * d)
    pre = (
        jnp.einsum("bd,de->be", xt.astype(jnp.float32),
                   params["w_x"].astype(jnp.float32))
        + rec + params["bias"].astype(jnp.float32)
    )
    i_pre, f_pre, z_pre, o_pre = jnp.split(pre, 4, axis=-1)
    log_i = i_pre
    log_f = _logsigmoid(f_pre)
    m_new = jnp.maximum(log_f + state["m"], log_i)
    i_g = jnp.exp(log_i - m_new)
    f_g = jnp.exp(log_f + state["m"] - m_new)
    c_new = f_g * state["c"] + i_g * jnp.tanh(z_pre)
    n_new = f_g * state["n"] + i_g
    h_new = jax.nn.sigmoid(o_pre) * c_new / jnp.maximum(n_new, 1e-6)
    return {"c": c_new, "n": n_new, "h": h_new, "m": m_new}


def slstm_init_state(batch: int, d_model: int, dtype=jnp.float32):
    z = jnp.zeros((batch, d_model), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": z}


def _slstm_seq_local(params, x: jax.Array, num_heads: int) -> jax.Array:
    batch, n, d = x.shape
    state0 = slstm_init_state(batch, d)

    def body(state, xt):
        new = _slstm_cell(params, xt, state, num_heads)
        return new, new["h"]

    _, hs = jax.lax.scan(body, state0, x.transpose(1, 0, 2))
    h = hs.transpose(1, 0, 2).astype(x.dtype)
    h = L.rmsnorm(params["out_norm"], h)
    return jnp.einsum("bnd,de->bne", h, params["w_out"])


def slstm_seq(params, x: jax.Array, num_heads: int) -> jax.Array:
    """Sequential sLSTM over the sequence (lax.scan). x ``[B, n, d]``.

    Under a production mesh the whole scan runs inside shard_map: pure
    batch data-parallelism with replicated (small) weights. Left to the
    auto-partitioner, the per-timestep recurrence picks up a model-axis
    reshard — one collective per step × 4096 steps × layers × µbatches
    (measured 0.96–4.9 TB/chip per train step depending on pinning).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = shd.get_active_mesh()
    if mesh is None:
        return _slstm_seq_local(params, x, num_heads)
    dp = shd.data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_axis = dp if (x.shape[0] % dp_size == 0 and x.shape[0] > 1) \
        else None
    x_spec = P(batch_axis, None, None)
    param_specs = jax.tree.map(lambda _: P(), params)
    return jax.shard_map(
        lambda p, xx: _slstm_seq_local(p, xx, num_heads),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(params, x)


def slstm_step(params, x: jax.Array, state, num_heads: int):
    """x ``[B, 1, d]`` → (y ``[B, 1, d]``, new_state)."""
    new = _slstm_cell(params, x[:, 0], state, num_heads)
    h = L.rmsnorm(params["out_norm"], new["h"][:, None].astype(x.dtype))
    y = jnp.einsum("bnd,de->bne", h, params["w_out"])
    return y, new


# ===========================================================================
# Mamba2 (SSD — state-space duality), used by zamba2
# ===========================================================================


def init_mamba2(
    key, d_model: int, d_state: int, head_dim: int = 64,
    expand: int = 2, dtype=jnp.float32,
):
    d_in = expand * d_model
    num_heads = d_in // head_dim
    conv_dim = d_in + 2 * d_state
    ks = jax.random.split(key, 6)
    std = d_model ** -0.5
    return {
        # fused in-proj: [z, x, B, C, dt]
        "w_in": L.trunc_normal(
            ks[0], (d_model, 2 * d_in + 2 * d_state + num_heads), std, dtype
        ),
        "conv": L.trunc_normal(ks[1], (4, conv_dim), 0.3, dtype),
        "a_log": jnp.log(
            jnp.linspace(1.0, 16.0, num_heads).astype(jnp.float32)
        ),
        "dt_bias": jnp.zeros((num_heads,), jnp.float32),
        "d_skip": jnp.ones((num_heads,), jnp.float32),
        "out_norm": L.init_rmsnorm(d_in, dtype),
        "w_out": L.trunc_normal(ks[2], (d_in, d_model), d_in ** -0.5, dtype),
    }


def _mamba2_proj(params, x, d_state: int, head_dim: int, expand: int,
                 conv_state=None):
    d_model = x.shape[-1]
    d_in = expand * d_model
    num_heads = d_in // head_dim
    proj = jnp.einsum("bnd,de->bne", x, params["w_in"])
    z = proj[..., :d_in]
    xbc = proj[..., d_in:d_in + d_in + 2 * d_state]
    dt_pre = proj[..., -num_heads:]
    xbc, new_conv = _causal_conv(xbc, params["conv"], conv_state)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in]
    b = xbc[..., d_in:d_in + d_state]
    c = xbc[..., d_in + d_state:]
    dt = jax.nn.softplus(
        dt_pre.astype(jnp.float32) + params["dt_bias"]
    )  # [B, n, H]
    return z, xs, b, c, dt, new_conv


def mamba2_seq(
    params, x: jax.Array, d_state: int, head_dim: int = 64,
    expand: int = 2, chunk: int = 128,
) -> jax.Array:
    """Chunked SSD over a full sequence. x ``[B, n, d_model]``."""
    batch, n, d_model = x.shape
    d_in = expand * d_model
    num_heads = d_in // head_dim
    chunk = min(chunk, n)
    while n % chunk:
        chunk //= 2
    nc = n // chunk

    z, xs, b, c, dt, _ = _mamba2_proj(params, x, d_state, head_dim, expand)
    xh = xs.reshape(batch, nc, chunk, num_heads, head_dim)
    bt = b.reshape(batch, nc, chunk, d_state).astype(jnp.float32)
    ct = c.reshape(batch, nc, chunk, d_state).astype(jnp.float32)
    dtc = dt.reshape(batch, nc, chunk, num_heads)
    a = -jnp.exp(params["a_log"])                      # [H], negative
    log_a = dtc * a                                    # [B,nc,L,H]
    ca = jnp.cumsum(log_a, axis=2)                     # within-chunk cumsum

    xdt = xh.astype(jnp.float32) * dtc[..., None]      # dt-weighted input

    # --- intra-chunk (quadratic within L) ---
    g = jnp.einsum("bcts,bcls->bctl", ct, bt)          # C_t·B_s  [B,nc,L,L]
    decay = ca[..., :, None, :] - ca[..., None, :, :]  # [B,nc,t,s,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, ..., None], decay, NEG_INF)
    w = g[..., None] * jnp.exp(decay)                  # [B,nc,t,s,H]
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", w, xdt)

    # --- chunk-boundary states + inter-chunk scan ---
    decay_end = ca[..., -1:, :] - ca                   # [B,nc,L,H]
    s_chunk = jnp.einsum(
        "bcls,bclhp,bclh->bchsp", bt, xdt, jnp.exp(decay_end)
    )                                                   # [B,nc,H,N,P]
    a_total = jnp.exp(ca[..., -1, :])                  # [B,nc,H]

    def scan_body(s_prev, inp):
        s_c, a_tot = inp
        s_out = s_prev
        s_next = a_tot[..., None, None] * s_prev + s_c
        return s_next, s_out

    s0 = jnp.zeros((batch, num_heads, d_state, head_dim), jnp.float32)
    _, s_in = jax.lax.scan(
        scan_body, s0,
        (s_chunk.transpose(1, 0, 2, 3, 4), a_total.transpose(1, 0, 2)),
    )
    s_in = s_in.transpose(1, 0, 2, 3, 4)               # [B,nc,H,N,P]

    y_inter = jnp.einsum(
        "bcts,bchsp,bcth->bcthp", ct, s_in, jnp.exp(ca)
    )

    y = y_intra + y_inter + params["d_skip"][..., None] * xh.astype(jnp.float32)
    y = y.reshape(batch, n, d_in).astype(x.dtype)
    y = L.rmsnorm(params["out_norm"], y * jax.nn.silu(z))
    return jnp.einsum("bne,ed->bnd", y, params["w_out"])


def mamba2_init_state(
    batch: int, d_model: int, d_state: int, head_dim: int = 64,
    expand: int = 2, dtype=jnp.float32,
):
    d_in = expand * d_model
    num_heads = d_in // head_dim
    return {
        "ssm": jnp.zeros((batch, num_heads, d_state, head_dim), jnp.float32),
        "conv": jnp.zeros((batch, 3, d_in + 2 * d_state), dtype),
    }


def mamba2_step(
    params, x: jax.Array, state, d_state: int, head_dim: int = 64,
    expand: int = 2,
):
    """One decode step. x ``[B, 1, d_model]``."""
    batch = x.shape[0]
    z, xs, b, c, dt, conv_state = _mamba2_proj(
        params, x, d_state, head_dim, expand, state["conv"]
    )
    num_heads = xs.shape[-1] // head_dim
    xh = xs[:, 0].reshape(batch, num_heads, head_dim).astype(jnp.float32)
    bt = b[:, 0].astype(jnp.float32)                   # [B,N]
    ct = c[:, 0].astype(jnp.float32)
    dt1 = dt[:, 0]                                     # [B,H]
    a = -jnp.exp(params["a_log"])
    a_step = jnp.exp(dt1 * a)                          # [B,H]
    s_new = (
        a_step[..., None, None] * state["ssm"]
        + jnp.einsum("bs,bhp,bh->bhsp", bt, xh, dt1)
    )
    y = jnp.einsum("bs,bhsp->bhp", ct, s_new)
    y = y + params["d_skip"][..., None] * xh
    y = y.reshape(batch, 1, -1).astype(x.dtype)
    y = L.rmsnorm(params["out_norm"], y * jax.nn.silu(z))
    y = jnp.einsum("bne,ed->bnd", y, params["w_out"])
    return y, {"ssm": s_new, "conv": conv_state}
