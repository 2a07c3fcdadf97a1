"""Multi-head attention with GQA, RoPE, qk-norm, sliding windows, KV cache
— all routed through Energon dynamic sparse attention (`repro.core`).

Calling convention keeps activations ``[batch, seq, d_model]`` and maps
GQA by repeating KV heads to the query-head count before handing
``[B, H, n, hd]`` tensors to ``energon_attention`` (XLA fuses the repeat;
on the Pallas path the repeat is a view over the folded head axis).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (
    EnergonConfig,
    energon_attention,
    energon_decode_attention,
    energon_paged_decode_attention,
    energon_paged_prefill_attention,
)
from repro.core import quantization as qlib
from repro.distributed import sharding as shd
from repro.models import layers as L


def init_attention(
    key,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    use_qk_norm: bool = False,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    k_q, k_k, k_v, k_o = jax.random.split(key, 4)
    std = d_model ** -0.5
    params = {
        "wq": L.trunc_normal(k_q, (d_model, num_heads, head_dim), std, dtype),
        "wk": L.trunc_normal(k_k, (d_model, num_kv_heads, head_dim), std, dtype),
        "wv": L.trunc_normal(k_v, (d_model, num_kv_heads, head_dim), std, dtype),
        "wo": L.trunc_normal(
            k_o, (num_heads, head_dim, d_model),
            (num_heads * head_dim) ** -0.5, dtype,
        ),
    }
    if use_qk_norm:
        params["q_norm"] = L.init_rmsnorm(head_dim, dtype)
        params["k_norm"] = L.init_rmsnorm(head_dim, dtype)
    return params


def _project_qkv(
    params, x: jax.Array, positions: jax.Array,
    use_qk_norm: bool, rope_theta: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x ``[B, n, d_model]`` → q ``[B, n, H, hd]``, k/v ``[B, n, KV, hd]``."""
    q = jnp.einsum("bnd,dhk->bnhk", x, params["wq"])
    k = jnp.einsum("bnd,dhk->bnhk", x, params["wk"])
    v = jnp.einsum("bnd,dhk->bnhk", x, params["wv"])
    if use_qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    q = L.apply_rope(q, positions, rope_theta)
    k = L.apply_rope(k, positions, rope_theta)
    return q, k, v


def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    """``[B, KV, n, hd]`` → ``[B, KV*groups, n, hd]``."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=1)


def attention_block(
    params,
    x: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    use_qk_norm: bool = False,
    window: Optional[int] = None,
    layer_index: int = 10**9,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence (training / prefill) attention. x ``[B, n, d]``."""
    batch, n, _ = x.shape
    if positions is None:
        positions = jnp.arange(n)[None, :]
    q, k, v = _project_qkv(params, x, positions, use_qk_norm, rope_theta)
    q = q.transpose(0, 2, 1, 3)  # [B, H, n, hd]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    groups = num_heads // num_kv_heads
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    # Head-shard all attention operands over the model axis (uneven head
    # counts are padded by GSPMD): the MP-MRF filter, the block gather
    # and — critically — its backward scatter-add all stay device-local.
    q = shd.constrain(q, ("dp", "model", None, None), allow_uneven=True)
    k = shd.constrain(k, ("dp", "model", None, None), allow_uneven=True)
    v = shd.constrain(v, ("dp", "model", None, None), allow_uneven=True)
    out = energon_attention(
        q, k, v, energon,
        causal=True, window=window, layer_index=layer_index,
    )
    out = out.transpose(0, 2, 1, 3)  # [B, n, H, hd]
    return jnp.einsum("bnhk,hkd->bnd", out, params["wo"])


# ---------------------------------------------------------------------------
# KV-cache decode path
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int,
    num_kv_heads: int,
    max_len: int,
    head_dim: int,
    dtype,
    filter_block: int = 0,
) -> Dict[str, jax.Array]:
    """Padded decode cache; ``filter_block > 0`` adds the persistent
    quantized filter operands (DESIGN.md §3): int16 K codes and one
    float32 scale per ``filter_block``-token key block, maintained
    incrementally by the cache writers so decode filtering never
    re-quantizes the cache. ``max_len`` must then divide into blocks."""
    cache = {
        "k": jnp.zeros((batch, num_kv_heads, max_len, head_dim), dtype),
        "v": jnp.zeros((batch, num_kv_heads, max_len, head_dim), dtype),
    }
    if filter_block > 0:
        if max_len % filter_block:
            raise ValueError(
                f"max_len {max_len} not divisible by filter block "
                f"{filter_block}"
            )
        cache["k_codes"] = jnp.zeros(
            (batch, num_kv_heads, max_len, head_dim), jnp.int16
        )
        cache["k_scale"] = jnp.zeros(
            (batch, num_kv_heads, max_len // filter_block), jnp.float32
        )
    return cache


def _refresh_filter_block(
    k_cache: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    pos: jax.Array,
    block: int,
) -> Tuple[jax.Array, jax.Array]:
    """Re-quantize only the key block each slot's append touched.

    The incremental-append invariant: after every cache write, block j's
    (codes, scale) equal a fresh per-block quantization of block j's
    float rows. A decode append changes exactly one block per slot, so
    the refresh quantizes ``block · head_dim`` values per KV head —
    O(1) in context length — and scatters them with a one-hot block
    mask (same idiom as the float-cache scatter, so the cache layout
    constraint keeps everything shard-local).
    """
    batch, kv, max_len, hd = k_cache.shape
    n_kb = max_len // block
    blk = jnp.clip(pos, 0, max_len - 1) // block            # [B]
    kb = k_cache.reshape(batch, kv, n_kb, block, hd)
    sel = jnp.take_along_axis(
        kb, blk[:, None, None, None, None], axis=2
    )[:, :, 0]                                              # [B,KV,blk,hd]
    new_codes, new_scale = qlib.quantize_int16_blocks(sel, block)
    oh = jnp.arange(n_kb)[None, :] == blk[:, None]          # [B, n_kb]
    codes_r = jnp.where(
        oh[:, None, :, None, None],
        new_codes[:, :, None],
        codes.reshape(batch, kv, n_kb, block, hd),
    )
    scales_r = jnp.where(oh[:, None, :], new_scale, scales)
    return codes_r.reshape(batch, kv, max_len, hd), scales_r


def _project_update_fold(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    positions: jax.Array,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float,
    use_qk_norm: bool,
    filter_block: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Shared serve-path front half (decode = the C=1 special case).

    Projects QKV for ``x [B, C, d]`` at absolute cache ``positions
    [B, C]``, scatters the C new K/V rows into the padded cache, and
    folds GQA head groups into the query axis. Returns
    ``(q_folded [B, KV, G·C, hd], new_cache)``.

    When the cache carries the persistent filter operands (``k_codes`` /
    ``k_scale``), they are refreshed *here*, at write time, so they can
    never drift from the float rows: a decode append (C = 1)
    re-quantizes exactly the one touched key block per slot; a prefill
    chunk re-quantizes every block from the updated cache (prefill is
    already O(C·max_len) — the refresh is not the bottleneck there, and
    full refresh keeps ragged/sentinel writes trivially correct).

    Layout rules: when KV heads divide the model axis the cache is
    head-sharded → q matches; otherwise the cache is *sequence*-sharded
    (context parallel) and q is replicated over 'model', else XLA
    all-gathers the whole cache every layer (measured 64 MB × L per
    decode step). The scatter is a one-hot product pinned to the cache
    layout for the same reason; out-of-range positions (>= max_len)
    produce all-zero one-hot rows, i.e. padding sentinels write nothing.
    The GQA fold avoids materializing a repeated cache — `jnp.repeat`
    of a sequence-sharded cache makes GSPMD all-gather it per layer.
    """
    batch, chunk, _ = x.shape
    max_len = cache["k"].shape[2]
    q, k, v = _project_qkv(params, x, positions, use_qk_norm, rope_theta)
    q = q.transpose(0, 2, 1, 3)              # [B, H, C, hd]
    k_new = k.transpose(0, 2, 1, 3)          # [B, KV, C, hd]
    v_new = v.transpose(0, 2, 1, 3)

    mesh = shd.get_active_mesh()
    kv_head_sharded = (
        mesh is not None and "model" in mesh.axis_names
        and num_kv_heads % mesh.shape["model"] == 0
    )
    q = shd.constrain(
        q,
        ("dp", "model" if kv_head_sharded else None, None, None),
        allow_uneven=True,
    )

    onehot = jax.nn.one_hot(
        positions, max_len, dtype=k_new.dtype
    )  # [B, C, max_len]
    write = jnp.sum(onehot, axis=1)          # [B, max_len] 0/1
    write = shd.constrain_cache_onehot(write, cache["k"].shape)
    k_cache = shd.constrain_kv_cache(
        cache["k"] * (1 - write)[:, None, :, None]
        + jnp.einsum("bcm,bhcd->bhmd", onehot, k_new)
    )
    v_cache = shd.constrain_kv_cache(
        cache["v"] * (1 - write)[:, None, :, None]
        + jnp.einsum("bcm,bhcd->bhmd", onehot, v_new)
    )

    new_cache = dict(cache)
    new_cache["k"] = k_cache
    new_cache["v"] = v_cache
    if "k_codes" in cache:
        if filter_block <= 0:
            raise ValueError(
                "cache carries filter planes but filter_block is unset"
            )
        if chunk == 1:
            codes, scales = _refresh_filter_block(
                k_cache, cache["k_codes"], cache["k_scale"],
                positions[:, 0], filter_block,
            )
        else:
            codes, scales = qlib.quantize_int16_blocks(
                k_cache, filter_block
            )
        new_cache["k_codes"] = shd.constrain_kv_cache(codes)
        new_cache["k_scale"] = scales

    groups = num_heads // num_kv_heads
    head_dim = q.shape[-1]
    if groups > 1:
        q = q.reshape(batch, num_kv_heads, groups * chunk, head_dim)
    return q, new_cache


def _unfold_heads_out(
    out: jax.Array, params, num_heads: int, chunk: int
) -> jax.Array:
    """``[B, KV, G·C, hd]`` attention output → ``[B, C, d_model]``.

    Under a mesh the heads are gathered first: a head-sharded output
    would let the partitioner split the projection's contraction over
    devices and all-reduce bf16 partial sums, which rounds differently
    from the one-device contraction (the serve-TP bit-identity
    contract, DESIGN.md §9)."""
    batch, _, _, head_dim = out.shape
    out = shd.constrain(out, ("dp", None, None, None))
    out = out.reshape(batch, num_heads, chunk, head_dim)
    out = out.transpose(0, 2, 1, 3)          # [B, C, H, hd]
    return jnp.einsum("bnhk,hkd->bnd", out, params["wo"])


def prefill_attention_block(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    positions: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    use_qk_norm: bool = False,
    window: Optional[jax.Array] = None,
    layer_index: int = 10**9,
    telemetry: bool = False,
):
    """Chunked-prefill attention: a C-token chunk against the KV cache.

    x ``[B, C, d]``; positions ``[B, C]`` absolute cache positions per
    token. The chunk's K/V rows are scattered into the cache at their
    positions in one shot, then the chunk's queries attend the *updated*
    cache under a per-row causal mask (key pos ≤ query pos) — admitting a
    length-L prompt costs O(L/C) dispatches instead of L decode steps.

    Rows with ``positions >= max_len`` are padding sentinels: they write
    nothing, are masked out of (pooled) score selection, and their
    outputs are garbage the caller ignores. This is how ragged final
    chunks and engine slots not being prefilled stay inert inside one
    fixed-shape jitted call.
    """
    chunk = x.shape[1]
    qg, new_cache = _project_update_fold(
        params, x, cache, positions,
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        rope_theta=rope_theta, use_qk_norm=use_qk_norm,
        filter_block=energon.decode_key_block,
    )
    groups = num_heads // num_kv_heads
    # folded row (g, c) keeps token c's position → same per-row mask
    qpos = jnp.tile(positions, (1, groups)) if groups > 1 else positions
    filter_cache = None
    if "k_codes" in new_cache:
        # the planes were refreshed by the fold above, so the chunk's
        # own selection already reads them — fused prefill (impl
        # "pallas") and the XLA selection consume the same operands
        filter_cache = {
            "codes": new_cache["k_codes"], "scale": new_cache["k_scale"],
        }
    out = energon_attention(
        qg, new_cache["k"], new_cache["v"], energon,
        causal=True, window=window, layer_index=layer_index,
        q_positions=qpos, filter_cache=filter_cache,
        telemetry=telemetry,
    )
    if telemetry:
        out, stats = out
    y = _unfold_heads_out(out, params, num_heads, chunk)
    if telemetry:
        return y, new_cache, stats
    return y, new_cache


def decode_attention_block(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    cache_index: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    use_qk_norm: bool = False,
    window: Optional[int] = None,
    layer_index: int = 10**9,
    telemetry: bool = False,
):
    """One-token decode step. x ``[B, 1, d]``; cache_index ``[B]``.

    Updates the cache in-place (functionally) at ``cache_index`` and runs
    Energon decode attention (MP-MRF filtering over the cache, §IV-D
    l=1 case) over the valid prefix. When the cache carries the
    persistent filter planes, the touched key block is re-quantized at
    append and the filter consumes the resident codes/scales — the
    per-step filter never re-quantizes the cache.
    """
    qg, new_cache = _project_update_fold(
        params, x, cache, cache_index[:, None],
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        rope_theta=rope_theta, use_qk_norm=use_qk_norm,
        filter_block=energon.decode_key_block,
    )
    filter_cache = None
    if "k_codes" in new_cache:
        filter_cache = {
            "codes": new_cache["k_codes"], "scale": new_cache["k_scale"],
        }
    out = energon_decode_attention(
        qg, new_cache["k"], new_cache["v"], cache_index + 1, energon,
        layer_index=layer_index, window=window, filter_cache=filter_cache,
        telemetry=telemetry,
    )
    if telemetry:
        out, stats = out
    y = _unfold_heads_out(out, params, num_heads, 1)
    if telemetry:
        return y, new_cache, stats
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged KV-cache serve path (shared page pool + block-table indirection)
# ---------------------------------------------------------------------------


def init_paged_kv_cache(
    num_pages: int,
    num_kv_heads: int,
    page_size: int,
    head_dim: int,
    dtype,
    filter_planes: bool = True,
) -> Dict[str, jax.Array]:
    """One layer's shared page pool (``repro.runtime.paged_cache``
    layout): K/V rows ``[KV, num_pages · page_size, hd]`` plus — when
    the decode filter cache is enabled — the per-page filter operands
    (int16 codes in cache layout, one f32 absmax scale per physical
    page, so the PR 2 incremental-quantization invariant holds per
    page). There is no batch axis: slots address the pool through their
    block tables."""
    rows = num_pages * page_size
    cache = {
        "k": jnp.zeros((num_kv_heads, rows, head_dim), dtype),
        "v": jnp.zeros((num_kv_heads, rows, head_dim), dtype),
    }
    if filter_planes:
        cache["k_codes"] = jnp.zeros(
            (num_kv_heads, rows, head_dim), jnp.int16
        )
        cache["k_scale"] = jnp.zeros((num_kv_heads, num_pages), jnp.float32)
    return cache


def _project_update_fold_paged(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    positions: jax.Array,
    block_table: jax.Array,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float,
    use_qk_norm: bool,
    filter_block: int = 0,
    write_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Paged serve-path front half: the write site appends *through the
    block table*. Token (b, c) at logical position p lands in pool row
    ``table[b, p // ps] · ps + p % ps``; sentinel positions and
    masked-off slots resolve to an out-of-range row and the
    ``mode="drop"`` scatter discards them (in a shared pool an idle
    slot must not self-heal — its table may alias pages a live slot
    owns, so idle writes are *dropped*, not overwritten later).

    Prefix sharing strengthens that aliasing: a *live* slot's table may
    alias pages other live slots also map (shared prompt prefixes).
    The scheduler guarantees writes only ever target exclusively-owned
    pages — a slot about to write a shared or content-registered page
    gets a copy-on-write clone first (``PageAllocator.cow`` +
    ``LMModel.clone_pages``) — so this function needs no extra masking:
    by construction, positions it writes resolve to single-writer rows.

    Filter-operand maintenance mirrors the unpaged invariant per
    physical page: a decode append (C = 1) re-quantizes exactly the one
    touched page per active slot; a prefill chunk re-quantizes the
    whole pool (every page's codes/scale equal a fresh per-page
    quantization of its float rows at every step).
    """
    from repro.runtime import paged_cache as pgc

    batch, chunk, _ = x.shape
    ps = filter_block if filter_block > 0 else 0
    if ps <= 0:
        raise ValueError("paged cache needs a positive page size")
    q, k, v = _project_qkv(params, x, positions, use_qk_norm, rope_theta)
    q = q.transpose(0, 2, 1, 3)              # [B, H, C, hd]
    k_new = k.transpose(0, 2, 1, 3)          # [B, KV, C, hd]
    v_new = v.transpose(0, 2, 1, 3)

    mesh = shd.get_active_mesh()
    kv_head_sharded = (
        mesh is not None and "model" in mesh.axis_names
        and num_kv_heads % mesh.shape["model"] == 0
    )
    q = shd.constrain(
        q,
        ("dp", "model" if kv_head_sharded else None, None, None),
        allow_uneven=True,
    )

    rowid = pgc.paged_row_targets(
        positions, block_table, ps, write_mask=write_mask
    )                                        # [B, C]
    flat_rows = rowid.reshape(-1)            # [B·C]
    k_flat = k_new.transpose(1, 0, 2, 3).reshape(num_kv_heads, -1, k_new.shape[-1])
    v_flat = v_new.transpose(1, 0, 2, 3).reshape(num_kv_heads, -1, v_new.shape[-1])
    k_pool = cache["k"].at[:, flat_rows].set(
        k_flat.astype(cache["k"].dtype), mode="drop"
    )
    v_pool = cache["v"].at[:, flat_rows].set(
        v_flat.astype(cache["v"].dtype), mode="drop"
    )

    new_cache = dict(cache)
    new_cache["k"] = k_pool
    new_cache["v"] = v_pool
    if "k_codes" in cache:
        num_pages = cache["k_scale"].shape[-1]
        if chunk == 1:
            # touched-page refresh: O(ps·hd) per active slot
            mb = block_table.shape[-1]
            blk = jnp.clip(positions[:, 0] // ps, 0, mb - 1)
            page = jnp.take_along_axis(
                block_table, blk[:, None], axis=-1
            )[:, 0]                          # [B]
            ok = positions[:, 0] < mb * ps
            if write_mask is not None:
                ok = jnp.logical_and(ok, write_mask)
            kb = k_pool.reshape(num_kv_heads, num_pages, ps, -1)
            sel = jnp.moveaxis(
                jnp.take(kb, page, axis=1), 1, 0
            )                                # [B, KV, ps, hd]
            new_codes, new_scale = qlib.quantize_int16_blocks(sel, ps)
            page_oob = jnp.where(ok, page, jnp.int32(2 ** 30))
            code_rows = jnp.where(
                ok[:, None],
                page[:, None] * ps + jnp.arange(ps)[None, :],
                jnp.int32(2 ** 30),
            ).reshape(-1)                    # [B·ps]
            codes_flat = new_codes.transpose(1, 0, 2, 3).reshape(
                num_kv_heads, -1, new_codes.shape[-1]
            )
            codes = cache["k_codes"].at[:, code_rows].set(
                codes_flat.astype(jnp.int16), mode="drop"
            )
            scales = cache["k_scale"].at[:, page_oob].set(
                new_scale[..., 0].T, mode="drop"
            )
        else:
            # Prefill chunk: refresh every page from the updated pool —
            # the same whole-cache choice the unpaged prefill makes
            # (and the pool is smaller than batch×max_len, so this is
            # strictly cheaper than the unpaged refresh). A ranged
            # refresh of just the ≤ ceil(C/ps)+1 touched pages per slot
            # would shrink it further, at the cost of weakening the
            # pool-wide invariant to mapped-pages-only.
            codes, scales = qlib.quantize_int16_blocks(k_pool, ps)
            codes = codes.astype(jnp.int16)
        new_cache["k_codes"] = codes
        new_cache["k_scale"] = scales

    groups = num_heads // num_kv_heads
    head_dim = q.shape[-1]
    if groups > 1:
        q = q.reshape(batch, num_kv_heads, groups * chunk, head_dim)
    return q, new_cache


def paged_prefill_attention_block(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    positions: jax.Array,
    block_table: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    use_qk_norm: bool = False,
    window: Optional[jax.Array] = None,
    layer_index: int = 10**9,
    telemetry: bool = False,
):
    """Chunked-prefill attention against the page pool.

    The chunk's K/V rows are scattered through the block table, then the
    chunk attends the pool through
    :func:`repro.core.energon_paged_prefill_attention`: the fused
    prefill kernels read the pool in place (survivor ∘ block-table index
    composition — unselected and unmapped pages never leave HBM); the
    XLA fallback materializes the per-slot *logical* K/V views (a
    transient gather — persistent state stays pool-sized). The gathered
    view is value-identical to the equivalent unpaged cache, so paged
    and unpaged prefill logits agree bit-for-bit on the fallback, and
    selection agrees bit-for-bit on both.
    """
    chunk = x.shape[1]
    ps = energon.decode_key_block
    qg, new_cache = _project_update_fold_paged(
        params, x, cache, positions, block_table,
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        rope_theta=rope_theta, use_qk_norm=use_qk_norm,
        filter_block=ps,
    )
    groups = num_heads // num_kv_heads
    qpos = jnp.tile(positions, (1, groups)) if groups > 1 else positions
    out = energon_paged_prefill_attention(
        qg, new_cache, block_table, qpos, energon,
        layer_index=layer_index, window=window, telemetry=telemetry,
    )
    if telemetry:
        out, stats = out
    y = _unfold_heads_out(out, params, num_heads, chunk)
    if telemetry:
        return y, new_cache, stats
    return y, new_cache


def paged_decode_attention_block(
    params,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    cache_index: jax.Array,
    block_table: jax.Array,
    energon: EnergonConfig,
    *,
    num_heads: int,
    num_kv_heads: int,
    rope_theta: float = 10000.0,
    use_qk_norm: bool = False,
    window: Optional[int] = None,
    layer_index: int = 10**9,
    active: Optional[jax.Array] = None,
    telemetry: bool = False,
):
    """One-token paged decode step. x ``[B, 1, d]``; cache_index ``[B]``.

    Appends through the block table (``active`` gates slots whose write
    must be dropped — in a shared pool an idle slot's table may alias
    live pages) and runs the paged Energon decode attention: selection
    and output are bit-identical to the unpaged path on the same
    logical contents.
    """
    qg, new_cache = _project_update_fold_paged(
        params, x, cache, cache_index[:, None], block_table,
        num_heads=num_heads, num_kv_heads=num_kv_heads,
        rope_theta=rope_theta, use_qk_norm=use_qk_norm,
        filter_block=energon.decode_key_block,
        write_mask=active,
    )
    out = energon_paged_decode_attention(
        qg, new_cache, block_table, cache_index + 1, energon,
        layer_index=layer_index, window=window, telemetry=telemetry,
    )
    if telemetry:
        out, stats = out
    y = _unfold_heads_out(out, params, num_heads, 1)
    if telemetry:
        return y, new_cache, stats
    return y, new_cache
