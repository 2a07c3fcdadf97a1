"""Public jit'd wrappers around the Pallas kernels.

These compose the kernels into the full Energon pipeline
(quantize → fused filter → Eq. 3 block selection → block-sparse AU) and
pick interpret mode automatically off-TPU so the same call sites work in
CPU tests and on real hardware.

Gradients: the kernels are forward/serving paths (the paper's Energon is
an inference co-processor). Training uses the XLA implementations in
``repro.core``; `energon_block_attention` therefore attaches a custom
VJP that recomputes through the XLA block path so the module stays
differentiable if a training config selects ``impl="pallas"``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import filtering as flt
from repro.core import quantization as qlib
from repro.core import sparse_attention as spa
from repro.distributed import sharding as shd
from repro.kernels import block_sparse_attention as bsa_kernel
from repro.kernels import flash_attention as fa_kernel
from repro.kernels import mpmrf_decode as dec_kernel
from repro.kernels import mpmrf_filter as filt_kernel
from repro.kernels import mpmrf_prefill as pre_kernel

NEG_INF = -1e30


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tp_mesh(kv_heads: int):
    """The active serve mesh, iff the fused paged kernels should
    shard-map over its 'model' axis.

    Engagement requires the KV-head axis to divide the model axis —
    the same condition under which :func:`paged_pool_pspec` head-shards
    the resident pools, so the shard_map's in_specs match the pool
    layout and no resharding happens at the boundary. Pools whose KV
    heads don't divide (page-aligned row sharding) stay on the GSPMD
    auto-partitioned path: a row shard splits one head's pages across
    devices, so its survivor attention would need a cross-device
    partial-softmax merge — numerically fine, but not bit-identical,
    and the serve engine's equivalence contracts demand bit-identity
    (DESIGN.md §9).
    """
    mesh = shd.get_active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    tp = mesh.shape["model"]
    if tp <= 1 or kv_heads % tp:
        return None
    return mesh


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Dense flash attention over ``[bh, n, d]`` operands."""
    interpret = _default_interpret() if interpret is None else interpret
    return fa_kernel.flash_attention(
        q, k, v,
        causal=causal, q_offset=q_offset, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def mpmrf_select_blocks(
    q: jax.Array,
    k: jax.Array,
    *,
    round_bits: Tuple[int, ...] = (2, 4),
    alphas: Tuple[float, ...] = (0.0, 0.0),
    block_budget: int = 8,
    query_block: int = 128,
    key_block: int = 128,
    causal: bool = True,
    q_offset: int = 0,
    keep_first: bool = True,
    keep_diagonal: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full FU pipeline: quantize → fused filter kernel → block selection.

    q/k: float ``[bh, n, d]``. Returns (block_indices, block_valid), each
    ``[bh, n_qb, B]`` int32, ready for :func:`block_sparse_attention`.
    """
    if len(round_bits) != 2:
        raise ValueError("fused kernel supports the default 2-round config")
    interpret = _default_interpret() if interpret is None else interpret
    lo, hi = round_bits
    bq, bk = query_block, key_block
    n_q, n_k = q.shape[-2], k.shape[-2]
    n_qb, n_kb = n_q // bq, n_k // bk

    q16 = qlib.quantize_int16(q, axis=-1)
    k16 = qlib.quantize_int16(k, axis=(-2, -1))
    q_plane = q16.bit_plane(hi).astype(jnp.int8)
    k_msb = k16.bit_plane(lo).astype(jnp.int8)
    k_rem = k16.lsb_remainder(lo, hi).astype(jnp.int8)

    s0_blk, s1_blk = filt_kernel.mpmrf_filter_scores(
        q_plane, k_msb, k_rem, q16.scale,
        shift=hi - lo,
        query_block=bq, key_block=bk,
        causal=causal, q_offset=q_offset,
        interpret=interpret,
    )
    # Scalar factors deferred from the kernel: per-head k scale × the
    # q plane's 2^(16-hi) × the round-r k plane's 2^(16-bits).
    k_scale = jnp.squeeze(k16.scale, axis=(-2, -1))[:, None, None]
    q_plane_factor = float(2 ** (16 - hi))
    s0_blk = jnp.where(
        s0_blk <= NEG_INF / 2, NEG_INF,
        s0_blk * k_scale * q_plane_factor * float(2 ** (16 - lo)),
    )
    s1_blk = jnp.where(
        s1_blk <= NEG_INF / 2, NEG_INF,
        s1_blk * k_scale * q_plane_factor * float(2 ** (16 - hi)),
    )

    blk_valid = s0_blk > NEG_INF / 2
    keep = blk_valid
    theta0 = flt.eq3_threshold(s0_blk, alphas[0], keep)
    keep = jnp.logical_and(keep, s0_blk >= theta0)
    theta1 = flt.eq3_threshold(s1_blk, alphas[1], keep)
    keep = jnp.logical_and(keep, s1_blk >= theta1)

    if keep_first:
        keep = keep.at[..., 0].set(blk_valid[..., 0])
    if keep_diagonal:
        diag = jnp.minimum((jnp.arange(n_qb) * bq) // bk, n_kb - 1)
        diag_mask = jax.nn.one_hot(diag, n_kb, dtype=bool)
        keep = jnp.logical_or(keep, jnp.logical_and(diag_mask, blk_valid))

    b = min(block_budget, n_kb)
    sel = jnp.where(keep, s1_blk, NEG_INF)
    top_vals, block_indices = jax.lax.top_k(sel, b)
    block_valid = (top_vals > NEG_INF / 2).astype(jnp.int32)
    # Padded slots point at block 0 (harmless: masked out by block_valid).
    block_indices = jnp.where(block_valid > 0, block_indices, 0)
    return block_indices.astype(jnp.int32), block_valid


def block_sparse_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_indices: jax.Array,
    block_valid: Optional[jax.Array] = None,
    *,
    query_block: int = 128,
    key_block: int = 128,
    causal: bool = True,
    q_offset: int = 0,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """AU kernel wrapper; ``[bh, n, d]`` operands."""
    interpret = _default_interpret() if interpret is None else interpret
    if block_valid is None:
        block_valid = jnp.ones(block_indices.shape, jnp.int32)
    return bsa_kernel.block_sparse_attention(
        q, k, v, block_indices, block_valid,
        query_block=query_block, key_block=key_block,
        causal=causal, q_offset=q_offset, scale=scale,
        interpret=interpret,
    )


def fused_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_codes: jax.Array,
    k_block_scale: jax.Array,
    cache_length: jax.Array,
    *,
    round_bits: Tuple[int, ...] = (2, 4),
    alphas: Tuple[float, ...] = (0.0, 0.0),
    key_block: int = 64,
    block_budget: int = 8,
    keep_all: bool = False,
    keep_first: bool = True,
    keep_diagonal: bool = True,
    live_budget: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    telemetry: bool = False,
):
    """Fused Pallas decode path over the resident filter cache (l = 1).

    Pipeline: the decode filter kernel scores every key block straight
    off the cached int16 codes (bit planes derived in-register, Fig. 7
    shift-and-add), Eq. 3 thresholds + exact-budget tier selection run
    on the tiny ``[bh, n_kb]`` score planes in XLA (the identical rule
    the XLA path uses, so selections agree bit-for-bit), and the gather
    kernel streams *only* the surviving K/V blocks via the
    scalar-prefetch survivor table — unselected blocks never leave HBM.

    Args:
      q: ``[B, H, G, d]`` folded GQA query rows (H = KV heads).
      k_cache, v_cache: ``[B, H, n_k, d]`` padded caches.
      k_codes: int16 ``[B, H, n_k, d]`` resident filter codes.
      k_block_scale: f32 ``[B, H, n_kb]`` resident per-block scales.
      cache_length: int32 ``[B]`` live lengths.
      live_budget: optional int32 ``[B]`` per-slot effective budget.
      telemetry: also return int32 ``[B, 4]`` selection stats (selected
        / live / pinned / filled block counts summed over heads — see
        :func:`repro.core.filtering.selection_stats`), computed from
        the selection planes already in registers; the kernels and
        their HBM traffic are unchanged.

    Returns:
      ``[B, H, G, d]`` attention output (dtype of v_cache); with
      ``telemetry``, ``(out, stats)``.
    """
    if len(round_bits) != 2:
        raise ValueError("fused decode kernel supports 2-round configs")
    interpret = _default_interpret() if interpret is None else interpret
    batch, heads, _, d = q.shape
    bk = key_block
    n_kb = k_cache.shape[-2] // bk
    n_pages = batch * heads * n_kb
    out, stats = _decode_pipeline(
        q,
        k_cache.reshape(n_pages, bk, d),
        v_cache.reshape(n_pages, bk, d),
        k_codes.reshape(n_pages, bk, d),
        k_block_scale.reshape(n_pages, 1),
        _identity_table(batch * heads, n_kb),
        cache_length, live_budget,
        round_bits=tuple(round_bits), alphas=tuple(alphas),
        key_block=bk, block_budget=block_budget, keep_all=keep_all,
        keep_first=keep_first, keep_diagonal=keep_diagonal, scale=scale,
        interpret=interpret, with_stats=telemetry,
    )
    return (out, stats) if telemetry else out


def _identity_table(rows: int, blocks: int) -> jax.Array:
    """Block table of an unpaged cache viewed as pages: row r's logical
    block j is page ``r · blocks + j`` of the cache reshaped to
    ``[rows · blocks, bk, d]`` (a free reshape — no copy)."""
    return jnp.arange(rows * blocks, dtype=jnp.int32).reshape(rows, blocks)


def _head_offset_table(block_table: jax.Array, heads: int,
                       num_pages: int) -> jax.Array:
    """``[B, mb]`` slot tables → ``[B·KV, mb]`` rows into pools whose
    KV-head axis is folded into the page axis ([KV, P, ...] →
    [KV·P, ...]): row b·KV+h points at head h's copy of the slot's
    pages."""
    head_off = jnp.arange(heads, dtype=jnp.int32) * num_pages
    bt = block_table.astype(jnp.int32)[:, None, :] + head_off[None, :, None]
    return bt.reshape(-1, block_table.shape[-1])


def _fused_decode_select(
    s0: jax.Array,
    s1: jax.Array,
    cl_bh: jax.Array,
    *,
    alphas: Tuple[float, ...],
    key_block: int,
    block_budget: int,
    keep_all: bool,
    keep_first: bool,
    keep_diagonal: bool,
    live_budget: Optional[jax.Array],
    heads: int,
    with_stats: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Eq. 3 thresholds + exact-budget tier selection on the kernel's
    ``[bh, n_kb]`` block-max score planes — the one selection rule the
    fused unpaged and paged decode paths share with the XLA paths
    (:func:`repro.core.filtering.decode_block_tier_select`), which is
    what keeps all of them bit-identical in selection.

    Returns ``(idx, val, stats)`` with ``stats`` int32 ``[bh, 4]``
    selection counts when ``with_stats`` (else None)."""
    blk_valid = s0 > NEG_INF / 2
    keep = blk_valid
    if not keep_all:
        theta0 = flt.eq3_threshold(s0, alphas[0], keep)
        keep = jnp.logical_and(keep, s0 >= theta0)
        theta1 = flt.eq3_threshold(s1, alphas[1], keep)
        keep = jnp.logical_and(keep, s1 >= theta1)

    newest = (cl_bh - 1) // key_block
    lb_bh = None
    if live_budget is not None:
        lb_bh = jnp.repeat(live_budget.astype(jnp.int32), heads)
    if with_stats:
        idx, val, sel_tier = flt.decode_block_tier_select(
            s1, keep, blk_valid, newest, block_budget,
            keep_first=keep_first, keep_diagonal=keep_diagonal,
            live_budget=lb_bh, with_tiers=True,
        )
        stats = flt.selection_stats(flt.FilterResult(
            keep_mask=keep, block_indices=idx,
            survivor_fraction=s1[..., :0], scores=s1,
            block_valid=val, sel_tier=sel_tier, live_mask=blk_valid,
        ))
        return idx, val, stats
    idx, val = flt.decode_block_tier_select(
        s1, keep, blk_valid, newest, block_budget,
        keep_first=keep_first, keep_diagonal=keep_diagonal,
        live_budget=lb_bh,
    )
    return idx, val, None


def fused_paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_codes: jax.Array,
    k_scale: jax.Array,
    block_table: jax.Array,
    cache_length: jax.Array,
    *,
    round_bits: Tuple[int, ...] = (2, 4),
    alphas: Tuple[float, ...] = (0.0, 0.0),
    key_block: int = 64,
    block_budget: int = 8,
    keep_all: bool = False,
    keep_first: bool = True,
    keep_diagonal: bool = True,
    live_budget: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    telemetry: bool = False,
):
    """Fused Pallas decode over a shared page pool (paged l = 1).

    Same pipeline as :func:`fused_decode_attention`, but cache state is
    the page pool and both kernels address it through the block table:
    the filter kernel's BlockSpec streams physical pages named by the
    table, and the gather kernel composes the survivor table with the
    block table inside its index maps (selected logical block →
    physical page → stream K/V), so unselected *and unmapped* pages
    never leave HBM. The Eq. 3 + tier-selection step between the
    kernels is shared with the unpaged fused path and the XLA paths —
    selections agree bit-for-bit.

    Args:
      q: ``[B, KV, G, d]`` folded GQA query rows.
      k_pool, v_pool: ``[KV, pool_rows, d]`` shared page pools.
      k_codes: int16 ``[KV, pool_rows, d]`` resident filter codes.
      k_scale: f32 ``[KV, num_pages]`` resident per-page scales.
      block_table: int32 ``[B, max_blocks]`` logical → physical pages.
      cache_length: int32 ``[B]`` live logical lengths.
      live_budget: optional int32 ``[B]`` per-slot effective budget.
      telemetry: also return int32 ``[B, 4]`` selection stats (as in
        :func:`fused_decode_attention`).

    Under an active serve mesh with a >1 'model' axis (and KV heads
    divisible by it), the whole pipeline runs inside ``shard_map``:
    each device holds a KV-head shard of the resident pools
    (`paged_pool_pspec`), scores and selects on its *own* per-shard
    survivor tables, and streams only its shard's survivor blocks. Per
    (batch, head) row the filter/selection/gather math is untouched —
    the head axis is embarrassingly parallel — and the tiny ``[B, KV,
    G, d]`` output is all-gathered (an exact concatenation) back to
    replicated, so engaging tensor parallelism cannot perturb the
    bit-identical stream contracts. Telemetry stats psum over the mesh
    axis (int32 head sums — order-free).

    Returns:
      ``[B, KV, G, d]`` attention output (dtype of v_pool); with
      ``telemetry``, ``(out, stats)``.
    """
    if len(round_bits) != 2:
        raise ValueError("fused decode kernel supports 2-round configs")
    interpret = _default_interpret() if interpret is None else interpret
    kw = dict(
        round_bits=tuple(round_bits), alphas=tuple(alphas),
        key_block=key_block, block_budget=block_budget,
        keep_all=keep_all, keep_first=keep_first,
        keep_diagonal=keep_diagonal, scale=scale, interpret=interpret,
        with_stats=telemetry,
    )
    mesh = _tp_mesh(q.shape[1])
    if mesh is None:
        out, stats = _paged_decode_core(
            q, k_pool, v_pool, k_codes, k_scale, block_table,
            cache_length, live_budget, **kw,
        )
        return (out, stats) if telemetry else out

    args = [q, k_pool, v_pool, k_codes, k_scale, block_table,
            cache_length]
    specs = [
        P(None, "model", None, None),       # q: KV heads over TP
        P("model", None, None),             # k_pool
        P("model", None, None),             # v_pool
        P("model", None, None),             # k_codes
        P("model", None),                   # k_scale
        P(None, None),                      # block_table (replicated)
        P(None),                            # cache_length (replicated)
    ]
    has_lb = live_budget is not None
    if has_lb:
        args.append(live_budget)
        specs.append(P(None))

    def body(*xs):
        lb = xs[7] if has_lb else None
        out, stats = _paged_decode_core(*xs[:7], lb, **kw)
        out = jax.lax.all_gather(out, "model", axis=1, tiled=True)
        if stats is not None:
            stats = jax.lax.psum(stats, "model")
        return (out, stats) if telemetry else out

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P(), P()) if telemetry else P(),
        check_vma=False,
    )
    return fn(*args)


def _paged_decode_core(
    q, k_pool, v_pool, k_codes, k_scale, block_table, cache_length,
    live_budget, **kw,
):
    """Shard-local fused paged decode: the pipeline of
    :func:`fused_paged_decode_attention` over whatever KV-head slice of
    the pools the caller holds (the full pools on a single device).
    Returns ``(out, stats_or_None)``."""
    heads, d = q.shape[1], q.shape[-1]
    bk = kw["key_block"]
    num_pages = k_scale.shape[-1]
    n_pages = heads * num_pages
    return _decode_pipeline(
        q,
        k_pool.reshape(n_pages, bk, d),
        v_pool.reshape(n_pages, bk, d),
        k_codes.reshape(n_pages, bk, d),
        k_scale.reshape(n_pages, 1),
        _head_offset_table(block_table, heads, num_pages),
        cache_length, live_budget, **kw,
    )


def _decode_pipeline(
    q, k_pages, v_pages, codes_pages, scale_pages, bt_bh, cache_length,
    live_budget, *, round_bits, alphas, key_block, block_budget,
    keep_all, keep_first, keep_diagonal, scale, interpret, with_stats,
):
    """Filter kernel → shared selection → gather kernel over page-major
    operands ``[n_pages, bk, d]`` addressed by the per-(batch, head)
    table ``bt_bh [B·H, mb]``. Returns ``(out, stats_or_None)``."""
    batch, heads, g, d = q.shape
    bh = batch * heads

    q16 = qlib.quantize_int16(q, axis=-1)
    qp = q16.bit_plane(round_bits[-1]).reshape(bh, g, d)
    qs = q16.scale.reshape(bh, g, 1)
    cl_bh = jnp.repeat(cache_length.astype(jnp.int32), heads)

    s0, s1 = dec_kernel.mpmrf_paged_filter_scores(
        qp, qs, codes_pages, scale_pages, bt_bh, cl_bh,
        round_bits=tuple(round_bits),
        key_block=key_block,
        interpret=interpret,
    )

    idx, val, stats = _fused_decode_select(
        s0, s1, cl_bh,
        alphas=alphas, key_block=key_block, block_budget=block_budget,
        keep_all=keep_all, keep_first=keep_first,
        keep_diagonal=keep_diagonal,
        live_budget=live_budget, heads=heads, with_stats=with_stats,
    )

    out = dec_kernel.paged_decode_gather_attention(
        q.reshape(bh, g, d), k_pages, v_pages,
        idx, val, bt_bh, cl_bh,
        key_block=key_block, scale=scale, interpret=interpret,
    )
    out = out.reshape(batch, heads, g, d)
    if with_stats:
        return out, stats.reshape(batch, heads, 4).sum(axis=1)
    return out, None


def _fused_prefill_select(
    s0: jax.Array,
    s1: jax.Array,
    *,
    round_bits: Tuple[int, ...],
    alphas: Tuple[float, ...],
    query_block: int,
    key_block: int,
    block_budget: int,
    keep_all: bool,
    keep_first: bool,
    keep_diagonal: bool,
    diag_blocks: Optional[jax.Array],
    heads: int,
    with_stats: bool = False,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Eq. 3 rounds + safeguards + top-B on the kernel's block-max
    ``[bh, n_qb, n_kb]`` planes — through the one prefill selection
    helper the XLA path also uses
    (:func:`repro.core.filtering.prefill_block_select_from_planes`),
    which is what keeps fused and unfused prefill selection
    bit-identical (the prefix-sharing chunk-grid contract).

    Returns ``(idx, val, stats)`` with ``stats`` int32 ``[bh, 4]``
    selection counts when ``with_stats`` (else None)."""
    n_kb = s0.shape[-1]
    mcfg = flt.MPMRFConfig(
        round_bits=tuple(round_bits),
        alphas=tuple(alphas),
        granularity="block",
        query_block=query_block,
        key_block=key_block,
        block_budget=block_budget,
        keep_first=keep_first,
        keep_diagonal=keep_diagonal,
        reuse_partial=True,
        keep_all=keep_all,
    )
    diag_mask = None
    if keep_diagonal and diag_blocks is not None:
        # [B, n_qb] → [bh, n_qb]: every head of a batch row shares the
        # same diagonal targets (batch-major bh fold).
        db = jnp.repeat(diag_blocks.astype(jnp.int32), heads, axis=0)
        diag_mask = jax.nn.one_hot(
            jnp.clip(db, 0, n_kb - 1), n_kb, dtype=bool
        )
    res = flt.prefill_block_select_from_planes(
        [s0, s1], s0 > NEG_INF / 2, mcfg, diag_mask=diag_mask,
        with_stats=with_stats,
    )
    stats = flt.selection_stats(res) if with_stats else None
    return res.block_indices, res.block_valid, stats


def fused_prefill_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_codes: jax.Array,
    k_block_scale: jax.Array,
    q_positions: jax.Array,
    *,
    round_bits: Tuple[int, ...] = (2, 4),
    alphas: Tuple[float, ...] = (0.0, 0.0),
    query_block: int = 128,
    key_block: int = 128,
    filter_block: int = 64,
    block_budget: int = 8,
    keep_all: bool = False,
    keep_first: bool = True,
    keep_diagonal: bool = True,
    diag_blocks: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    telemetry: bool = False,
):
    """Fused Pallas prefill over the resident filter cache.

    The prefill twin of :func:`fused_decode_attention`: the filter
    kernel derives both rounds' bit planes in-register from the cached
    int16 codes (no plane tensors in HBM, no re-quantization of the
    float cache) and pools Eq. 3 scores per query block on-chip; the
    shared selection helper picks top-B survivor key blocks per query
    block on the tiny ``[bh, n_qb, n_kb]`` planes in XLA; the gather
    kernel streams only the survivor K/V blocks via the scalar-prefetch
    survivor table.

    Args:
      q: ``[B, H, n_q, d]`` folded chunk rows (H = KV heads).
      k_cache, v_cache: ``[B, H, n_k, d]`` padded caches.
      k_codes: int16 ``[B, H, n_k, d]`` resident filter codes.
      k_block_scale: f32 ``[B, H, n_k // filter_block]`` resident
        per-block scales (``filter_block`` = the decode key block the
        cache quantizes at — prefill key tiles may span several).
      q_positions: int32 ``[B, n_q]`` absolute position per query row
        (sentinels ≥ n_k).
      diag_blocks: optional int32 ``[B, n_qb]`` keep_diagonal targets
        (the caller derives them from ``q_positions`` exactly as the
        XLA path does).
      telemetry: also return int32 ``[B, 4]`` selection stats summed
        over heads and query blocks.

    Returns:
      ``[B, H, n_q, d]`` attention output (dtype of v_cache); with
      ``telemetry``, ``(out, stats)``.
    """
    if len(round_bits) != 2:
        raise ValueError("fused prefill kernel supports 2-round configs")
    interpret = _default_interpret() if interpret is None else interpret
    batch, heads, _, d = q.shape
    fb = filter_block
    n_fb = k_cache.shape[-2] // fb
    n_pages = batch * heads * n_fb
    out, stats = _prefill_pipeline(
        q,
        k_cache.reshape(n_pages, fb, d),
        v_cache.reshape(n_pages, fb, d),
        k_codes.reshape(n_pages, fb, d),
        k_block_scale.reshape(n_pages, 1),
        _identity_table(batch * heads, n_fb),
        q_positions, diag_blocks,
        round_bits=tuple(round_bits), alphas=tuple(alphas),
        query_block=query_block, key_block=key_block,
        block_budget=block_budget, keep_all=keep_all,
        keep_first=keep_first, keep_diagonal=keep_diagonal,
        scale=scale, interpret=interpret, with_stats=telemetry,
    )
    return (out, stats) if telemetry else out


def fused_paged_prefill_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_codes: jax.Array,
    k_scale: jax.Array,
    block_table: jax.Array,
    q_positions: jax.Array,
    *,
    round_bits: Tuple[int, ...] = (2, 4),
    alphas: Tuple[float, ...] = (0.0, 0.0),
    query_block: int = 128,
    key_block: int = 128,
    block_budget: int = 8,
    keep_all: bool = False,
    keep_first: bool = True,
    keep_diagonal: bool = True,
    diag_blocks: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    telemetry: bool = False,
):
    """Fused Pallas prefill over a shared page pool.

    Same pipeline as :func:`fused_prefill_attention`, but cache state is
    the page pool and both kernels address it through the block table:
    the filter kernel's BlockSpec streams physical pages named by the
    table, and the gather kernel composes the survivor table with the
    block table inside its index maps (selected logical block →
    physical page → stream K/V), so unselected *and unmapped* pages
    never leave HBM. ``key_block`` (the selection granularity) must be
    a whole number of pages; the page size is the pools' rows per
    ``k_scale`` entry.

    Args:
      q: ``[B, KV, n_q, d]`` folded chunk rows.
      k_pool, v_pool: ``[KV, pool_rows, d]`` shared page pools.
      k_codes: int16 ``[KV, pool_rows, d]`` resident filter codes.
      k_scale: f32 ``[KV, num_pages]`` resident per-page scales.
      block_table: int32 ``[B, max_blocks]`` logical → physical pages.
      q_positions: int32 ``[B, n_q]`` absolute positions per query row.
      diag_blocks: optional int32 ``[B, n_qb]`` keep_diagonal targets.
      telemetry: also return int32 ``[B, 4]`` selection stats summed
        over heads and query blocks.

    Under an active serve mesh with a >1 'model' axis, the pipeline
    runs inside ``shard_map`` with KV-head-sharded pools and per-shard
    survivor tables, exactly as :func:`fused_paged_decode_attention` —
    the prefill twin shares its engagement rule, its all-gathered
    (exact) output, and its bit-identity argument.

    Returns:
      ``[B, KV, n_q, d]`` attention output (dtype of v_pool); with
      ``telemetry``, ``(out, stats)``.
    """
    if len(round_bits) != 2:
        raise ValueError("fused prefill kernel supports 2-round configs")
    interpret = _default_interpret() if interpret is None else interpret
    kw = dict(
        round_bits=tuple(round_bits), alphas=tuple(alphas),
        query_block=query_block, key_block=key_block,
        block_budget=block_budget, keep_all=keep_all,
        keep_first=keep_first, keep_diagonal=keep_diagonal,
        scale=scale, interpret=interpret, with_stats=telemetry,
    )
    mesh = _tp_mesh(q.shape[1])
    if mesh is None:
        out, stats = _paged_prefill_core(
            q, k_pool, v_pool, k_codes, k_scale, block_table,
            q_positions, diag_blocks, **kw,
        )
        return (out, stats) if telemetry else out

    args = [q, k_pool, v_pool, k_codes, k_scale, block_table,
            q_positions]
    specs = [
        P(None, "model", None, None),       # q: KV heads over TP
        P("model", None, None),             # k_pool
        P("model", None, None),             # v_pool
        P("model", None, None),             # k_codes
        P("model", None),                   # k_scale
        P(None, None),                      # block_table (replicated)
        P(None, None),                      # q_positions (replicated)
    ]
    has_diag = diag_blocks is not None
    if has_diag:
        args.append(diag_blocks)
        specs.append(P(None, None))

    def body(*xs):
        db = xs[7] if has_diag else None
        out, stats = _paged_prefill_core(*xs[:7], db, **kw)
        out = jax.lax.all_gather(out, "model", axis=1, tiled=True)
        if stats is not None:
            stats = jax.lax.psum(stats, "model")
        return (out, stats) if telemetry else out

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=(P(), P()) if telemetry else P(),
        check_vma=False,
    )
    return fn(*args)


def _paged_prefill_core(
    q, k_pool, v_pool, k_codes, k_scale, block_table, q_positions,
    diag_blocks, **kw,
):
    """Shard-local fused paged prefill: the pipeline of
    :func:`fused_paged_prefill_attention` over whatever KV-head slice
    of the pools the caller holds. Returns ``(out, stats_or_None)``."""
    heads, d = q.shape[1], q.shape[-1]
    num_pages = k_scale.shape[-1]
    page = k_pool.shape[-2] // num_pages
    n_pages = heads * num_pages
    return _prefill_pipeline(
        q,
        k_pool.reshape(n_pages, page, d),
        v_pool.reshape(n_pages, page, d),
        k_codes.reshape(n_pages, page, d),
        k_scale.reshape(n_pages, 1),
        _head_offset_table(block_table, heads, num_pages),
        q_positions, diag_blocks, **kw,
    )


def _prefill_pipeline(
    q, k_pages, v_pages, codes_pages, scale_pages, bt_bh, q_positions,
    diag_blocks, *, round_bits, alphas, query_block, key_block,
    block_budget, keep_all, keep_first, keep_diagonal, scale,
    interpret, with_stats,
):
    """Filter kernel → shared selection → gather kernel over page-major
    operands ``[n_pages, page, d]`` addressed by ``bt_bh [B·H, mb]``.

    Selection runs at ``key_block`` granularity, a whole number ``r``
    of pages: the kernels work per page, the filter's page maxima are
    max-pooled r at a time (max of maxima — exact), and each selected
    key block expands to its r pages for the gather. Returns
    ``(out, stats_or_None)``."""
    batch, heads, n_q, d = q.shape
    page = k_pages.shape[-2]
    if key_block % page:
        raise ValueError(
            f"key block {key_block} is not a whole number of {page}-row pages"
        )
    r = key_block // page
    mb = bt_bh.shape[-1]
    if mb % r:
        raise ValueError(f"{mb} pages do not tile {key_block}-row key blocks")
    bh = batch * heads

    q16 = qlib.quantize_int16(q, axis=-1)
    qp = q16.bit_plane(round_bits[-1]).reshape(bh, n_q, d)
    qs = q16.scale.reshape(bh, n_q, 1)
    qpos_bh = jnp.repeat(q_positions.astype(jnp.int32), heads, axis=0)

    s0, s1 = pre_kernel.mpmrf_paged_prefill_filter_scores(
        qp, qs, qpos_bh, codes_pages, scale_pages, bt_bh,
        round_bits=tuple(round_bits),
        query_block=query_block,
        key_block=page,
        interpret=interpret,
    )
    if r > 1:
        n_qb = n_q // query_block
        s0 = jnp.max(s0.reshape(bh, n_qb, mb // r, r), axis=-1)
        s1 = jnp.max(s1.reshape(bh, n_qb, mb // r, r), axis=-1)

    idx, val, stats = _fused_prefill_select(
        s0, s1,
        round_bits=round_bits, alphas=alphas,
        query_block=query_block, key_block=key_block,
        block_budget=block_budget, keep_all=keep_all,
        keep_first=keep_first, keep_diagonal=keep_diagonal,
        diag_blocks=diag_blocks, heads=heads, with_stats=with_stats,
    )
    if r > 1:
        idx = (idx[..., None] * r + jnp.arange(r, dtype=idx.dtype))
        idx = idx.reshape(*idx.shape[:-2], -1)
        val = jnp.repeat(val, r, axis=-1)

    out = pre_kernel.paged_prefill_gather_attention(
        q.reshape(bh, n_q, d), qpos_bh, k_pages, v_pages,
        idx, val, bt_bh,
        query_block=query_block, key_block=page,
        scale=scale, interpret=interpret,
    )
    out = out.reshape(batch, heads, n_q, d)
    if with_stats:
        return out, stats.reshape(batch, heads, 4).sum(axis=1)
    return out, None


@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6),
)
def energon_block_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_budget: int = 8,
    query_block: int = 128,
    key_block: int = 128,
    causal: bool = True,
) -> jax.Array:
    """End-to-end Energon attention via Pallas (FU kernel + AU kernel).

    Differentiable: the VJP recomputes through the XLA block path with
    the same selection (selection itself is non-differentiable, as in
    straight-through sparse attention).
    """
    idx, val = mpmrf_select_blocks(
        q, k,
        block_budget=block_budget,
        query_block=query_block, key_block=key_block,
        causal=causal,
    )
    return block_sparse_attention(
        q, k, v, idx, val,
        query_block=query_block, key_block=key_block, causal=causal,
    )


def _eba_fwd(q, k, v, block_budget, query_block, key_block, causal):
    idx, val = mpmrf_select_blocks(
        q, k,
        block_budget=block_budget,
        query_block=query_block, key_block=key_block,
        causal=causal,
    )
    out = block_sparse_attention(
        q, k, v, idx, val,
        query_block=query_block, key_block=key_block, causal=causal,
    )
    return out, (q, k, v, idx, val)


def _eba_bwd(block_budget, query_block, key_block, causal, res, g):
    q, k, v, idx, val = res

    def xla_path(q, k, v):
        from repro.kernels import ref as kref

        return kref.block_sparse_attention_ref(
            q, k, v, idx, val,
            query_block=query_block, key_block=key_block, causal=causal,
        )

    _, vjp = jax.vjp(xla_path, q, k, v)
    return vjp(g)


energon_block_attention.defvjp(_eba_fwd, _eba_bwd)
