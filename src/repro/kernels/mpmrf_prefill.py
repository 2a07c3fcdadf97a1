"""Fused MP-MRF prefill kernels (the prefill twin of ``mpmrf_decode``).

Serve-time chunked prefill: a C-token chunk (folded GQA rows at per-row
absolute positions) attends the cache it just updated. The XLA path
re-streams the whole cache — float K for quantization plus K/V for the
gather — every chunk; at 1–2k context that re-quantize traffic
dominates prefill. These kernels keep the filter on the *resident*
per-page ``k_codes``/``k_scale`` planes instead, and address the cache
as pages through a block table (an unpaged cache is an identity table
over its own rows, :mod:`repro.kernels.ops`):

* :func:`mpmrf_paged_prefill_filter_scores` — grid ``(live, mb)``
  over the live query blocks ``(b, i)`` of ``[bh, n_qb]``: each step
  streams one page's int16 codes once, derives both rounds' bit planes
  *in-register* (arithmetic shifts — no plane tensors in HBM), runs the
  Fig. 7 shift-and-add scoring for one query block on the MXU, and
  pools the Eq. 3 scores per *query block* on-chip (block-max across
  the chunk's rows) into two ``[bh, n_qb, mb]`` planes. Callers whose
  key tile spans several pages pool those pages by max afterwards —
  exact, since max pooling nests.
* :func:`paged_prefill_gather_attention` — grid ``(live, budget)``:
  block-gather flash attention whose K/V BlockSpec index maps compose
  survivor table ∘ block table (``bt[b, idx[b, i, j]]``) — unselected
  *and* unmapped pages never leave HBM, exactly as the decode kernels.
* :func:`paged_prefill_dense_attention` (``paged_prefill_dense``) —
  grid ``(B, mb)``, for the layers below ``min_prune_layer``, which do
  not prune: causal flash attention of slot b's folded rows of every KV
  head to its logical block j. One ``(KV, bk, d)`` BlockSpec each for K
  and V fetches all heads' copies of the page ``bt[b, j]`` in one
  strided DMA (as the decode filter's codes); the slot's query rows and
  online-softmax state stay resident in VMEM across j, and blocks past
  the slot's written extent are neither computed nor fetched. It engages
  where the two kernels above do (impl ``pallas``, resident planes, key
  tiles of whole pages), so no score tensor is materialized in any
  layer of a served prefill.

Masking is per query row: ``(kpos <= q_position) & (q_position < n_k)``
— the same rule the XLA ``q_positions`` path applies, so ragged tail
chunks and padding sentinel rows (position ≥ n_k, wholly invalid)
cannot leak garbage into the pooled planes. A query block with no live
row (a slot that is not prefilling in this wave) is wholly masked, so
the filter and gather skip it: their leading grid axis is the dynamic
count of live blocks, listed first in a scalar-prefetch operand, and
XLA writes the value such a block reads (NEG_INF planes, zero rows).
Eq. 3 thresholds and the top-B selection run between the kernels in
plain XLA on the tiny ``[bh, n_qb, n_kb]`` planes, through the *same*
selection helper as the XLA path
(:func:`repro.core.filtering.prefill_block_select_from_planes`)
— fused and unfused prefill selection is bit-identical, which the
prefix-sharing chunk-grid skip contract depends on (DESIGN.md §4).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mpmrf_decode import apply_page_scale, round_maxima, store_lane

NEG_INF = -1e30
_LANES = 128


def _live_query_blocks(q_positions, query_block: int, n_k: int):
    """Which ``[bh, n_qb]`` query blocks hold a row below ``n_k``, their
    flat ids with the live ones first, and how many grid steps to run
    over them (at least one: a dead block reads NEG_INF / 0 anyway).

    A chunk wave is compiled for every slot, but only the slots that
    are prefilling carry live rows; the rest are sentinels. The fused
    prefill kernels take this list as a scalar-prefetch operand and a
    dynamic leading grid axis, so dead query blocks cost no grid step —
    not even the fixed per-step cost a ``pl.when`` skip would still pay.
    """
    bh, n_q = q_positions.shape
    n_qb = n_q // query_block
    live = jnp.any(
        q_positions.reshape(bh, n_qb, query_block) < n_k, axis=-1
    )
    order = jnp.argsort(~live.reshape(-1), stable=True).astype(jnp.int32)
    steps = jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)
    return live, order, steps


def _paged_prefill_filter_kernel(
    order_ref, bt_ref,                         # scalar-prefetch operands
    qp_ref, qs_ref, qpos_ref, kc_ref,
    m0_ref, m1_ref,
    *, lo: int, hi: int, block_k: int, n_k: int,
):
    """Grid step (k, j) streams the *physical page* ``bt[b, j]``
    holding row b's logical block j for the k-th live query block
    ``(b, i)`` — the BlockSpec index maps read the scalar-prefetched
    block table, so the HBM→VMEM pipeline only ever touches pages the
    table names. Unmapped logical blocks alias whatever the table
    carries: their logical positions exceed every real query position,
    so all their scores are NEG_INF-masked."""
    j = pl.program_id(1)
    bq = qp_ref.shape[0]
    qpos = qpos_ref[...]                              # [bq, 1] int32
    # positions are *logical*: block j's tokens, wherever they live
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1
    )
    ok = jnp.logical_and(kpos <= qpos, qpos < n_k)
    m0, m1 = round_maxima(
        qp_ref[...], kc_ref[...], qs_ref[...], ok, lo=lo, hi=hi
    )
    store_lane(m0_ref, j, m0)
    store_lane(m1_ref, j, m1)


@functools.partial(
    jax.jit,
    static_argnames=("round_bits", "query_block", "key_block", "interpret"),
)
def mpmrf_paged_prefill_filter_scores(
    q_plane: jax.Array,
    q_scale: jax.Array,
    q_positions: jax.Array,
    k_codes_pages: jax.Array,
    k_page_scale: jax.Array,
    block_table: jax.Array,
    *,
    round_bits: Tuple[int, int] = (2, 4),
    query_block: int = 128,
    key_block: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Two-round on-chip-pooled prefill scores off the resident pool.

    Args:
      q_plane: int ``[bh, n_q, d]`` query hi-bit plane (fits int8).
      q_scale: float32 ``[bh, n_q, 1]`` per-row quantization scales.
      q_positions: int32 ``[bh, n_q]`` absolute position per query row.
      k_codes_pages: int16 ``[n_pages, bk, d]`` pool codes, page-major
        (KV-head axis folded into the page axis by the caller);
        ``key_block`` is the page's row count.
      k_page_scale: float32 ``[n_pages, 1]`` per-page scales.
      block_table: int32 ``[bh, mb]`` physical page of each logical
        block (already head-offset).

    Returns:
      ``(s0, s1)`` float32 ``[bh, n_qb, mb]`` block-max score planes.
    """
    lo, hi = round_bits
    bh, n_q, d = q_plane.shape
    bq, bk = query_block, key_block
    if n_q % bq:
        raise ValueError(f"chunk rows {n_q} not divisible by {bq}")
    n_qb = n_q // bq
    mb = block_table.shape[-1]
    n_k = mb * bk
    block_table = block_table.astype(jnp.int32)

    q_positions = q_positions.astype(jnp.int32)
    live, order, steps = _live_query_blocks(q_positions, bq, n_k)

    kernel = functools.partial(
        _paged_prefill_filter_kernel, lo=lo, hi=hi, block_k=bk, n_k=n_k
    )

    def rows(k, j, order, bt):
        return order[k] // n_qb, order[k] % n_qb, 0

    def page(k, j, order, bt):
        return bt[order[k] // n_qb, j], 0, 0

    def out_map(k, j, order, bt):
        return order[k] // n_qb, order[k] % n_qb, 0, 0

    out_spec = pl.BlockSpec((None, None, 1, mb), out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps, mb),
        in_specs=[
            pl.BlockSpec((None, bq, d), rows),
            pl.BlockSpec((None, bq, 1), rows),
            pl.BlockSpec((None, bq, 1), rows),
            pl.BlockSpec((None, bk, d), page),
        ],
        out_specs=[out_spec, out_spec],
    )
    m0, m1 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_qb, 1, mb), jnp.float32),
            jax.ShapeDtypeStruct((bh, n_qb, 1, mb), jnp.float32),
        ],
        interpret=interpret,
        name="mpmrf_prefill_filter",
    )(
        order,
        block_table,
        q_plane.astype(jnp.int8),
        q_scale.astype(jnp.float32),
        q_positions[..., None],
        k_codes_pages,
    )
    # query blocks with no live row were never visited: every key of
    # theirs is masked, which reads NEG_INF
    live = live[..., None]
    m0 = jnp.where(live, m0[:, :, 0, :], NEG_INF)
    m1 = jnp.where(live, m1[:, :, 0, :], NEG_INF)
    ks = k_page_scale.astype(jnp.float32)[:, 0][block_table][:, None, :]
    return apply_page_scale(m0, ks, lo), apply_page_scale(m1, ks, hi)


def _paged_prefill_gather_kernel(
    order_ref, idx_ref, val_ref, bt_ref,  # scalar-prefetch operands
    q_ref, qpos_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, block_k: int, budget: int, n_k: int, n_qb: int,
):
    """Paged survivor-gather: the K/V BlockSpec index maps compose the
    survivor table with the block table (``bt[b, idx[b, qb, slot]]`` —
    selected logical block → physical page), so the HBM→VMEM pipeline
    streams exactly the selected resident pages: unselected *and
    unmapped* pages never leave HBM. Flash (online-softmax)
    accumulation; position masking uses the *logical* block id. Step
    (k, slot) serves the k-th live query block ``(b, qb)``."""
    b = order_ref[pl.program_id(0)] // n_qb
    qb = order_ref[pl.program_id(0)] % n_qb
    slot = pl.program_id(1)

    @pl.when(slot == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    kb = idx_ref[b, qb, slot]             # logical block id
    is_valid = val_ref[b, qb, slot]

    q = q_ref[...].astype(jnp.float32)                # [bq, d]
    k = k_ref[...].astype(jnp.float32)                # [bk, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                      # [bq, bk]

    bq = q.shape[0]
    qpos = qpos_ref[...]                              # [bq, 1] int32
    kpos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1
    )
    mask = jnp.logical_and(
        is_valid > 0,
        jnp.logical_and(kpos <= qpos, qpos < n_k),
    )
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scratch[:, 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scratch[:, 0:1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scratch[...] = acc_scratch[...] * corr + jax.lax.dot(
        p, v_ref[...].astype(jnp.float32)
    )
    m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
    l_scratch[...] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(slot == budget - 1)
    def _finalize():
        o_ref[...] = (
            acc_scratch[...] / jnp.maximum(l_scratch[:, 0:1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("query_block", "key_block", "scale", "interpret"),
)
def paged_prefill_gather_attention(
    q: jax.Array,
    q_positions: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_indices: jax.Array,
    block_valid: jax.Array,
    block_table: jax.Array,
    *,
    query_block: int = 128,
    key_block: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Two-level survivor-table prefill attention over a page pool.

    Args:
      q: ``[bh, n_q, d]`` folded chunk rows.
      q_positions: int32 ``[bh, n_q]`` absolute positions per row.
      k_pages, v_pages: ``[n_pages, bk, d]`` page-major pools (KV-head
        axis folded into the page axis by the caller).
      block_indices / block_valid: int32 ``[bh, n_qb, budget]`` —
        *logical* survivor block ids + validity bits.
      block_table: int32 ``[bh, mb]`` logical block → physical page
        (head-offset); composed with the survivor table inside the
        BlockSpec index maps.
    """
    bh, n_q, d = q.shape
    bq, bk = query_block, key_block
    if n_q % bq:
        raise ValueError(f"chunk rows {n_q} not divisible by {bq}")
    n_qb = n_q // bq
    mb = block_table.shape[-1]
    n_k = mb * bk
    budget = block_indices.shape[-1]
    sm_scale = scale if scale is not None else 1.0 / (d ** 0.5)

    q_positions = q_positions.astype(jnp.int32)
    live, order, steps = _live_query_blocks(q_positions, bq, n_k)

    kernel = functools.partial(
        _paged_prefill_gather_kernel,
        sm_scale=sm_scale, block_k=bk, budget=budget, n_k=n_k, n_qb=n_qb,
    )

    def rows(k, j, order, idx, val, bt):
        return order[k] // n_qb, order[k] % n_qb, 0

    def page(k, j, order, idx, val, bt):
        b, i = order[k] // n_qb, order[k] % n_qb
        return bt[b, idx[b, i, j]], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps, budget),
        in_specs=[
            pl.BlockSpec((None, bq, d), rows),
            pl.BlockSpec((None, bq, 1), rows),
            pl.BlockSpec((None, bk, d), page),
            pl.BlockSpec((None, bk, d), page),
        ],
        out_specs=pl.BlockSpec((None, bq, d), rows),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, n_q, d), v_pages.dtype),
        interpret=interpret,
        name="mpmrf_prefill_gather",
    )(
        order,
        block_indices.astype(jnp.int32),
        block_valid.astype(jnp.int32),
        block_table.astype(jnp.int32),
        q,
        q_positions[..., None],
        k_pages, v_pages,
    )
    # query blocks with no live row were never visited; every row of
    # theirs is masked, which reads 0
    out = out.reshape(bh, n_qb, bq, d)
    out = jnp.where(live[:, :, None, None], out, jnp.zeros((), out.dtype))
    return out.reshape(bh, n_q, d)


#: VMEM the dense prefill kernel may use: the slot's double-buffered
#: query and output rows, its f32 accumulator and lane-wide softmax
#: maxima and sums, and the page blocks — ≈ 14 MB at Qwen3-14B's 8 KV
#: heads × 640 folded rows × 128, next to the 16 MiB default limit.
_DENSE_VMEM_BYTES = 64 << 20


def _paged_prefill_dense_kernel(
    bt_ref, ext_ref,                      # scalar-prefetch operands
    q_ref, qpos_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, block_k: int, n_k: int,
):
    """Grid step (b, j) attends slot b's folded query rows of every KV
    head to its logical block j. The K/V BlockSpecs fetch ``[KV, bk,
    d]`` — each head's copy of the physical page ``bt[b, j]``, one
    strided DMA each; the query rows and the f32 online-softmax state
    stay resident across j. Blocks at or past the slot's written extent
    (all of them for a slot with no live row) are not computed, and
    their index map repeats the last live page, so no copy starts."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    kv, rows, _ = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    @pl.when(j * block_k < ext_ref[b])
    def _attend():
        qpos = qpos_ref[...]                          # [rows, 1] int32
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1
        )
        ok = jnp.logical_and(kpos <= qpos, qpos < n_k)
        for h in range(kv):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                              # [rows, bk]
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scratch[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_scratch[h, :, 0:1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            acc_scratch[h] = acc_scratch[h] * corr + jax.lax.dot(
                p.astype(v_ref.dtype), v_ref[h],
                preferred_element_type=jnp.float32,
            )
            m_scratch[h] = jnp.broadcast_to(m_new, (rows, _LANES))
            l_scratch[h] = jnp.broadcast_to(l_new, (rows, _LANES))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (
            acc_scratch[...] / jnp.maximum(l_scratch[:, :, 0:1], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("key_block", "scale", "interpret")
)
def paged_prefill_dense_attention(
    q: jax.Array,
    q_positions: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    *,
    key_block: int = 64,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal dense prefill attention straight off the page pool.

    Flash (online-softmax) attention of each slot's folded chunk rows
    to the pages its block table names, for layers that do not prune:
    no score or mask tensor and no logical K/V view exists in HBM.

    Args:
      q: ``[B, KV, n_q, d]`` folded chunk rows.
      q_positions: int32 ``[B, n_q]`` absolute position per row
        (sentinels ≥ the logical rows are wholly masked; they read 0).
      k_pool, v_pool: ``[S, KV, rows, d]`` with pages of ``key_block``
        rows along ``rows``: ``S = 1`` for a shared page pool, or
        ``S = B`` for one cache per slot (slot b reads ``k_pool[b]``).
      block_table: int32 ``[B, mb]`` page of each logical block, the
        same for every head. Blocks past a slot's written extent may
        alias any in-range page: they are never read.

    Returns:
      ``[B, KV, n_q, d]`` attention output (dtype of v_pool).
    """
    batch, kv, n_q, d = q.shape
    slots = k_pool.shape[0]
    if slots not in (1, batch):
        raise ValueError(f"{slots} cache slots for a batch of {batch}")
    bk = key_block
    mb = block_table.shape[-1]
    n_k = mb * bk
    sm_scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q_positions = q_positions.astype(jnp.int32)
    # rows written so far: positions are contiguous per slot, so the
    # highest live position bounds every key a row may attend
    extent = jnp.max(
        jnp.where(q_positions < n_k, q_positions + 1, 0), axis=1
    )

    def kv_map(b, j, bt, ext):
        last = jnp.maximum(ext[b] - 1, 0) // bk
        return (b if slots > 1 else 0, 0, bt[b, jnp.minimum(j, last)], 0)

    def per_slot(*block):
        return pl.BlockSpec(
            (None,) + block, lambda b, j, bt, ext: (b,) + (0,) * len(block)
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, mb),
        in_specs=[
            per_slot(kv, n_q, d),
            per_slot(n_q, 1),
            pl.BlockSpec((None, kv, bk, d), kv_map),
            pl.BlockSpec((None, kv, bk, d), kv_map),
        ],
        out_specs=per_slot(kv, n_q, d),
        scratch_shapes=[
            pltpu.VMEM((kv, n_q, _LANES), jnp.float32),
            pltpu.VMEM((kv, n_q, _LANES), jnp.float32),
            pltpu.VMEM((kv, n_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_prefill_dense_kernel,
            sm_scale=sm_scale, block_k=bk, n_k=n_k,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv, n_q, d), v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_DENSE_VMEM_BYTES
        ),
        interpret=interpret,
        name="paged_prefill_dense",
    )(
        block_table.astype(jnp.int32), extent,
        q, q_positions[..., None], k_pool, v_pool,
    )
