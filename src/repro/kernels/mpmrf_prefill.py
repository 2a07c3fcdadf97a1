"""Fused MP-MRF prefill kernels (the prefill twin of ``mpmrf_decode``).

Serve-time chunked prefill: a C-token chunk (folded GQA rows at per-row
absolute positions) attends the cache it just updated. The XLA path
re-streams the whole cache — float K for quantization plus K/V for the
gather — every chunk; at 1–2k context that re-quantize traffic
dominates prefill. These kernels keep the filter on the *resident*
per-page ``k_codes``/``k_scale`` planes instead, and address the cache
as pages through a block table (an unpaged cache is an identity table
over its own rows, :mod:`repro.kernels.ops`):

* :func:`mpmrf_paged_prefill_filter_scores` — grid ``(bh, n_qb, mb)``:
  each step streams one page's int16 codes once, derives both rounds'
  bit planes *in-register* (arithmetic shifts — no plane tensors in
  HBM), runs the Fig. 7 shift-and-add scoring for one query block on
  the MXU, and pools the Eq. 3 scores per *query block* on-chip
  (block-max across the chunk's rows) into two ``[bh, n_qb, mb]``
  planes. Callers whose key tile spans several pages pool those pages
  by max afterwards — exact, since max pooling nests.
* :func:`paged_prefill_gather_attention` — grid ``(bh, n_qb, budget)``:
  block-gather flash attention whose K/V BlockSpec index maps compose
  survivor table ∘ block table (``bt[b, idx[b, i, j]]``) — unselected
  *and* unmapped pages never leave HBM, exactly as the decode kernels.

Masking is per query row: ``(kpos <= q_position) & (q_position < n_k)``
— the same rule the XLA ``q_positions`` path applies, so ragged tail
chunks and padding sentinel rows (position ≥ n_k, wholly invalid)
cannot leak garbage into the pooled planes. Eq. 3 thresholds and the
top-B selection run between the kernels in plain XLA on the tiny
``[bh, n_qb, n_kb]`` planes, through the *same* selection helper as the
XLA path (:func:`repro.core.filtering.prefill_block_select_from_planes`)
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


def _paged_prefill_filter_kernel(
    bt_ref,                                    # scalar-prefetch operand
    qp_ref, qs_ref, qpos_ref, kc_ref,
    m0_ref, m1_ref,
    *, lo: int, hi: int, block_k: int, n_k: int,
):
    """Grid step (b, i, j) streams the *physical page* ``bt[b, j]``
    holding row b's logical block j — the BlockSpec index maps read the
    scalar-prefetched block table, so the HBM→VMEM pipeline only ever
    touches pages the table names. Unmapped logical blocks alias
    whatever the table carries: their logical positions exceed every
    real query position, so all their scores are NEG_INF-masked."""
    j = pl.program_id(2)
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

    kernel = functools.partial(
        _paged_prefill_filter_kernel, lo=lo, hi=hi, block_k=bk, n_k=n_k
    )
    out_spec = pl.BlockSpec(
        (None, None, 1, mb), lambda b, i, j, bt: (b, i, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_qb, mb),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, i, j, bt: (b, i, 0)),
            pl.BlockSpec((None, bq, 1), lambda b, i, j, bt: (b, i, 0)),
            pl.BlockSpec((None, bq, 1), lambda b, i, j, bt: (b, i, 0)),
            pl.BlockSpec(
                (None, bk, d), lambda b, i, j, bt: (bt[b, j], 0, 0)
            ),
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
        block_table,
        q_plane.astype(jnp.int8),
        q_scale.astype(jnp.float32),
        q_positions.astype(jnp.int32)[..., None],
        k_codes_pages,
    )
    ks = k_page_scale.astype(jnp.float32)[:, 0][block_table][:, None, :]
    return (
        apply_page_scale(m0[:, :, 0, :], ks, lo),
        apply_page_scale(m1[:, :, 0, :], ks, hi),
    )


def _paged_prefill_gather_kernel(
    idx_ref, val_ref, bt_ref,             # scalar-prefetch operands
    q_ref, qpos_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, block_k: int, budget: int, n_k: int,
):
    """Paged survivor-gather: the K/V BlockSpec index maps compose the
    survivor table with the block table (``bt[b, idx[b, qb, slot]]`` —
    selected logical block → physical page), so the HBM→VMEM pipeline
    streams exactly the selected resident pages: unselected *and
    unmapped* pages never leave HBM. Flash (online-softmax)
    accumulation; position masking uses the *logical* block id."""
    b = pl.program_id(0)
    qb = pl.program_id(1)
    slot = pl.program_id(2)

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

    kernel = functools.partial(
        _paged_prefill_gather_kernel,
        sm_scale=sm_scale, block_k=bk, budget=budget, n_k=n_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bh, n_qb, budget),
        in_specs=[
            pl.BlockSpec(
                (None, bq, d), lambda b, i, j, idx, val, bt: (b, i, 0)
            ),
            pl.BlockSpec(
                (None, bq, 1), lambda b, i, j, idx, val, bt: (b, i, 0)
            ),
            pl.BlockSpec(
                (None, bk, d),
                lambda b, i, j, idx, val, bt: (bt[b, idx[b, i, j]], 0, 0),
            ),
            pl.BlockSpec(
                (None, bk, d),
                lambda b, i, j, idx, val, bt: (bt[b, idx[b, i, j]], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, bq, d), lambda b, i, j, idx, val, bt: (b, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, n_q, d), v_pages.dtype),
        interpret=interpret,
        name="mpmrf_prefill_gather",
    )(
        block_indices.astype(jnp.int32),
        block_valid.astype(jnp.int32),
        block_table.astype(jnp.int32),
        q,
        q_positions.astype(jnp.int32)[..., None],
        k_pages, v_pages,
    )
