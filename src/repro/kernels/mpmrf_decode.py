"""Fused MP-MRF decode kernels (Energon §IV-D, l = 1, on TPU).

The serve-time hot path: folded GQA query groups against a long KV
cache whose *filter operands are resident* — every cache page carries
persistent int16 key codes and one scale (DESIGN.md §3), so the filter
never re-quantizes. The kernels address the cache as pages through a
block table; an unpaged cache is the special case of an identity table
over its own rows (:mod:`repro.kernels.ops`). Two kernels:

* :func:`mpmrf_paged_filter_scores` — grid ``(B, mb)``: step (b, j)
  scores slot b's logical block j for *every* KV head. The pool's
  ``[KV, rows, d]`` layout puts all heads' copies of one page at one row
  offset, so a single ``(KV, bk, d)`` BlockSpec fetches them in one
  strided DMA: bytes/step = ``KV · bk · d`` int16 codes (393 KB for
  phi3-mini's 32 heads of 96 over 64-row pages), once; steps/call =
  ``B · mb``. The step derives the two rounds' bit planes *in-register*
  (arithmetic shifts — no plane materialization in HBM), scores them as
  two int8 MXU matmuls of the heads' ``[KV·G, d]`` query planes against
  their ``[KV·bk, d]`` key planes (int32 accumulation; the hi-bit
  round's product equals Fig. 7's shift-and-add exactly), keeps each
  query row's own head's lanes, and writes the two block maxima of
  every row into lane j. A head's maximum over its G rows, then the
  page's scale, are applied afterwards. Blocks past a slot's
  length are neither fetched nor scored. An unpaged cache is the same
  kernel over a slot axis of per-slot codes with an identity table.
* :func:`paged_decode_gather_attention` — grid ``(bh, budget)``:
  block-gather flash attention over the survivor table. The K/V
  BlockSpec ``index_map`` composes the scalar-prefetched survivor ids
  with the block table, so the HBM→VMEM pipeline only ever streams
  selected pages — during decode, unselected K/V never leaves HBM
  (On-Demand Fetching at serve time).

Eq. 3 thresholds + exact-budget tier selection run between the two
kernels in plain XLA: they touch ``[B·KV, mb]`` scalars — noise next to
the cache streams — and reuse the exact selection rule of the XLA path
(:func:`repro.core.filtering.decode_block_tier_select`), keeping fused
and unfused decode bit-identical in selection.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
_INT_MIN = -(2 ** 31)
#: VMEM for one head group's int32-widened codes inside a filter step.
_GROUP_BYTES = 4 << 20


def round_maxima(qp, codes, qs, ok, *, lo: int, hi: int):
    """Both rounds' masked maxima of ``acc · q_scale`` over one tile.

    ``qp`` int8 ``[rows, d]`` query hi-bit plane, ``codes`` int16
    ``[bk, d]`` one page's resident codes, ``qs`` f32 ``[rows, 1]`` the
    query plane scales, ``ok`` bool ``[rows, bk]`` validity. The planes
    fit int8 (MSB 2 bits, remainder ≤ 4 bits), so both rounds run as
    int8 MXU matmuls with exact int32 accumulation.

    The page's key scale is applied to the maxima by the caller
    (:func:`apply_page_scale`): multiplying by one non-negative scalar
    is monotone under IEEE rounding, so ``max(x · c) == max(x) · c``
    bit for bit and the scores equal the XLA pipeline's
    ``(acc · q_plane_scale) · k_plane_scale`` pooled by max.
    """
    codes = codes.astype(jnp.int32)
    msb = jnp.right_shift(codes, 16 - lo)
    rem = jnp.right_shift(codes, 16 - hi) - jnp.left_shift(msb, hi - lo)
    dims = (((1,), (1,)), ((), ()))
    acc0 = jax.lax.dot_general(
        qp, msb.astype(jnp.int8), dims, preferred_element_type=jnp.int32
    )
    acc1 = jnp.left_shift(acc0, hi - lo) + jax.lax.dot_general(
        qp, rem.astype(jnp.int8), dims, preferred_element_type=jnp.int32
    )
    qs = qs * float(2 ** (16 - hi))
    m0 = jnp.max(jnp.where(ok, acc0.astype(jnp.float32) * qs, NEG_INF))
    m1 = jnp.max(jnp.where(ok, acc1.astype(jnp.float32) * qs, NEG_INF))
    return m0, m1


def store_lane(ref, j, value):
    """``ref[..., j] = value`` as a whole-row vector select — the TPU has
    no scalar store into a vector register at a dynamic lane."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ref.shape, len(ref.shape) - 1)
    ref[...] = jnp.where(lane == j, value, ref[...])


def apply_page_scale(m, page_scale, bits: int):
    """Scale a kernel's per-page maxima by the page's key scale at
    round precision ``bits``; fully masked pages stay NEG_INF."""
    return jnp.where(
        m > NEG_INF / 2, m * (page_scale * float(2 ** (16 - bits))), NEG_INF
    )


def _heads_per_step(kv: int, g: int, block_k: int, d: int) -> int:
    """Heads scored together inside one grid step: the largest divisor
    of ``kv`` whose query rows fill at most one MXU tile (``heads · g ≤
    128``) and whose int32-widened codes fit :data:`_GROUP_BYTES` of
    VMEM."""
    best = 1
    for h in range(1, kv + 1):
        if (kv % h == 0 and h * g <= _LANES
                and h * block_k * d * 4 <= _GROUP_BYTES):
            best = h
    return best


def _paged_filter_kernel(
    bt_ref, cl_ref,                       # scalar-prefetch operands
    qp_ref, qs_ref, kc_ref,
    m0_ref, m1_ref,
    *, lo: int, hi: int, block_k: int, g: int,
):
    """Grid step (b, j) scores slot b's logical block j for every KV
    head at once. The codes BlockSpec fetches ``[KV, bk, d]`` — each
    head's copy of the *physical page* ``bt[b, j]``, one strided DMA.
    Per group of ``hg`` heads the step runs two int8 MXU matmuls of the
    group's ``[hg·g, d]`` query planes against its ``[hg·bk, d]`` key
    planes and keeps, for each query row, the lanes of its own head:
    the products are exact int32, so the other heads' lanes change
    nothing. Round 1 scores the whole hi-bit key plane, whose product
    equals Fig. 7's shift-and-add ``(acc0 << (hi − lo)) + q · rem``
    exactly.

    Maxima are taken on the integers first — ``max_k(f32(acc) · s) ==
    f32(max_k acc) · s`` bit for bit for a query scale ``s > 0``, since
    rounding and scaling are monotone — so each (head, query) row's
    maximum equals :func:`round_maxima`'s. Blocks at or past the slot's
    length are NEG_INF (set once at j = 0) and not computed; their index
    map repeats the last live page, so the pipeline starts no copy."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_grp, rows, _ = m0_ref.shape

    @pl.when(j == 0)
    def _init():
        m0_ref[...] = jnp.full(m0_ref.shape, NEG_INF, jnp.float32)
        m1_ref[...] = jnp.full(m1_ref.shape, NEG_INF, jnp.float32)

    @pl.when(j * block_k < cl_ref[b])
    def _score():
        hg = rows // g
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, hg * block_k), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, hg * block_k), 0)
        # positions are *logical*: block j's tokens, wherever they live
        ok = jnp.logical_and(
            lane // block_k == row // g,
            j * block_k + lane % block_k < cl_ref[b],
        )
        dims = (((1,), (1,)), ((), ()))
        for grp in range(n_grp):
            codes = kc_ref[grp * hg:(grp + 1) * hg].astype(jnp.int32)
            codes = codes.reshape(hg * block_k, codes.shape[-1])
            qp = qp_ref[grp]                                  # [hg·g, d]
            qs = qs_ref[grp] * float(2 ** (16 - hi))          # [hg·g, 1]
            for bits, ref in ((lo, m0_ref), (hi, m1_ref)):
                acc = jax.lax.dot_general(
                    qp, jnp.right_shift(codes, 16 - bits).astype(jnp.int8),
                    dims, preferred_element_type=jnp.int32,
                )                                             # [hg·g, hg·bk]
                t = jnp.max(jnp.where(ok, acc, _INT_MIN), axis=1,
                            keepdims=True)
                store_lane(ref.at[grp], j, t.astype(jnp.float32) * qs)


@functools.partial(
    jax.jit,
    static_argnames=("round_bits", "key_block", "interpret"),
)
def mpmrf_paged_filter_scores(
    q_plane: jax.Array,
    q_scale: jax.Array,
    k_codes: jax.Array,
    k_page_scale: jax.Array,
    block_table: jax.Array,
    cache_length: jax.Array,
    *,
    round_bits: Tuple[int, int] = (2, 4),
    key_block: int = 64,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Two-round block-max decode scores off resident int16 codes.

    Args:
      q_plane: int ``[B, KV·G, d]`` query hi-bit planes (fit int8), head
        major: row ``h·G + i`` is query ``i`` of KV head ``h``.
      q_scale: float32 ``[B, KV·G, 1]`` per-row quantization scales.
      k_codes: int16 ``[S, KV, rows, d]`` codes, pages of ``key_block``
        rows along ``rows``: ``S = 1`` for a shared page pool, or
        ``S = B`` for one cache per slot (slot b reads ``k_codes[b]``).
      k_page_scale: float32 ``[S, KV, rows / key_block]`` page scales.
      block_table: int32 ``[B, mb]`` page of each logical block, the
        same for every head. Blocks at or past ``cache_length`` may
        alias any in-range page: they are never scored.
      cache_length: int32 ``[B]`` live logical lengths.

    Returns:
      ``(s0, s1)`` float32 ``[B, KV, mb]`` block-max scores per round.
    """
    lo, hi = round_bits
    batch, _, d = q_plane.shape
    slots, kv = k_codes.shape[:2]
    if slots not in (1, batch):
        raise ValueError(f"{slots} code slots for a batch of {batch}")
    g = q_plane.shape[1] // kv
    bk = key_block
    mb = block_table.shape[-1]
    hg = _heads_per_step(kv, g, bk, d)
    n_grp = kv // hg
    block_table = block_table.astype(jnp.int32)

    def codes_map(b, j, bt, cl):
        last = jnp.maximum(cl[b] - 1, 0) // bk
        return (b if slots > 1 else 0, 0, bt[b, jnp.minimum(j, last)], 0)

    def per_slot(*block):
        return pl.BlockSpec(
            (None, n_grp) + block, lambda b, j, bt, cl: (b, 0, 0, 0)
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, mb),
        in_specs=[
            per_slot(hg * g, d),
            per_slot(hg * g, 1),
            pl.BlockSpec((None, kv, bk, d), codes_map),
        ],
        out_specs=[per_slot(hg * g, mb), per_slot(hg * g, mb)],
    )
    m0, m1 = pl.pallas_call(
        functools.partial(
            _paged_filter_kernel, lo=lo, hi=hi, block_k=bk, g=g
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, n_grp, hg * g, mb), jnp.float32)
        ] * 2,
        interpret=interpret,
        name="mpmrf_decode_filter",
    )(
        block_table,
        cache_length.astype(jnp.int32),
        q_plane.astype(jnp.int8).reshape(batch, n_grp, hg * g, d),
        q_scale.astype(jnp.float32).reshape(batch, n_grp, hg * g, 1),
        k_codes,
    )
    # a head's maximum over its G query rows (exact)
    m0 = jnp.max(m0.reshape(batch, kv, g, mb), axis=2)
    m1 = jnp.max(m1.reshape(batch, kv, g, mb), axis=2)
    ks = jnp.take_along_axis(                                 # [B, KV, mb]
        k_page_scale.astype(jnp.float32), block_table[:, None, :], axis=-1
    )
    return apply_page_scale(m0, ks, lo), apply_page_scale(m1, ks, hi)


def _paged_gather_kernel(
    idx_ref, val_ref, bt_ref, cl_ref,     # scalar-prefetch operands
    q_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, block_k: int, budget: int,
):
    """Paged survivor-gather: the K/V BlockSpec index maps compose the
    survivor table with the block table (``bt[b, idx[b, slot]]`` —
    selected logical block → physical page), so the HBM→VMEM pipeline
    streams exactly the selected resident pages: unselected *and
    unmapped* pages never leave HBM. Flash (online-softmax)
    accumulation; position masking uses the *logical* block id."""
    b = pl.program_id(0)
    slot = pl.program_id(1)

    @pl.when(slot == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    kb = idx_ref[b, slot]                 # logical block id
    is_valid = val_ref[b, slot]

    q = q_ref[...].astype(jnp.float32)                # [G, d]
    k = k_ref[...].astype(jnp.float32)                # [bk, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale                                      # [G, bk]

    g = q.shape[0]
    kpos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (g, block_k), 1
    )
    mask = jnp.logical_and(is_valid > 0, kpos < cl_ref[b])
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
    static_argnames=("key_block", "scale", "interpret"),
)
def paged_decode_gather_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_indices: jax.Array,
    block_valid: jax.Array,
    block_table: jax.Array,
    cache_length: jax.Array,
    *,
    key_block: int = 64,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Two-level survivor-table decode attention over a page pool.

    Args:
      q: ``[bh, G, d]`` folded query rows.
      k_pages, v_pages: ``[n_pages, bk, d]`` page-major pools (KV-head
        axis folded into the page axis by the caller).
      block_indices / block_valid: int32 ``[bh, budget]`` — *logical*
        survivor block ids + validity bits.
      block_table: int32 ``[bh, mb]`` logical block → physical page
        (head-offset). Composed with ``block_indices`` inside the
        BlockSpec index maps.
      cache_length: int32 ``[bh]`` live logical lengths.
    """
    bh, g, d = q.shape
    bk = key_block
    budget = block_indices.shape[-1]
    sm_scale = scale if scale is not None else 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _paged_gather_kernel,
        sm_scale=sm_scale, block_k=bk, budget=budget,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bh, budget),
        in_specs=[
            pl.BlockSpec(
                (None, g, d), lambda b, j, idx, val, bt, cl: (b, 0, 0)
            ),
            pl.BlockSpec(
                (None, bk, d),
                lambda b, j, idx, val, bt, cl: (bt[b, idx[b, j]], 0, 0),
            ),
            pl.BlockSpec(
                (None, bk, d),
                lambda b, j, idx, val, bt, cl: (bt[b, idx[b, j]], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, g, d), lambda b, j, idx, val, bt, cl: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, g, d), v_pages.dtype),
        interpret=interpret,
        name="mpmrf_decode_gather",
    )(
        block_indices.astype(jnp.int32),
        block_valid.astype(jnp.int32),
        block_table.astype(jnp.int32),
        cache_length.astype(jnp.int32),
        q, k_pages, v_pages,
    )
