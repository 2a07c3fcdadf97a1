"""EnergonAttention — the paper's technique as one composable module.

This is the single entry point the model zoo calls. It dispatches between:

* ``dense``        — vanilla attention (the unpruned baseline the paper
                     compares against, and the path used by archs where
                     MP-MRF is configured off).
* ``mpmrf_row``    — paper-faithful Alg. 2: per-row multi-round filtering
                     + masked high-precision sparse attention.
* ``mpmrf_block``  — TPU-adapted block-granular MP-MRF with a static
                     block budget; real FLOP/byte savings under XLA.
* ``pallas``       — the fused Pallas TPU kernels (filter + block-sparse
                     flash attention). Falls back to interpret mode on CPU.

All variants share a (batch, heads, seq, head_dim) calling convention;
GQA head-group mapping happens in ``repro.models.attention``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import filtering as flt
from repro.core import sparse_attention as spa

# Measured crossover of the resident filter cache (BENCH_decode.json,
# CPU host): below ~1k cache rows the plane maintenance + state traffic
# costs more than the re-quantize it saves (traffic ratio 1.01 at
# max_len 512, < 1 from 1024 up). Contexts shorter than this default
# run with fresh quantization unless the config pins the gate open.
FILTER_CACHE_AUTO_MIN_LEN = 1024


@dataclasses.dataclass(frozen=True)
class EnergonConfig:
    """Attention implementation selector + MP-MRF parameters."""

    impl: str = "mpmrf_block"  # dense | mpmrf_row | mpmrf_block | pallas
    round_bits: Tuple[int, ...] = (2, 4)
    alphas: Tuple[float, ...] = (0.0, 0.0)
    query_block: int = 128
    key_block: int = 128
    # Target pruning ratio ρ ⇒ block budget B = ceil(n_kb / ρ). The paper's
    # "adjustable pruning ratio" (§III-B(3)) expressed statically. ρ ≤ 1
    # means "keep everything": all MP-MRF paths become exactly dense.
    pruning_ratio: float = 4.0
    # Key-block width for the block-granular *decode* path (the l=1
    # serve case pools the padded KV cache into blocks this wide; the
    # prefill/train blocks above are MXU-sized, decode blocks trade a
    # little selection sharpness for gather granularity). 0 disables the
    # block decode path (row-granular filtering over the full cache).
    decode_key_block: int = 64
    # Carry persistent int16 K codes + per-key-block scales in the
    # decode cache (written once at prefill scatter / decode append) so
    # every decode step's MP-MRF filter reads resident integer planes
    # instead of re-quantizing the whole padded cache (§IV-B premise:
    # filtering must stay cheap relative to attention).
    filter_cache: bool = True
    # Context-length crossover gate for the resident filter cache:
    # caches shorter than this never allocate (or maintain) the
    # quantized planes and fall back to fresh per-block quantization —
    # at short context the plane upkeep costs more HBM traffic than the
    # re-quantize it avoids. ``None`` → the auto-measured default
    # (``FILTER_CACHE_AUTO_MIN_LEN``); ``0`` → always engage.
    filter_cache_min_len: Optional[int] = None
    keep_first: bool = True
    keep_diagonal: bool = True
    reuse_partial: bool = True
    # Layers below this index run dense (the paper does not prune the
    # first two blocks, §III-A).
    min_prune_layer: int = 2
    # Switch to scan-over-query-blocks paths when n_q·n_k exceeds this
    # (the [n_q, n_k] score tensor would be ≥64 MB/head at f32).
    chunk_threshold: int = 2048 * 2048

    @property
    def uses_decode_block(self) -> bool:
        """True when the block-granular decode path can engage at all."""
        return self.impl in ("mpmrf_block", "pallas") and \
            self.decode_key_block > 0

    @property
    def uses_filter_cache(self) -> bool:
        """True when decode caches should carry quantized filter planes."""
        return self.filter_cache and self.uses_decode_block

    def filter_cache_engages(self, max_len: int) -> bool:
        """Crossover gate: do resident planes pay off at ``max_len``?

        Cache initializers consult this with their (rounded) context
        capacity — below the threshold the planes are simply never
        allocated, so every consumer (decode filter, fused kernels,
        prefill selection) falls back to fresh quantization without a
        second dispatch-level switch. Selection is bit-identical either
        way: fresh quantization at the same per-block granularity obeys
        the same invariant the resident planes are maintained under.
        """
        if not self.uses_filter_cache:
            return False
        threshold = self.filter_cache_min_len
        if threshold is None:
            threshold = FILTER_CACHE_AUTO_MIN_LEN
        return max_len >= threshold

    def mpmrf(self, granularity: str, n_kb: Optional[int] = None) -> flt.MPMRFConfig:
        budget = None
        if granularity == "block" and n_kb is not None:
            budget = max(1, math.ceil(n_kb / self.pruning_ratio))
        return flt.MPMRFConfig(
            round_bits=self.round_bits,
            alphas=self.alphas,
            granularity=granularity,
            query_block=self.query_block,
            key_block=self.key_block,
            block_budget=budget,
            keep_first=self.keep_first,
            keep_diagonal=self.keep_diagonal,
            reuse_partial=self.reuse_partial,
            keep_all=self.pruning_ratio <= 1.0,
        )


def energon_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: EnergonConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    layer_index: int = 10**9,
    q_offset: int = 0,
    q_positions: Optional[jax.Array] = None,
    kv_length: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    filter_cache: Optional[Dict[str, jax.Array]] = None,
    telemetry: bool = False,
):
    """Multi-head attention with Energon dynamic sparse attention.

    Args:
      q: ``[B, H, n_q, d]`` queries; k/v: ``[B, H, n_k, d]``.
      cfg: Energon configuration.
      causal: apply causal masking (decoder LMs).
      window: optional sliding-window size (local attention layers).
      layer_index: current layer; layers < cfg.min_prune_layer run dense.
      q_offset: absolute position of query row 0 (decode/chunked prefill).
      q_positions: optional int32 ``[B, n_q]`` absolute position per query
        row — the chunked-prefill case where positions are per-slot and
        not necessarily contiguous (folded GQA rows, padding sentinels
        ≥ n_k that attend everything and are ignored by the caller).
        Overrides ``q_offset`` for masking.
      kv_length: optional ``[B]`` true cache lengths for padded caches.
      scale: score scale; default 1/√d.
      filter_cache: optional resident quantized filter planes of ``k``
        (DESIGN.md §3 layout: ``{"codes": int16 [..., n_k, d], "scale":
        f32 [..., n_k // cfg.decode_key_block]}``). When present, the
        block-granular selection paths read them instead of
        re-quantizing ``k`` — and the ``pallas`` chunked-prefill path
        (``q_positions`` set) engages the fused prefill kernels, which
        derive both rounds' bit planes from the resident codes
        in-register and stream only survivor K/V blocks.
      telemetry: also return int32 ``[B, 4]`` selection stats
        (selected / live / pinned / filled candidate-block counts, see
        :func:`repro.core.filtering.selection_stats`). Only the
        block-granular budget selections measure anything; dense, row,
        chunked and pure-kernel paths report zeros.

    Returns:
      ``[B, H, n_q, d]`` attention output (dtype of v); with
      ``telemetry``, ``(out, stats)``.
    """
    n_q, n_k = q.shape[-2], k.shape[-2]

    def ret(out, stats=None):
        if not telemetry:
            return out
        return out, (stats if stats is not None else _zero_stats(q.shape[0]))

    impl = cfg.impl
    if layer_index < cfg.min_prune_layer and impl != "dense":
        impl = "dense"
    # Block paths need block-divisible sequences; short sequences and
    # ragged decode steps fall back to row granularity automatically.
    if impl in ("mpmrf_block", "pallas"):
        if (n_q % cfg.query_block) or (n_k % cfg.key_block):
            impl = "mpmrf_row"
        elif n_k // cfg.key_block <= 1:
            impl = "mpmrf_row"

    # Fused Pallas prefill: resident planes + per-row positions. This
    # short-circuits *before* the [n_q, n_k] mask/score materialization
    # (causality, sentinels and pooling all happen on-chip per tile),
    # which is also why the chunk_threshold guard below does not apply
    # to it.
    if (
        impl == "pallas" and q_positions is not None and causal
        and _fused_prefill_engaged(
            cfg, filter_cache is not None, window, kv_length, n_k
        )
    ):
        from repro.kernels import ops as kops

        n_kb = n_k // cfg.key_block
        return kops.fused_prefill_attention(
            q, k, v,
            filter_cache["codes"], filter_cache["scale"],
            q_positions,
            round_bits=cfg.round_bits,
            alphas=cfg.alphas,
            query_block=cfg.query_block,
            key_block=cfg.key_block,
            filter_block=cfg.decode_key_block,
            block_budget=max(1, math.ceil(n_kb / cfg.pruning_ratio)),
            keep_all=cfg.pruning_ratio <= 1.0,
            keep_first=cfg.keep_first,
            keep_diagonal=cfg.keep_diagonal,
            diag_blocks=_prefill_diag_blocks(
                q_positions, cfg.query_block, cfg.key_block, n_k
            ),
            scale=scale,
            telemetry=telemetry,
        )

    # Above this size, materialized [n_q, n_k] scores/masks do not fit
    # HBM: switch to the scan-over-query-blocks (flash-style) paths.
    # The q_positions (serve-prefill) form has no chunked variant, so
    # enforce the guard instead of silently materializing past it.
    if q_positions is not None and n_q * n_k > cfg.chunk_threshold:
        raise ValueError(
            f"q_positions attention materializes [{n_q}, {n_k}] masks "
            f"past chunk_threshold={cfg.chunk_threshold}; lower the "
            "prefill chunk (or raise chunk_threshold)"
        )
    chunked = n_q * n_k > cfg.chunk_threshold

    if chunked:
        from repro.core import chunked_attention as chk

        if impl in ("mpmrf_block", "pallas"):
            # pallas impl lowers through the chunked XLA pipeline on the
            # dry-run/prefill path (kernels are serving/TPU-runtime).
            # Telemetry reports zeros here: the chunked scan discards
            # its per-chunk selections, and serving never takes this
            # path (engine chunks stay under chunk_threshold).
            return ret(chk.energon_block_attention_chunked(
                q, k, v,
                round_bits=cfg.round_bits,
                alphas=cfg.alphas,
                pruning_ratio=cfg.pruning_ratio,
                query_block=cfg.query_block,
                key_block=cfg.key_block,
                causal=causal, window=window, q_offset=q_offset,
                kv_length=kv_length,
                keep_first=cfg.keep_first,
                keep_diagonal=cfg.keep_diagonal,
                scale=scale,
            ))
        # dense / row fall back to chunked dense (row-granular MP-MRF at
        # this size would materialize token-level masks).
        return ret(chk.dense_attention_chunked(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_length=kv_length, scale=scale,
        ))

    valid = None
    if q_positions is not None:
        qpos = q_positions[:, None, :, None]        # [B, 1, n_q, 1]
        kpos = jnp.arange(n_k)[None, None, None, :]
        if causal:
            valid = kpos <= qpos
        if window is not None:
            w_ok = jnp.where(window > 0, kpos > qpos - window, True)
            valid = w_ok if valid is None else jnp.logical_and(valid, w_ok)
        # padding sentinel rows (qpos >= n_k) are wholly invalid: their
        # garbage scores must never leak into the pooled block-selection
        # planes the real rows of a ragged chunk share.
        not_sentinel = qpos < n_k
        valid = not_sentinel if valid is None else jnp.logical_and(
            valid, not_sentinel
        )
    elif window is not None:
        valid = flt.sliding_window_valid_mask(n_q, n_k, window, q_offset)
    elif causal:
        valid = flt.causal_valid_mask(n_q, n_k, q_offset)
    if valid is not None:
        valid = jnp.broadcast_to(valid, q.shape[:-2] + (n_q, n_k))
    if kv_length is not None:
        in_range = jnp.arange(n_k)[None, :] < kv_length[:, None]
        in_range = in_range[:, None, None, :]  # [B,1,1,n_k]
        valid = in_range if valid is None else jnp.logical_and(valid, in_range)
        valid = jnp.broadcast_to(valid, q.shape[:-2] + (n_q, n_k))

    # keep_diagonal target per query block: at absolute positions the
    # local block is position//key_block, not the offset-0 default.
    diag_blocks = None
    if q_positions is not None and impl in ("mpmrf_block", "pallas"):
        diag_blocks = _prefill_diag_blocks(
            q_positions, cfg.query_block, cfg.key_block, n_k
        )

    # Resident planes (when the caller carries them) replace the fresh
    # per-head quantization in *every* block-granular selection — the
    # XLA paths must consume the same operands the fused kernels read,
    # or "fused on" and "fused off" would select from differently
    # quantized scores and the bit-exactness contract would break.
    k_quant = None
    if (
        filter_cache is not None
        and impl in ("mpmrf_block", "pallas")
        and cfg.decode_key_block > 0
        and n_k % cfg.decode_key_block == 0
    ):
        from repro.core import quantization as qlib

        k_quant = qlib.blockwise_quantized_view(
            filter_cache["codes"], filter_cache["scale"],
            cfg.decode_key_block,
        )

    if impl == "dense":
        return ret(spa.dense_attention(q, k, v, valid, scale))

    if impl == "mpmrf_row":
        res = flt.mpmrf_row_select(q, k, cfg.mpmrf("row"), valid)
        return ret(spa.masked_sparse_attention(q, k, v, res.keep_mask, scale))

    if impl == "mpmrf_block":
        n_kb = n_k // cfg.key_block
        res = flt.mpmrf_block_select(
            q, k, cfg.mpmrf("block", n_kb), valid, diag_blocks=diag_blocks,
            k_quant=k_quant, with_stats=telemetry,
        )
        out = spa.block_gather_attention(
            q, k, v, res.block_indices, valid,
            cfg.query_block, cfg.key_block, scale,
            block_valid=res.block_valid,
        )
        return ret(out, flt.selection_stats(res) if telemetry else None)

    if impl == "pallas":
        # Imported lazily: pallas lowering only exists for the TPU target;
        # tests exercise it via interpret mode. Window / padded-cache /
        # per-row-position masks (when the fused prefill kernel did not
        # engage above) are not in the kernel contract — fall back to
        # XLA block.
        if window is not None or kv_length is not None or q_positions is not None:
            n_kb = n_k // cfg.key_block
            res = flt.mpmrf_block_select(
                q, k, cfg.mpmrf("block", n_kb), valid,
                diag_blocks=diag_blocks, k_quant=k_quant,
                with_stats=telemetry,
            )
            out = spa.block_gather_attention(
                q, k, v, res.block_indices, valid,
                cfg.query_block, cfg.key_block, scale,
                block_valid=res.block_valid,
            )
            return ret(out, flt.selection_stats(res) if telemetry else None)
        from repro.kernels import ops as kops

        batch, heads, _, d = q.shape
        n_kb = n_k // cfg.key_block
        budget = max(1, math.ceil(n_kb / cfg.pruning_ratio))
        qf = q.reshape(batch * heads, n_q, d)
        kf = k.reshape(batch * heads, n_k, d)
        vf = v.reshape(batch * heads, n_k, d)
        idx, val = kops.mpmrf_select_blocks(
            qf, kf,
            round_bits=cfg.round_bits,
            alphas=cfg.alphas,
            block_budget=budget,
            query_block=cfg.query_block,
            key_block=cfg.key_block,
            causal=causal,
            q_offset=q_offset,
            keep_first=cfg.keep_first,
            keep_diagonal=cfg.keep_diagonal,
        )
        out = kops.block_sparse_attention(
            qf, kf, vf, idx, val,
            query_block=cfg.query_block,
            key_block=cfg.key_block,
            causal=causal,
            q_offset=q_offset,
            scale=scale,
        )
        # Telemetry reports zeros here: the pure-kernel path is the
        # offline/training route — serving telemetry flows through the
        # decode/paged/fused-prefill entry points, which carry tiers.
        return ret(out.reshape(q.shape))

    raise ValueError(f"unknown Energon impl: {cfg.impl}")


def _zero_stats(batch: int) -> jax.Array:
    """All-zero selection stats for paths with no block selection."""
    return jnp.zeros((batch, 4), jnp.int32)


def decode_live_budget(
    cache_length: jax.Array, key_block: int, pruning_ratio: float
) -> jax.Array:
    """Per-slot effective block budget ``ceil(ceil(len/bk) / ρ)``.

    The static gather width must come from the *padded* cache (shapes),
    but the number of blocks a slot actually keeps must come from its
    *live* length — otherwise a long max_len silently drives the
    effective pruning ratio toward 1 (budget ≥ live blocks ⇒ dense).
    """
    live_blocks = (cache_length + key_block - 1) // key_block
    lb = jnp.ceil(
        live_blocks.astype(jnp.float32) / max(pruning_ratio, 1e-6)
    ).astype(jnp.int32)
    return jnp.maximum(lb, 1)


def _decode_valid_mask(
    q: jax.Array, n_k: int, cache_length: jax.Array, window: Optional[int]
) -> jax.Array:
    """Cache-length (+ optional sliding-window) validity for one-token
    decode, broadcast to ``[..., n_q, n_k]``. Shared by the unpaged and
    paged entry points so their masking can never drift apart."""
    in_range = jnp.arange(n_k)[None, :] < cache_length[:, None]
    valid = in_range[:, None, None, :]
    if window is not None:
        w_lo = cache_length[:, None] - window
        w_valid = jnp.where(
            window > 0, jnp.arange(n_k)[None, :] >= w_lo, True
        )
        valid = jnp.logical_and(valid, w_valid[:, None, None, :])
    return jnp.broadcast_to(valid, q.shape[:-2] + (q.shape[-2], n_k))


def _decode_block_plan(cfg: EnergonConfig, n_k: int, cache_length: jax.Array):
    """Budget/keep_all/live-budget/filter-config for the block decode
    paths — one derivation for unpaged and paged (the paged≡unpaged
    contract depends on these staying in lockstep)."""
    bk = cfg.decode_key_block
    n_kb = n_k // bk
    budget = max(1, math.ceil(n_kb / cfg.pruning_ratio))
    keep_all = cfg.pruning_ratio <= 1.0
    live_budget = None
    if not keep_all:
        live_budget = decode_live_budget(cache_length, bk, cfg.pruning_ratio)
    mcfg = flt.MPMRFConfig(
        round_bits=cfg.round_bits,
        alphas=cfg.alphas,
        granularity="block",
        query_block=1,
        key_block=bk,
        block_budget=budget,
        keep_first=cfg.keep_first,
        keep_diagonal=cfg.keep_diagonal,
        reuse_partial=cfg.reuse_partial,
        keep_all=keep_all,
    )
    return budget, keep_all, live_budget, mcfg


def _fused_decode_engaged(
    cfg: EnergonConfig, filter_planes_resident: bool, window: Optional[int]
) -> bool:
    """Engagement predicate of the fused Pallas decode kernels, shared
    by the unpaged and paged dispatchers: resident filter planes, no
    window, the default 2-round config, and Fig. 7 result reuse (the
    kernel hard-codes it; independent-rescore takes the XLA path)."""
    return (
        cfg.impl == "pallas"
        and filter_planes_resident
        and window is None
        and len(cfg.round_bits) == 2
        and cfg.reuse_partial
    )


def _prefill_diag_blocks(
    q_positions: jax.Array, query_block: int, key_block: int, n_k: int
) -> jax.Array:
    """keep_diagonal target per query block at absolute positions.

    ``[B, n_q]`` per-row positions → ``[B, n_qb]`` local key-block index
    (position // key_block of the block's highest real row). Sentinel
    rows (≥ n_k) are dropped from the max so a ragged tail block aims
    at its last *real* row's diagonal. One derivation shared by the XLA
    selection, the fused prefill dispatch and the paged prefill entry —
    the bit-exactness contract needs them in lockstep.
    """
    n_q = q_positions.shape[-1]
    eff = jnp.where(q_positions < n_k, q_positions, -1)  # drop sentinels
    qb_pos = jnp.max(
        eff.reshape(eff.shape[0], n_q // query_block, query_block),
        axis=-1,
    )
    return jnp.clip(qb_pos, 0, n_k - 1) // key_block


def _fused_prefill_engaged(
    cfg: EnergonConfig,
    filter_planes_resident: bool,
    window: Optional[int],
    kv_length: Optional[jax.Array],
    n_k: int,
) -> bool:
    """Engagement predicate of the fused Pallas prefill kernels.

    Mirrors :func:`_fused_decode_engaged` (resident planes, no window,
    2 rounds, Fig. 7 reuse) plus the prefill-only constraints: no
    padded-cache ``kv_length`` masking (the kernel masks by per-row
    positions alone), a cache length divisible into the resident plane
    blocks the codes are scaled by, and key tiles made of whole plane
    blocks (the kernels stream one scale block — one page — per step).
    Callers additionally require ``q_positions`` + ``causal`` — the
    kernel's on-chip mask is exactly ``key_pos ≤ query_pos < n_k``.
    """
    return (
        cfg.impl == "pallas"
        and filter_planes_resident
        and window is None
        and kv_length is None
        and len(cfg.round_bits) == 2
        and cfg.reuse_partial
        and cfg.decode_key_block > 0
        and n_k % cfg.decode_key_block == 0
        and cfg.key_block % cfg.decode_key_block == 0
    )


def energon_paged_prefill_attention(
    q: jax.Array,
    cache: Dict[str, jax.Array],
    block_table: jax.Array,
    q_positions: jax.Array,
    cfg: EnergonConfig,
    *,
    layer_index: int = 10**9,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    telemetry: bool = False,
):
    """Chunked-prefill attention straight against the page pool.

    The paged counterpart of the ``q_positions`` form of
    :func:`energon_attention`. When the fused prefill kernels engage
    (resident planes, key tiles of whole pages, block-divisible shapes),
    the chunk attends the pool *in place*: the filter kernel scores the
    per-page codes through the block table and the gather kernel's
    BlockSpec index maps compose survivor table ∘ block table, so
    unselected and unmapped pages never leave HBM. Otherwise the
    per-slot logical K/V views are materialized transiently (zeroed
    past each slot's written extent, exactly as before) and fed to
    ``energon_attention`` — with the *gathered* resident planes as its
    filter operands, so fused and fallback selection stay bit-identical
    on the same pool contents.

    Args:
      q: ``[B, KV, n_q, d]`` folded GQA query rows.
      cache: one layer's pool slice (``k``/``v`` ``[KV, pool_rows, d]``
        + ``k_codes``/``k_scale`` when the filter cache is resident).
      block_table: int32 ``[B, max_blocks]``.
      q_positions: int32 ``[B, n_q]`` absolute logical positions
        (sentinels ≥ logical rows are inert).
    """
    from repro.runtime import paged_cache as pgc

    ps = cfg.decode_key_block
    if ps <= 0:
        raise ValueError("paged prefill needs decode_key_block > 0")
    mb = block_table.shape[-1]
    n_k = mb * ps
    n_q = q.shape[-2]

    fused = (
        layer_index >= cfg.min_prune_layer
        and _fused_prefill_engaged(cfg, "k_codes" in cache, window,
                                   None, n_k)
        and n_k % cfg.key_block == 0
        and n_q % cfg.query_block == 0
        and n_k // cfg.key_block > 1
    )
    if fused:
        from repro.kernels import ops as kops

        n_kb = n_k // cfg.key_block
        return kops.fused_paged_prefill_attention(
            q, cache["k"], cache["v"],
            cache["k_codes"], cache["k_scale"],
            block_table, q_positions,
            round_bits=cfg.round_bits,
            alphas=cfg.alphas,
            query_block=cfg.query_block,
            key_block=cfg.key_block,
            block_budget=max(1, math.ceil(n_kb / cfg.pruning_ratio)),
            keep_all=cfg.pruning_ratio <= 1.0,
            keep_first=cfg.keep_first,
            keep_diagonal=cfg.keep_diagonal,
            diag_blocks=_prefill_diag_blocks(
                q_positions, cfg.query_block, cfg.key_block, n_k
            ),
            scale=scale,
            telemetry=telemetry,
        )

    k_log = pgc.gather_logical_rows(cache["k"], block_table, ps)
    v_log = pgc.gather_logical_rows(cache["v"], block_table, ps)
    # Zero the view past each slot's written extent: unmapped logical
    # blocks alias page 0 (another occupant's rows), and the per-head
    # absmax of row/block selection would otherwise quantize against
    # them. The unpaged cache holds zeros there — zeroing makes the
    # views (and hence prefill logits) bit-identical. Positions are
    # contiguous per slot (sentinels ≥ logical rows), so max+1 bounds
    # every row written so far.
    extent = jnp.max(
        jnp.where(q_positions < n_k, q_positions + 1, 0), axis=1
    )                                        # [B]
    row_ok = (
        jnp.arange(n_k)[None, :] < extent[:, None]
    )[:, None, :, None]
    k_log = k_log * row_ok
    v_log = v_log * row_ok
    filter_cache = None
    if "k_codes" in cache:
        # The gathered planes are the pool planes verbatim (the gather
        # is exact), so fallback selection reads the same codes/scales
        # the fused kernels stream through the block table. They are
        # deliberately *not* zeroed past the extent: the fused kernel
        # reads raw pages too, and blocks past the extent are wholly
        # masked before pooling either way.
        filter_cache = {
            "codes": pgc.gather_logical_rows(
                cache["k_codes"], block_table, ps
            ),
            "scale": pgc.gather_logical_scales(
                cache["k_scale"], block_table
            ),
        }
    return energon_attention(
        q, k_log, v_log, cfg,
        causal=True, window=window, layer_index=layer_index,
        q_positions=q_positions, scale=scale, filter_cache=filter_cache,
        telemetry=telemetry,
    )


def energon_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_length: jax.Array,
    cfg: EnergonConfig,
    *,
    layer_index: int = 10**9,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    filter_cache: Optional[Dict[str, jax.Array]] = None,
    telemetry: bool = False,
):
    """One-token decode attention over a (padded) KV cache.

    This is the paper's GPT-2 generation case (§IV-D, l = 1): MP-MRF
    filters the whole cache with low-bit mat-vecs, then exact attention
    touches only survivors. q: ``[B, H, n_q, d]`` (n_q > 1 ⇒ folded GQA
    group rows, all at the same position); caches ``[B, H, n, d]``;
    cache_length: ``[B]`` int32 — number of valid cache entries.

    ``filter_cache`` (optional) carries the persistent quantized filter
    operands maintained by the serving cache (DESIGN.md §3):
    ``{"codes": int16 [B, H, n, d], "scale": f32 [B, H, n // bk]}``.
    When present, the MP-MRF rounds read these resident planes instead
    of re-quantizing ``k_cache`` — the per-step filter drops from an
    O(max_len·d) quantize + rescale chain to integer mat-vecs on data
    already in cache layout. The invariant (block == fresh per-block
    quantization) makes cached and fresh selection bit-identical.

    Three sparse paths (DESIGN.md §3):

    * **pallas** (``impl`` pallas, no window, filter cache resident):
      fused decode kernel — two-round shift-and-add scoring straight
      off the cached planes and block-gather flash attention behind a
      scalar-prefetch survivor table, so unselected K/V blocks never
      leave HBM. Interpret mode is the CPU fallback.
    * **block** (``impl`` mpmrf_block/pallas, cache divisible by
      ``cfg.decode_key_block``): pool the cache into key blocks, select
      top-B via MP-MRF, and *gather* only the survivors — FLOPs/bytes
      shrink with the pruning ratio.
    * **row** (fallback): paper-faithful token mask over the full padded
      cache (exact selection, but no skipped bytes under XLA).
    """
    n_k = k_cache.shape[-2]
    valid = _decode_valid_mask(q, n_k, cache_length, window)

    def ret(out, stats=None):
        if not telemetry:
            return out
        return out, (stats if stats is not None else _zero_stats(q.shape[0]))

    if layer_index < cfg.min_prune_layer or cfg.impl == "dense":
        return ret(spa.dense_attention(q, k_cache, v_cache, valid, scale))

    bk = cfg.decode_key_block
    use_block = (
        cfg.impl in ("mpmrf_block", "pallas")
        and bk > 0 and n_k % bk == 0 and n_k // bk > 1
    )
    if use_block:
        budget, keep_all, live_budget, mcfg = _decode_block_plan(
            cfg, n_k, cache_length
        )

        if _fused_decode_engaged(cfg, filter_cache is not None, window):
            from repro.kernels import ops as kops

            return kops.fused_decode_attention(
                q, k_cache, v_cache,
                filter_cache["codes"], filter_cache["scale"],
                cache_length,
                round_bits=cfg.round_bits,
                alphas=cfg.alphas,
                key_block=bk,
                block_budget=budget,
                keep_all=keep_all,
                keep_first=cfg.keep_first,
                keep_diagonal=cfg.keep_diagonal,
                live_budget=live_budget,
                scale=scale,
                telemetry=telemetry,
            )

        k_quant = None
        if filter_cache is not None:
            from repro.core import quantization as qlib

            k_quant = qlib.blockwise_quantized_view(
                filter_cache["codes"], filter_cache["scale"], bk
            )
        res = flt.mpmrf_decode_block_select(
            q, k_cache, mcfg, valid, cache_length,
            k_quant=k_quant, live_budget=live_budget,
            with_stats=telemetry,
        )
        out = spa.decode_block_gather_attention(
            q, k_cache, v_cache, res.block_indices, res.block_valid,
            cache_length, bk, window=window, scale=scale,
        )
        return ret(out, flt.selection_stats(res) if telemetry else None)

    if cfg.pruning_ratio <= 1.0:
        # ρ ≤ 1 ⇒ nothing to prune: skip the filter mat-vec entirely.
        return ret(spa.dense_attention(q, k_cache, v_cache, valid, scale))
    res = flt.mpmrf_row_select(q, k_cache, cfg.mpmrf("row"), valid)
    return ret(spa.decode_sparse_attention(
        q, k_cache, v_cache, res.keep_mask, scale
    ))


def energon_paged_decode_attention(
    q: jax.Array,
    cache: Dict[str, jax.Array],
    block_table: jax.Array,
    cache_length: jax.Array,
    cfg: EnergonConfig,
    *,
    layer_index: int = 10**9,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    telemetry: bool = False,
):
    """One-token decode attention over a shared page pool.

    The paged counterpart of :func:`energon_decode_attention`: cache
    state is a page pool (``repro.runtime.paged_cache`` layout — K/V
    rows plus the resident filter operands per physical page) and each
    slot addresses it through a block table. The contract is
    **bit-identical outputs** to the unpaged path on the equivalent
    logical contents, for every decode path:

    * **pallas** — the paged fused kernels
      (:func:`repro.kernels.ops.fused_paged_decode_attention`) compose
      the survivor table with the block table inside the BlockSpec
      index maps, so unselected *and unmapped* pages never leave HBM.
    * **block** — :func:`repro.core.filtering.mpmrf_paged_block_select`
      scores the resident per-page planes through the block table, then
      only the surviving physical pages are gathered.
    * **row / dense** (fallbacks: prefix layers, ρ≤1, row impls) — the
      per-slot logical view is materialized transiently and fed to the
      unpaged implementations; persistent state stays pool-sized.

    Args:
      q: ``[B, KV, n_q, d]`` folded GQA query rows.
      cache: the layer's pool slice: ``k``/``v`` ``[KV, pool_rows, d]``
        (+ ``k_codes``/``k_scale`` when the filter cache is resident).
      block_table: int32 ``[B, max_blocks]``.
      cache_length: int32 ``[B]`` live logical lengths.
    """
    from repro.runtime import paged_cache as pgc

    bk = cfg.decode_key_block
    if bk <= 0:
        raise ValueError("paged decode needs decode_key_block > 0")
    mb = block_table.shape[-1]
    n_k = mb * bk
    valid = _decode_valid_mask(q, n_k, cache_length, window)

    def logical(name):
        return pgc.gather_logical_rows(cache[name], block_table, bk)

    def ret(out, stats=None):
        if not telemetry:
            return out
        return out, (stats if stats is not None else _zero_stats(q.shape[0]))

    if layer_index < cfg.min_prune_layer or cfg.impl == "dense":
        return ret(
            spa.dense_attention(q, logical("k"), logical("v"), valid, scale)
        )

    use_block = cfg.impl in ("mpmrf_block", "pallas") and n_k // bk > 1
    if use_block:
        budget, keep_all, live_budget, mcfg = _decode_block_plan(
            cfg, n_k, cache_length
        )

        if _fused_decode_engaged(cfg, "k_codes" in cache, window):
            from repro.kernels import ops as kops

            return kops.fused_paged_decode_attention(
                q, cache["k"], cache["v"],
                cache["k_codes"], cache["k_scale"],
                block_table, cache_length,
                round_bits=cfg.round_bits,
                alphas=cfg.alphas,
                key_block=bk,
                block_budget=budget,
                keep_all=keep_all,
                keep_first=cfg.keep_first,
                keep_diagonal=cfg.keep_diagonal,
                live_budget=live_budget,
                scale=scale,
                telemetry=telemetry,
            )

        res = flt.mpmrf_paged_block_select(
            q, cache, block_table, mcfg, valid, cache_length,
            live_budget=live_budget, with_stats=telemetry,
        )
        out = spa.paged_decode_block_gather_attention(
            q, cache["k"], cache["v"], res.block_indices, res.block_valid,
            block_table, cache_length, bk, window=window, scale=scale,
        )
        return ret(out, flt.selection_stats(res) if telemetry else None)

    if cfg.pruning_ratio <= 1.0:
        return ret(
            spa.dense_attention(q, logical("k"), logical("v"), valid, scale)
        )
    # Row-granular selection quantizes K with a per-head absmax over the
    # *whole* row axis; unmapped logical blocks alias page 0 (another
    # occupant's rows), which would inflate the absmax and shift the
    # selection. The unpaged cache holds zeros past cache_length — zero
    # the gathered view the same way so the quantization (and therefore
    # the selection) stays bit-identical.
    row_ok = (
        jnp.arange(n_k)[None, :] < cache_length[:, None]
    )[:, None, :, None]
    k_log = logical("k") * row_ok
    res = flt.mpmrf_row_select(q, k_log, cfg.mpmrf("row"), valid)
    return ret(spa.decode_sparse_attention(
        q, k_log, logical("v"), res.keep_mask, scale
    ))
