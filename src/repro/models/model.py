"""LMModel — one config-driven entry point for every assigned architecture.

Families:
  dense / moe / vlm / audio — decoder transformer stack (scan over layers)
  ssm                       — xLSTM: groups of (m × mLSTM + s × sLSTM)
  hybrid                    — zamba2: Mamba2 backbone + shared attention

API (all pure functions of a param pytree):
  init(rng)                           → params
  apply(params, batch)                → (logits, aux_loss)
  loss(params, batch)                 → (loss, metrics)
  init_cache(batch, max_len)          → decode cache pytree
  decode_step(params, cache, inputs, cache_index) → (logits, cache)

Batch convention: token families use ``{"inputs": [B,n] int32,
"targets": [B,n] int32}``; vlm/audio use ``{"embeddings": [B,n,d_model],
"targets": [B,n]}`` (the modality frontend is a stub per the task spec —
see `repro.models.multimodal`).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import transformer as tfm


class LMModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.family not in ("dense", "moe", "ssm", "hybrid", "vlm", "audio"):
            raise ValueError(f"unknown family {cfg.family}")

    # ------------------------------------------------------------------
    # shared bits
    # ------------------------------------------------------------------

    @property
    def _dtype(self):
        return jnp.dtype(self.cfg.dtype)

    def _moe_cfg(self) -> Optional[moe_lib.MoEConfig]:
        if self.cfg.family != "moe":
            return None
        return moe_lib.MoEConfig(
            num_experts=self.cfg.num_experts,
            experts_per_token=self.cfg.experts_per_token,
            d_model=self.cfg.d_model,
            d_ff=self.cfg.d_ff,
            activation=self.cfg.activation,
            capacity_factor=self.cfg.capacity_factor,
            quantized_weight_gather=self.cfg.moe_quantized_gather,
        )

    def layer_windows(self) -> Optional[jnp.ndarray]:
        """Per-layer sliding windows (0 ⇒ global). None ⇒ all global."""
        cfg = self.cfg
        if cfg.sliding_window <= 0 or cfg.global_every <= 0:
            return None
        ids = jnp.arange(cfg.num_layers)
        is_global = (ids + 1) % cfg.global_every == 0
        return jnp.where(is_global, 0, cfg.sliding_window).astype(jnp.int32)

    def _init_tfm_block(self, key):
        cfg = self.cfg
        return tfm.init_block(
            key,
            d_model=cfg.d_model,
            num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            d_ff=cfg.d_ff,
            activation=cfg.activation,
            norm=cfg.norm,
            use_qk_norm=cfg.use_qk_norm,
            moe_cfg=self._moe_cfg(),
            dtype=self._dtype,
        )

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        k_emb, k_blocks, k_head, k_extra = jax.random.split(rng, 4)
        params: Dict[str, Any] = {
            "embed": L.init_embedding(
                k_emb, cfg.vocab_size, cfg.d_model, self._dtype
            ),
            "final_norm": L.init_norm(cfg.norm, cfg.d_model, self._dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.init_lm_head(
                k_head, cfg.d_model, cfg.vocab_size, self._dtype
            )

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            params["blocks"] = tfm.init_stack(
                k_blocks, cfg.num_layers, self._init_tfm_block
            )
        elif cfg.family == "ssm":
            m_per, s_per = cfg.xlstm_group
            groups = cfg.num_layers // (m_per + s_per)
            k_m, k_s = jax.random.split(k_blocks)

            def init_group_m(key):
                keys = jax.random.split(key, m_per)
                return jax.vmap(
                    lambda kk: {
                        "norm": L.init_norm(cfg.norm, cfg.d_model, self._dtype),
                        "cell": ssm_lib.init_mlstm(
                            kk, cfg.d_model, cfg.num_heads, self._dtype
                        ),
                    }
                )(keys)

            params["mlstm"] = jax.vmap(init_group_m)(
                jax.random.split(k_m, groups)
            )
            params["slstm"] = jax.vmap(
                lambda kk: {
                    "norm": L.init_norm(cfg.norm, cfg.d_model, self._dtype),
                    "cell": ssm_lib.init_slstm(
                        kk, cfg.d_model, cfg.num_heads, self._dtype
                    ),
                }
            )(jax.random.split(k_s, groups))
        elif cfg.family == "hybrid":
            period = cfg.hybrid_attn_every
            groups = cfg.num_layers // period
            tail = cfg.num_layers - groups * period
            k_a, k_b, k_t, k_sh = jax.random.split(k_blocks, 4)

            def init_mamba(key):
                return {
                    "norm": L.init_norm(cfg.norm, cfg.d_model, self._dtype),
                    "cell": ssm_lib.init_mamba2(
                        key, cfg.d_model, cfg.ssm_state,
                        cfg.ssm_head_dim, dtype=self._dtype,
                    ),
                }

            def init_group_a(key):
                return jax.vmap(init_mamba)(jax.random.split(key, period - 1))

            params["mamba_pre"] = jax.vmap(init_group_a)(
                jax.random.split(k_a, groups)
            )
            params["mamba_post"] = jax.vmap(init_mamba)(
                jax.random.split(k_b, groups)
            )
            if tail:
                params["mamba_tail"] = jax.vmap(init_mamba)(
                    jax.random.split(k_t, tail)
                )
            params["shared"] = self._init_tfm_block(k_sh)
        return params

    # ------------------------------------------------------------------
    # forward (training / prefill)
    # ------------------------------------------------------------------

    def _embed_in(self, params, batch) -> jax.Array:
        if self.cfg.uses_embeddings_input and "embeddings" in batch:
            x = batch["embeddings"].astype(self._dtype)
        else:
            x = L.embed_tokens(params["embed"], batch["inputs"]).astype(
                self._dtype
            ) * (self.cfg.d_model ** 0.5)
        # table features are TP-sharded; bring activations back to
        # batch-DP layout before the stack.
        return shd.constrain(x, ("dp", None, None))

    def _logits_out(self, params, x) -> jax.Array:
        with jax.named_scope("head"):
            x = L.apply_norm(self.cfg.norm, params["final_norm"], x)
            if self.cfg.tie_embeddings:
                logits = L.tied_lm_logits(params["embed"], x)
            else:
                logits = L.lm_logits(params["lm_head"], x)
            return shd.constrain(logits, ("dp", None, "model"))

    def _tfm_block_fn(self):
        cfg = self.cfg
        has_windows = cfg.sliding_window > 0 and cfg.global_every > 0

        def block_fn(layer_params, x, window, layer_idx):
            return tfm.apply_block(
                layer_params, x, cfg.energon,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                rope_theta=cfg.rope_theta,
                use_qk_norm=cfg.use_qk_norm,
                activation=cfg.activation,
                norm=cfg.norm,
                window=window if has_windows else None,
                layer_index=layer_idx,
                moe_cfg=self._moe_cfg(),
            )

        return block_fn

    def apply(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = self._embed_in(params, batch)
        aux = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            x, aux = tfm.apply_stack(
                params["blocks"], x, self.layer_windows(),
                self._tfm_block_fn(), remat=cfg.remat,
                prefix_layers=cfg.energon.min_prune_layer,
            )
        elif cfg.family == "ssm":
            x = self._apply_xlstm(params, x)
        elif cfg.family == "hybrid":
            x = self._apply_hybrid(params, x)
        return self._logits_out(params, x), aux

    def _apply_xlstm(self, params, x):
        cfg = self.cfg

        def mlstm_block(p, x):
            return x + ssm_lib.mlstm_seq(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x), cfg.num_heads
            )

        def slstm_block(p, x):
            return x + ssm_lib.slstm_seq(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x), cfg.num_heads
            )

        def group_body(x, group_params):
            mp, sp = group_params

            def inner(x, p_layer):
                fn = mlstm_block
                if cfg.remat != "none":
                    fn = jax.checkpoint(mlstm_block)
                return shd.constrain(fn(p_layer, x), ("dp", None, None)), None

            x, _ = jax.lax.scan(lambda c, p: inner(c, p), x, mp)
            fn = slstm_block
            if cfg.remat != "none":
                fn = jax.checkpoint(slstm_block)
            x = shd.constrain(fn(sp, x), ("dp", None, None))
            return x, None

        x, _ = jax.lax.scan(
            group_body, x, (params["mlstm"], params["slstm"])
        )
        return x

    def _apply_hybrid(self, params, x):
        cfg = self.cfg
        block_fn = self._tfm_block_fn()

        def mamba_block(p, x):
            return x + ssm_lib.mamba2_seq(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x),
                cfg.ssm_state, cfg.ssm_head_dim,
            )

        def maybe_ckpt(fn):
            return jax.checkpoint(fn) if cfg.remat != "none" else fn

        def group_body(x, group_params):
            pre, post = group_params
            x, _ = jax.lax.scan(
                lambda c, p: (shd.constrain(
                    maybe_ckpt(mamba_block)(p, c), ("dp", None, None)
                ), None), x, pre
            )
            # shared attention block (params closed over — weights shared)
            x, _ = maybe_ckpt(
                lambda p, c: block_fn(p, c, jnp.int32(0), 10**9)
            )(params["shared"], x)
            x = maybe_ckpt(mamba_block)(post, x)
            return shd.constrain(x, ("dp", None, None)), None

        x, _ = jax.lax.scan(
            group_body, x, (params["mamba_pre"], params["mamba_post"])
        )
        if "mamba_tail" in params:
            x, _ = jax.lax.scan(
                lambda c, p: (maybe_ckpt(mamba_block)(p, c), None),
                x, params["mamba_tail"],
            )
        return x

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.apply(params, batch)
        ce, n_tokens = L.softmax_cross_entropy(
            logits, batch["targets"], batch.get("mask")
        )
        total = ce + aux
        return total, {
            "loss": total, "ce": ce, "aux": aux, "tokens": n_tokens,
        }

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def decode_cache_len(self, max_len: int) -> int:
        """Serving cache row count for a requested ``max_len``.

        When the block-granular decode path is enabled, the cache is
        rounded up to a whole number of ``decode_key_block`` blocks —
        at least two, since the block dispatch needs n_kb > 1 — so an
        off-size ``max_len`` can never silently fall back to the
        row-granular path (the padding rows are masked by cache_length
        everywhere). Callers that build position sentinels must use the
        rounded value (see ``runtime.serve_loop.ServeLoop``)."""
        e = self.cfg.energon
        if e.uses_decode_block:
            bk = e.decode_key_block
            return max(-(-max_len // bk), 2) * bk
        return max_len

    def init_cache(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        dt = self._dtype
        max_len = self.decode_cache_len(max_len)
        # Crossover gate: short caches never allocate the quantized
        # filter planes — below the measured threshold the plane upkeep
        # costs more traffic than the re-quantize it avoids, and every
        # consumer falls back to fresh (bit-identical) quantization
        # simply because the planes are absent.
        filter_block = (
            cfg.energon.decode_key_block
            if cfg.energon.filter_cache_engages(max_len) else 0
        )

        def attn_cache():
            return attn_lib.init_kv_cache(
                batch, cfg.num_kv_heads, max_len, cfg.head_dim, dt,
                filter_block=filter_block,
            )

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            one = attn_cache()
            return jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.num_layers,) + a.shape
                ).copy(),
                one,
            )
        if cfg.family == "ssm":
            m_per, s_per = cfg.xlstm_group
            groups = cfg.num_layers // (m_per + s_per)
            m_state = ssm_lib.mlstm_init_state(
                batch, cfg.d_model, cfg.num_heads, dt
            )
            s_state = ssm_lib.slstm_init_state(batch, cfg.d_model)
            return {
                "mlstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (groups, m_per) + a.shape
                    ).copy(), m_state,
                ),
                "slstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (groups,) + a.shape
                    ).copy(), s_state,
                ),
            }
        if cfg.family == "hybrid":
            period = cfg.hybrid_attn_every
            groups = cfg.num_layers // period
            tail = cfg.num_layers - groups * period
            m_state = ssm_lib.mamba2_init_state(
                batch, cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim, dtype=dt
            )
            cache = {
                "mamba_pre": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (groups, period - 1) + a.shape
                    ).copy(), m_state,
                ),
                "mamba_post": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (groups,) + a.shape
                    ).copy(), m_state,
                ),
                "shared_attn": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (groups,) + a.shape
                    ).copy(), attn_cache(),
                ),
            }
            if tail:
                cache["mamba_tail"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (tail,) + a.shape
                    ).copy(), m_state,
                )
            return cache
        raise ValueError(cfg.family)

    @property
    def supports_prefill(self) -> bool:
        """True when the family has a multi-token chunked-prefill path.

        Recurrent families (ssm/hybrid) carry sequential state and fall
        back to token-by-token admission in the serve loop.
        """
        return self.cfg.family in ("dense", "moe", "vlm", "audio")

    @property
    def supports_paged(self) -> bool:
        """True when the family can serve from a shared page pool.

        Needs a positional KV cache (attention families — recurrent
        state is O(1) per slot, paging it is meaningless), a positive
        ``decode_key_block`` (pages are exactly decode key blocks), and
        a non-dense impl (pure dense decode has no block machinery to
        page against).
        """
        e = self.cfg.energon
        return (
            self.supports_prefill
            and e.decode_key_block > 0
            and e.impl in ("mpmrf_row", "mpmrf_block", "pallas")
        )

    def init_paged_cache(
        self, num_pages: int, max_len: Optional[int] = None
    ) -> Dict[str, Any]:
        """Shared page-pool decode cache (DESIGN.md §4): per-layer pools
        with **no batch axis** — slots address them through the block
        table the scheduler threads via ``inputs['block_table']``.

        ``max_len`` is the per-slot logical capacity the serving loop
        will address through its block tables; the filter-plane
        crossover gate keys on it (pool capacity stands in when the
        caller doesn't know it yet)."""
        cfg = self.cfg
        if not self.supports_paged:
            raise ValueError(
                f"paged cache unsupported for family={cfg.family!r} / "
                f"impl={cfg.energon.impl!r}"
            )
        gate_len = (
            max_len if max_len is not None
            else num_pages * cfg.energon.decode_key_block
        )
        one = attn_lib.init_paged_kv_cache(
            num_pages, cfg.num_kv_heads, cfg.energon.decode_key_block,
            cfg.head_dim, self._dtype,
            filter_planes=cfg.energon.filter_cache_engages(gate_len),
        )
        return jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (cfg.num_layers,) + a.shape
            ).copy(),
            one,
        )

    def reset_pages(self, cache, page_mask: jax.Array):
        """Zero the K/V rows, filter codes and absmax scales of the
        masked physical pages (``page_mask`` ``[num_pages]`` bool).

        The paged analogue of :meth:`reset_decode_slots`: a page handed
        to a new occupant still holds its previous occupant's rows, and
        a boundary page mixing fresh rows with stale ones would
        quantize the fresh rows against an inflated stale absmax — so
        every freshly allocated page is zeroed before first use.
        """
        ps = self.cfg.energon.decode_key_block
        row_mask = jnp.repeat(page_mask, ps)          # [pool_rows]
        out = dict(cache)
        for key in ("k", "v", "k_codes"):
            if key in cache:
                leaf = cache[key]                     # [L, KV, rows, hd]
                out[key] = jnp.where(
                    row_mask[None, None, :, None], 0, leaf
                )
        if "k_scale" in cache:
            out["k_scale"] = jnp.where(
                page_mask[None, None, :], 0.0, cache["k_scale"]
            )
        return out

    def clone_pages(self, cache, src_pages, dst_pages):
        """Copy-on-write device step: duplicate whole physical pages
        (K/V rows + filter codes + per-page scale) of the paged cache.

        The prefix-sharing scheduler calls this when a slot must mutate
        a page that is shared (refcount > 1) or content-registered: the
        slot gets an exclusive bit-identical clone and the original
        stays immutable for its other readers. Destinations are fully
        overwritten, so they need no prior zeroing."""
        from repro.runtime import paged_cache as pgc

        return pgc.clone_page_rows(
            cache, self.cfg.energon.decode_key_block, src_pages, dst_pages
        )

    def prefill(
        self,
        params,
        cache,
        inputs: Dict[str, jax.Array],
        cache_index: jax.Array,
        telemetry: bool = False,
    ):
        """Multi-token chunked prefill: run a ``[B, C]`` prompt chunk
        against the cached history and write its K/V rows into the cache
        in one jitted call.

        inputs: ``{"tokens": [B, C]}`` (or ``{"embeddings": [B, C, d]}``
        for vlm/audio), plus optional ``"positions": [B, C]`` absolute
        cache positions per token. Positions default to
        ``cache_index[:, None] + arange(C)``; positions >= max_len mark
        padding tokens (no cache write, output ignored) so ragged chunks
        and partially-admitted batches share one compiled shape.

        Returns ``(logits [B, C, V], new_cache)``; with ``telemetry``,
        ``(logits, new_cache, stats)`` where stats is int32
        ``[L, B, 4]`` per-layer selection counts (see
        :func:`repro.core.filtering.selection_stats`). The caller
        advances ``cache_index`` by the number of real tokens per slot.
        """
        cfg = self.cfg
        if not self.supports_prefill:
            raise NotImplementedError(
                f"chunked prefill not supported for family {cfg.family!r}"
            )
        if cfg.uses_embeddings_input and "embeddings" in inputs:
            x = inputs["embeddings"].astype(self._dtype)
        else:
            x = L.embed_tokens(params["embed"], inputs["tokens"]).astype(
                self._dtype
            ) * (cfg.d_model ** 0.5)
        x = shd.constrain(x, ("dp", None, None))
        chunk = x.shape[1]
        positions = inputs.get("positions")
        if positions is None:
            positions = cache_index[:, None] + jnp.arange(chunk)[None, :]
        positions = positions.astype(jnp.int32)
        # paged serving: the scheduler threads the per-slot block table
        # (logical key block → physical page) alongside the tokens; the
        # cache write site then appends through it.
        block_table = inputs.get("block_table")

        has_windows = cfg.sliding_window > 0 and cfg.global_every > 0
        windows = self.layer_windows()

        def step_fn(layer_params, x, kv_cache, window, layer_idx):
            return self._prefill_attn_step(
                layer_params, x, kv_cache,
                window if has_windows else None, layer_idx, positions,
                block_table, telemetry=telemetry,
            )

        out = tfm.apply_stack_decode(
            params["blocks"], x, cache, windows, step_fn,
            prefix_layers=cfg.energon.min_prune_layer,
            telemetry=telemetry,
        )
        if telemetry:
            x, new_cache, stats = out
            return self._logits_out(params, x), new_cache, stats
        x, new_cache = out
        return self._logits_out(params, x), new_cache

    def _prefill_attn_step(self, layer_params, x, kv_cache, window,
                           layer_idx, positions, block_table=None,
                           telemetry=False):
        cfg = self.cfg

        def attn(p, xn, c):
            if block_table is not None:
                return attn_lib.paged_prefill_attention_block(
                    p, xn, c, positions, block_table, cfg.energon,
                    num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    rope_theta=cfg.rope_theta,
                    use_qk_norm=cfg.use_qk_norm,
                    window=window,
                    layer_index=layer_idx,
                    telemetry=telemetry,
                )
            return attn_lib.prefill_attention_block(
                p, xn, c, positions, cfg.energon,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                rope_theta=cfg.rope_theta,
                use_qk_norm=cfg.use_qk_norm,
                window=window,
                layer_index=layer_idx,
                telemetry=telemetry,
            )

        return self._serve_block_step(
            layer_params, x, kv_cache, attn, telemetry=telemetry
        )

    def _serve_block_step(self, layer_params, x, kv_cache, attn_call,
                          telemetry=False):
        """Shared decode/prefill block body: pre-norm attention +
        residual, then the MoE/MLP half. ``attn_call(params, x_normed,
        kv_cache) -> (h, new_cache)`` — ``(h, new_cache, stats)`` with
        ``telemetry``, threaded through unchanged. The two halves run
        under the named scopes ``attention`` and ``mlp`` (metadata for
        the device trace only)."""
        cfg = self.cfg
        xn = L.apply_norm(cfg.norm, layer_params["norm_attn"], x)
        with jax.named_scope("attention"):
            res = attn_call(layer_params["attn"], xn, kv_cache)
        if telemetry:
            h, new_cache, stats = res
        else:
            h, new_cache = res
        x = x + h
        h_in = L.apply_norm(cfg.norm, layer_params["norm_mlp"], x)
        with jax.named_scope("mlp"):
            if self._moe_cfg() is not None:
                h, _ = moe_lib.apply_moe(
                    layer_params["moe"], h_in, self._moe_cfg())
            else:
                h = L.apply_mlp(layer_params["mlp"], h_in, cfg.activation)
        if telemetry:
            return x + h, new_cache, stats
        return x + h, new_cache

    # Batch-axis position of each recurrent-state cache key (leading
    # axes are the scanned layer-group dims — see init_cache).
    _STATE_BATCH_AXES = {
        "mlstm": 2, "slstm": 1,
        "mamba_pre": 2, "mamba_post": 1, "mamba_tail": 1,
    }

    @staticmethod
    def _blend_state(new, old, active, batch_axis: int):
        """Per-slot state update gate: keep ``old`` where ``active`` is
        False. Recurrent states accumulate, so a whole-batch decode step
        must not advance slots that did not really consume a token."""
        def blend(n, o):
            shape = [1] * n.ndim
            shape[batch_axis] = -1
            return jnp.where(active.reshape(shape), n, o)

        return jax.tree.map(blend, new, old)

    # Attention serve-cache keys (KV rows + persistent filter planes);
    # leading axis is the stacked layer/group dim, batch axis is 1.
    _ATTN_CACHE_KEYS = ("k", "v", "k_codes", "k_scale")

    def reset_decode_slots(self, cache, reset_mask: jax.Array):
        """Zero the decode state of the masked slots (``reset_mask``
        ``[B]`` bool). Recurrent states accumulate and a freshly
        admitted slot must not inherit its previous occupant's state.
        Attention KV rows are positional and would self-heal, but the
        per-block filter scales are *block* aggregates: a boundary block
        mixing a new prompt's rows with a previous occupant's stale rows
        would quantize the real rows against an inflated stale absmax —
        so reset slots' KV rows and filter planes are zeroed too.

        (`_blend_state(new, old, active)` takes ``new`` where ``active``
        — the reset slots are the *active* ones here; the previous
        revision passed the complement, which zeroed every slot *except*
        the admitted one and left the admitted slot with its previous
        occupant's state.)"""
        out = dict(cache)
        for key, ax in self._STATE_BATCH_AXES.items():
            if key in cache:
                out[key] = self._blend_state(
                    jax.tree.map(jnp.zeros_like, cache[key]), cache[key],
                    reset_mask, ax,
                )

        def reset_attn(attn_cache):
            return {
                key: self._blend_state(
                    jnp.zeros_like(leaf), leaf, reset_mask, 1
                ) if key in self._ATTN_CACHE_KEYS else leaf
                for key, leaf in attn_cache.items()
            }

        # Every MP-MRF impl quantizes decode caches (block impls per
        # key block, the row path per head over the *whole* padded
        # cache), so stale rows poison absmax scales for all of them;
        # only pure dense decode never quantizes and keeps the free
        # positional-self-heal path.
        if self.cfg.energon.impl != "dense":
            if self.cfg.family in ("dense", "moe", "vlm", "audio"):
                out = reset_attn(out)
            if "shared_attn" in cache:
                out["shared_attn"] = reset_attn(cache["shared_attn"])
        return out

    def decode_step(
        self,
        params,
        cache,
        inputs: Dict[str, jax.Array],
        cache_index: jax.Array,
        telemetry: bool = False,
    ):
        """One-token decode. inputs: {"tokens": [B,1]} or
        {"embeddings": [B,1,d]}, plus optional {"active": [B] bool} —
        recurrent state only advances on active slots (KV-cache writes
        are positional and self-healing, so they are not gated);
        cache_index ``[B]`` current lengths.

        With ``telemetry``, returns ``(logits, new_cache, stats)``
        where stats is int32 ``[L, B, 4]`` per-layer selection counts;
        recurrent families report an empty ``[0, B, 4]`` (their
        attention, if any, lives inside group scans)."""
        cfg = self.cfg
        if cfg.uses_embeddings_input and "embeddings" in inputs:
            x = inputs["embeddings"].astype(self._dtype)
        else:
            x = L.embed_tokens(params["embed"], inputs["tokens"]).astype(
                self._dtype
            ) * (cfg.d_model ** 0.5)
        active = inputs.get("active")
        block_table = inputs.get("block_table")

        stats = None
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            out = self._decode_tfm(
                params, cache, x, cache_index,
                block_table=block_table, active=active,
                telemetry=telemetry,
            )
            if telemetry:
                x, new_cache, stats = out
            else:
                x, new_cache = out
        elif cfg.family == "ssm":
            x, new_cache = self._decode_xlstm(params, cache, x)
        elif cfg.family == "hybrid":
            x, new_cache = self._decode_hybrid(params, cache, x, cache_index)
        if active is not None and cfg.family in ("ssm", "hybrid"):
            for key, ax in self._STATE_BATCH_AXES.items():
                if key in new_cache:
                    new_cache[key] = self._blend_state(
                        new_cache[key], cache[key], active, ax
                    )
        logits = self._logits_out(params, x)
        if telemetry:
            if stats is None:
                stats = jnp.zeros((0, x.shape[0], 4), jnp.int32)
            return logits, new_cache, stats
        return logits, new_cache

    def _decode_attn_step(self, layer_params, x, kv_cache, window,
                          layer_idx, cache_index, block_table=None,
                          active=None, telemetry=False):
        cfg = self.cfg

        def attn(p, xn, c):
            if block_table is not None:
                return attn_lib.paged_decode_attention_block(
                    p, xn, c, cache_index, block_table, cfg.energon,
                    num_heads=cfg.num_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    rope_theta=cfg.rope_theta,
                    use_qk_norm=cfg.use_qk_norm,
                    window=window,
                    layer_index=layer_idx,
                    active=active,
                    telemetry=telemetry,
                )
            return attn_lib.decode_attention_block(
                p, xn, c, cache_index, cfg.energon,
                num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                rope_theta=cfg.rope_theta,
                use_qk_norm=cfg.use_qk_norm,
                window=window,
                layer_index=layer_idx,
                telemetry=telemetry,
            )

        return self._serve_block_step(
            layer_params, x, kv_cache, attn, telemetry=telemetry
        )

    def _decode_tfm(self, params, cache, x, cache_index,
                    block_table=None, active=None, telemetry=False):
        cfg = self.cfg
        has_windows = cfg.sliding_window > 0 and cfg.global_every > 0
        windows = self.layer_windows()

        def step_fn(layer_params, x, kv_cache, window, layer_idx):
            return self._decode_attn_step(
                layer_params, x, kv_cache,
                window if has_windows else None, layer_idx, cache_index,
                block_table=block_table, active=active,
                telemetry=telemetry,
            )

        return tfm.apply_stack_decode(
            params["blocks"], x, cache, windows, step_fn,
            prefix_layers=cfg.energon.min_prune_layer,
            telemetry=telemetry,
        )

    def _decode_xlstm(self, params, cache, x):
        cfg = self.cfg

        def m_step(p, x, st):
            h, new = ssm_lib.mlstm_step(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x),
                st, cfg.num_heads,
            )
            return x + h, new

        def s_step(p, x, st):
            h, new = ssm_lib.slstm_step(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x),
                st, cfg.num_heads,
            )
            return x + h, new

        def group_body(x, xs):
            (mp, sp), (mst, sst) = xs

            def inner(x, inner_xs):
                p_layer, st = inner_xs
                x, new_st = m_step(p_layer, x, st)
                return x, new_st

            x, new_mst = jax.lax.scan(inner, x, (mp, mst))
            x, new_sst = s_step(sp, x, sst)
            return x, (new_mst, new_sst)

        x, (new_m, new_s) = jax.lax.scan(
            group_body, x,
            ((params["mlstm"], params["slstm"]),
             (cache["mlstm"], cache["slstm"])),
        )
        return x, {"mlstm": new_m, "slstm": new_s}

    def _decode_hybrid(self, params, cache, x, cache_index):
        cfg = self.cfg

        def m_step(p, x, st):
            h, new = ssm_lib.mamba2_step(
                p["cell"], L.apply_norm(cfg.norm, p["norm"], x),
                st, cfg.ssm_state, cfg.ssm_head_dim,
            )
            return x + h, new

        def group_body(x, xs):
            (pre_p, post_p), (pre_st, post_st, attn_st) = xs
            x, new_pre = jax.lax.scan(
                lambda c, z: m_step(z[0], c, z[1]), x, (pre_p, pre_st)
            )
            x, new_attn = self._decode_attn_step(
                params["shared"], x, attn_st, None, 10**9, cache_index
            )
            x, new_post = m_step(post_p, x, post_st)
            return x, (new_pre, new_post, new_attn)

        x, (new_pre, new_post, new_attn) = jax.lax.scan(
            group_body, x,
            ((params["mamba_pre"], params["mamba_post"]),
             (cache["mamba_pre"], cache["mamba_post"],
              cache["shared_attn"])),
        )
        new_cache = {
            "mamba_pre": new_pre,
            "mamba_post": new_post,
            "shared_attn": new_attn,
        }
        if "mamba_tail" in params:
            x, new_tail = jax.lax.scan(
                lambda c, z: m_step(z[0], c, z[1]),
                x, (params["mamba_tail"], cache["mamba_tail"]),
            )
            new_cache["mamba_tail"] = new_tail
        return x, new_cache
