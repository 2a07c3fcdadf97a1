"""Serving-engine tests: chunked prefill, block-granular decode, and the
continuous-batching loop.

Covers the engine contracts the refactor introduced:
  * prefill/decode parity — chunked prefill + decode steps reproduce the
    full-sequence ``model.apply`` logits (dense and mpmrf_block impls);
  * block-granular decode matches row-granular decode at ρ=1;
  * admitting a long prompt costs O(L/chunk) jitted dispatches;
  * per-slot temperature/RNG — a greedy request is untouched by a
    stochastic batch neighbour.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import EnergonConfig, energon_decode_attention
from repro.models import LMModel
from repro.runtime import Request, ServeLoop


def _model(energon, **kw):
    cfg = ModelConfig(
        name="serve-test", family="dense", num_layers=3, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
        dtype="float32", remat="none", energon=energon, **kw,
    )
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _chunked_prefill(model, params, prompt, chunk, max_len):
    """Prefill `prompt` through the chunked path; returns per-token
    logits, the cache, and the final cache_index."""
    length = len(prompt)
    cache = model.init_cache(1, max_len)
    ci = jnp.zeros((1,), jnp.int32)
    outs = []
    for lo in range(0, length, chunk):
        part = prompt[lo:lo + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        pos = np.full((1, chunk), max_len, np.int32)  # sentinel = no write
        pos[0, :len(part)] = lo + np.arange(len(part))
        logits, cache = model.prefill(
            params, cache,
            {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)},
            ci,
        )
        outs.append(np.asarray(logits[0, :len(part)]))
        ci = ci + len(part)
    return np.concatenate(outs, axis=0), cache, ci


class TestPrefillDecodeParity:
    @pytest.mark.parametrize(
        "energon,atol",
        [
            (EnergonConfig(impl="dense"), 1e-4),
            # block path engaged in prefill (n_q = groups*chunk = 16),
            # decode, and full apply; ρ=1 ⇒ keep-everything ⇒ exact.
            (EnergonConfig(impl="mpmrf_block", pruning_ratio=1.0,
                           query_block=8, key_block=16,
                           decode_key_block=16, min_prune_layer=1), 1e-2),
        ],
        ids=["dense", "mpmrf_block_rho1"],
    )
    def test_chunked_prefill_then_decode_matches_apply(self, energon, atol):
        cfg, model, params = _model(energon)
        rng = np.random.default_rng(1)
        L, chunk, max_len = 32, 8, 64
        prompt = rng.integers(1, cfg.vocab_size - 1, size=L).tolist()
        toks = jnp.asarray([prompt], jnp.int32)
        full_logits, _ = model.apply(
            params, {"inputs": toks, "targets": toks}
        )
        pre_logits, cache, ci = _chunked_prefill(
            model, params, prompt, chunk, max_len
        )
        np.testing.assert_allclose(
            pre_logits, np.asarray(full_logits[0]), atol=atol, rtol=0
        )
        # decode continuation: greedy tokens + logits track apply()
        seq = list(prompt)
        for _ in range(4):
            nxt = int(jnp.argmax(full_logits[0, len(seq) - 1]))
            step_logits, cache = model.decode_step(
                params, cache,
                {"tokens": jnp.asarray([[nxt]], jnp.int32)}, ci,
            )
            ci = ci + 1
            seq.append(nxt)
            ext = jnp.asarray([seq], jnp.int32)
            full_logits, _ = model.apply(
                params, {"inputs": ext, "targets": ext}
            )
            np.testing.assert_allclose(
                np.asarray(step_logits[0, -1]),
                np.asarray(full_logits[0, -1]), atol=atol, rtol=0,
            )

    def test_ragged_chunk_and_sentinel_slots_are_inert(self):
        """Padding rows (position sentinel) must not perturb live slots:
        prefilling with batch=2 where slot 1 is inactive equals batch=1."""
        cfg, model, params = _model(EnergonConfig(impl="dense"))
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, cfg.vocab_size - 1, size=10).tolist()
        max_len, chunk = 32, 4  # 10 = 4+4+2 → ragged final chunk
        ref, _, _ = _chunked_prefill(model, params, prompt, chunk, max_len)

        cache = model.init_cache(2, max_len)
        ci = jnp.zeros((2,), jnp.int32)
        outs = []
        for lo in range(0, 10, chunk):
            part = prompt[lo:lo + chunk]
            toks = np.zeros((2, chunk), np.int32)
            toks[0, :len(part)] = part
            toks[1, :] = 17  # garbage tokens on the inactive slot
            pos = np.full((2, chunk), max_len, np.int32)
            pos[0, :len(part)] = lo + np.arange(len(part))
            logits, cache = model.prefill(
                params, cache,
                {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)},
                ci,
            )
            outs.append(np.asarray(logits[0, :len(part)]))
        got = np.concatenate(outs, axis=0)
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=0)
        # inactive slot's cache rows stay exactly zero (init state)
        k_cache = jax.tree_util.tree_leaves(cache)[0]
        assert float(jnp.abs(k_cache[:, 1]).max()) == 0.0

    def test_sentinel_rows_do_not_leak_into_block_selection(self):
        """Sentinel (padding) query rows share pooled block-score planes
        with a ragged chunk's real rows under mpmrf_block: their garbage
        content must not change which blocks the real rows attend."""
        from repro.core import energon_attention

        rng = np.random.default_rng(7)
        B, H, n_k, d = 1, 2, 64, 16
        real, pad = 8, 8
        k = jnp.asarray(rng.normal(size=(B, H, n_k, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, n_k, d)), jnp.float32)
        q_real = jnp.asarray(rng.normal(size=(B, H, real, d)), jnp.float32)
        pos = jnp.concatenate(
            [jnp.arange(32, 32 + real)[None, :],
             jnp.full((1, pad), n_k)], axis=1,
        ).astype(jnp.int32)
        cfg = EnergonConfig(impl="mpmrf_block", pruning_ratio=4.0,
                            query_block=16, key_block=8, min_prune_layer=0)
        outs = []
        for filler in (0.0, 1e3):
            q = jnp.concatenate(
                [q_real, jnp.full((B, H, pad, d), filler, jnp.float32)],
                axis=2,
            )
            out = energon_attention(q, k, v, cfg, causal=True,
                                    q_positions=pos)
            outs.append(np.asarray(out[:, :, :real]))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-6, rtol=0)


class TestBlockGranularDecode:
    def _qkv_cache(self, B=2, H=4, n=64, d=16, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda s: jnp.asarray(rng.normal(size=s), jnp.float32)
        return mk((B, H, 1, d)), mk((B, H, n, d)), mk((B, H, n, d))

    def test_matches_row_granular_at_ratio_1(self):
        q, k, v = self._qkv_cache()
        cl = jnp.asarray([7, 55], jnp.int32)
        block = energon_decode_attention(
            q, k, v, cl,
            EnergonConfig(impl="mpmrf_block", pruning_ratio=1.0,
                          decode_key_block=8, min_prune_layer=0),
            layer_index=5,
        )
        row = energon_decode_attention(
            q, k, v, cl,
            EnergonConfig(impl="mpmrf_row", pruning_ratio=1.0,
                          min_prune_layer=0),
            layer_index=5,
        )
        np.testing.assert_allclose(
            np.asarray(block), np.asarray(row), atol=1e-2, rtol=0
        )
        # and both equal dense over the valid prefix
        dense = energon_decode_attention(
            q, k, v, cl, EnergonConfig(impl="dense"), layer_index=5
        )
        np.testing.assert_allclose(
            np.asarray(block), np.asarray(dense), atol=1e-5, rtol=0
        )

    def test_pruned_budget_attends_subset(self):
        """At ρ>1 the gather only touches budget·bk keys; sanity-check
        output is finite and the sink + newest blocks are always kept."""
        from repro.core import MPMRFConfig, mpmrf_decode_block_select

        q, k, v = self._qkv_cache(seed=4)
        n = k.shape[-2]
        cl = jnp.asarray([39, 64], jnp.int32)
        bk = 8
        n_kb = n // bk
        budget = n_kb // 4
        valid = (jnp.arange(n)[None, :] < cl[:, None])[:, None, None, :]
        valid = jnp.broadcast_to(valid, q.shape[:-2] + (1, n))
        res = mpmrf_decode_block_select(
            q, k, MPMRFConfig(key_block=bk, granularity="block",
                              block_budget=budget),
            valid, cl,
        )
        assert res.block_indices.shape[-1] == budget
        idx = np.asarray(res.block_indices[..., 0, :])
        val = np.asarray(res.block_valid[..., 0, :])
        for b in range(q.shape[0]):
            last_blk = (int(cl[b]) - 1) // bk
            sel = {int(i) for i, v01 in zip(idx[b].ravel(), val[b].ravel())
                   if v01}
            # selection is per-head; sink and newest block in every head
            for h in range(q.shape[1]):
                head_sel = {int(i) for i, v01 in zip(idx[b, h], val[b, h])
                            if v01}
                assert 0 in head_sel
                assert last_blk in head_sel
                # never selects fully-invalid blocks
                n_valid_blk = -(-int(cl[b]) // bk)
                assert max(head_sel) < n_valid_blk
        out = energon_decode_attention(
            q, k, v, cl,
            EnergonConfig(impl="mpmrf_block", pruning_ratio=4.0,
                          decode_key_block=bk, min_prune_layer=0),
            layer_index=5,
        )
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_budget_fill_is_score_ordered(self):
        """Unused budget slots fill with the *highest-scoring* remaining
        valid blocks, not the lowest-indexed ones."""
        from repro.core import MPMRFConfig, mpmrf_decode_block_select

        n, bk, d = 64, 8, 8
        n_kb = n // bk
        q = jnp.ones((1, 1, 1, d), jnp.float32)
        # block j's keys are (j+1)·0.05 ⇒ block scores strictly increase
        # with j; the Eq.3 mean threshold keeps the upper half.
        k = jnp.concatenate(
            [jnp.full((1, 1, bk, d), (j + 1) * 0.05) for j in range(n_kb)],
            axis=2,
        ).astype(jnp.float32)
        cl = jnp.asarray([n], jnp.int32)
        valid = jnp.ones((1, 1, 1, n), bool)
        res = mpmrf_decode_block_select(
            q, k, MPMRFConfig(key_block=bk, block_budget=6), valid, cl
        )
        sel = {int(i) for i, v in zip(np.asarray(res.block_indices[0, 0, 0]),
                                      np.asarray(res.block_valid[0, 0, 0]))
               if v}
        # pins: sink 0 + newest 7; survivors: 4,5,6; fill: best
        # non-survivor = 3 (NOT block 1 or 2, which index-order would pick)
        assert sel == {0, 7, 4, 5, 6, 3}, sel

    def test_prefill_block_select_keeps_offset_local_block(self):
        """keep_diagonal must pin the block holding each query block's
        *absolute* newest position for offset (prefill) chunks, not the
        offset-0 default of block 0."""
        from repro.core import MPMRFConfig, mpmrf_block_select

        rng = np.random.default_rng(11)
        B, H, n_q, n_k, d, bq, bk = 1, 2, 8, 64, 16, 8, 16
        q = jnp.asarray(rng.normal(size=(B, H, n_q, d)), jnp.float32)
        # local block's keys tiny ⇒ thresholds would drop it
        k = jnp.asarray(rng.normal(size=(B, H, n_k, d)), jnp.float32)
        k = k.at[:, :, 32:48].multiply(1e-3)
        positions = jnp.arange(32, 40)[None, :]          # local block = 2
        valid = (jnp.arange(n_k)[None, None, None, :]
                 <= positions[:, None, :, None])
        valid = jnp.broadcast_to(valid, (B, H, n_q, n_k))
        diag_blocks = jnp.full((B, n_q // bq), 2, jnp.int32)
        cfg = MPMRFConfig(query_block=bq, key_block=bk, block_budget=2)
        res = mpmrf_block_select(q, k, cfg, valid, diag_blocks=diag_blocks)
        # the threshold keep-mask must retain the true local block…
        assert bool(jnp.all(res.keep_mask[..., 2])), res.keep_mask
        # …whereas the offset-0 default would pin block 0 and let the
        # threshold rounds drop the local block entirely.
        res_default = mpmrf_block_select(q, k, cfg, valid)
        assert not bool(jnp.all(res_default.keep_mask[..., 2]))

    def test_q_positions_respects_chunk_threshold(self):
        """The q_positions form has no chunked fallback: exceeding
        chunk_threshold must raise instead of silently materializing."""
        from repro.core import energon_attention

        q = jnp.zeros((1, 1, 8, 4), jnp.float32)
        kv = jnp.zeros((1, 1, 64, 4), jnp.float32)
        pos = jnp.arange(8)[None, :]
        cfg = EnergonConfig(impl="dense", chunk_threshold=128)
        with pytest.raises(ValueError, match="chunk_threshold"):
            energon_attention(q, kv, kv, cfg, q_positions=pos)

    def test_windowed_block_decode_matches_dense(self):
        q, k, v = self._qkv_cache(seed=9)
        cl = jnp.asarray([33, 61], jnp.int32)
        for w in (8, 16):
            dense = energon_decode_attention(
                q, k, v, cl, EnergonConfig(impl="dense"),
                layer_index=5, window=w,
            )
            block = energon_decode_attention(
                q, k, v, cl,
                EnergonConfig(impl="mpmrf_block", pruning_ratio=1.0,
                              decode_key_block=8, min_prune_layer=0),
                layer_index=5, window=w,
            )
            np.testing.assert_allclose(
                np.asarray(block), np.asarray(dense), atol=1e-5, rtol=0
            )


class TestFilterCachePersistence:
    """The persistent quantized filter cache: incremental appends must
    stay bit-identical to a fresh per-block re-quantization of the
    float cache (the invariant the decode filter relies on), including
    across long generations and slot-reuse cycles."""

    BK = 16

    def _model(self, filter_cache=True, impl="mpmrf_block"):
        return _model(EnergonConfig(
            impl=impl, pruning_ratio=2.0, query_block=8, key_block=16,
            decode_key_block=self.BK, min_prune_layer=1,
            filter_cache=filter_cache, filter_cache_min_len=0,
        ))

    def _assert_invariant(self, cache):
        from repro.core import quantize_int16_blocks

        codes, scales = quantize_int16_blocks(cache["k"], self.BK)
        np.testing.assert_array_equal(
            np.asarray(codes), np.asarray(cache["k_codes"])
        )
        np.testing.assert_allclose(
            np.asarray(scales), np.asarray(cache["k_scale"])
        )

    def test_long_generation_matches_requantize_path(self):
        """≥64 incremental decode appends: cached-plane selection must
        equal fresh-requantize selection — asserted end-to-end as
        bit-equal greedy continuations plus the code/scale invariant."""
        def generate(filter_cache):
            cfg, model, params = self._model(filter_cache)
            cache = model.init_cache(1, 128)
            ci = jnp.zeros((1,), jnp.int32)
            prompt = list(range(1, 9))
            toks = np.zeros((1, 8), np.int32)
            toks[0] = prompt
            pos = np.arange(8, dtype=np.int32)[None, :]
            logits, cache = model.prefill(
                params, cache,
                {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)},
                ci,
            )
            ci = ci + 8
            nxt = int(jnp.argmax(logits[0, 7]))
            out = []
            for _ in range(70):
                logits, cache = model.decode_step(
                    params, cache,
                    {"tokens": jnp.asarray([[nxt]], jnp.int32)}, ci,
                )
                ci = ci + 1
                nxt = int(jnp.argmax(logits[0, -1]))
                out.append(nxt)
            return out, cache

        cached_toks, cache = generate(True)
        fresh_toks, _ = generate(False)
        assert cached_toks == fresh_toks
        assert "k_codes" in cache
        self._assert_invariant(cache)

    def test_slot_reuse_cycle_preserves_invariant(self):
        """More requests than slots forces reset_decode_slots reuse
        cycles; the filter cache must hold the invariant afterwards and
        per-request outputs must match the requantize engine exactly."""
        def run(filter_cache):
            cfg, model, params = self._model(filter_cache)
            engine = ServeLoop(model, params, batch_slots=2, max_len=96,
                               eos_token=cfg.vocab_size - 1,
                               prefill_chunk=8)
            rng = np.random.default_rng(0)
            for uid in range(5):
                engine.submit(Request(
                    uid=uid,
                    prompt=rng.integers(
                        1, cfg.vocab_size - 1,
                        size=int(rng.integers(3, 24))).tolist(),
                    max_new_tokens=12,
                ))
            done = engine.run_until_drained()
            return {r.uid: r.tokens_out for r in done}, engine.cache

        toks_cached, cache = run(True)
        toks_fresh, _ = run(False)
        assert toks_cached == toks_fresh
        self._assert_invariant(cache)

    def test_pallas_impl_drains_and_holds_invariant(self):
        """cfg.impl='pallas' serves through the fused decode kernel
        (interpret mode on CPU) inside the jitted engine step."""
        cfg, model, params = self._model(impl="pallas")
        engine = ServeLoop(model, params, batch_slots=2, max_len=64,
                           eos_token=cfg.vocab_size - 1, prefill_chunk=8)
        for uid in range(3):
            engine.submit(Request(uid=uid, prompt=[1 + uid, 2, 3, 4, 5],
                                  max_new_tokens=6))
        done = engine.run_until_drained()
        assert len(done) == 3
        for r in done:
            assert 1 <= len(r.tokens_out) <= 6
            assert all(0 <= t < cfg.vocab_size for t in r.tokens_out)
        self._assert_invariant(engine.cache)

    def test_cache_len_rounds_up_to_block_multiple(self):
        cfg, model, params = self._model()
        assert model.decode_cache_len(60) == 64
        # ≥ 2 blocks always: the block dispatch needs n_kb > 1
        assert model.decode_cache_len(10) == 32
        cache = model.init_cache(1, 60)
        assert cache["k"].shape[-2] == 64
        assert cache["k_codes"].shape[-2] == 64
        assert cache["k_scale"].shape[-1] == 4
        engine = ServeLoop(model, params, batch_slots=1, max_len=60,
                           eos_token=cfg.vocab_size - 1)
        assert engine.max_len == 64
        # dense impls keep the requested size and a lean cache
        cfg_d, model_d, _ = _model(EnergonConfig(impl="dense"))
        assert model_d.decode_cache_len(60) == 60
        assert "k_codes" not in model_d.init_cache(1, 60)

    def test_reset_decode_slots_clears_reset_slot_only(self):
        cfg, model, params = self._model()
        cache = model.init_cache(2, 64)
        ci = jnp.zeros((2,), jnp.int32)
        toks = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        pos = np.broadcast_to(np.arange(4, dtype=np.int32), (2, 4)).copy()
        _, cache = model.prefill(
            params, cache,
            {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos)}, ci,
        )
        assert float(jnp.abs(cache["k"][:, 1]).max()) > 0
        reset = model.reset_decode_slots(
            cache, jnp.asarray([False, True])
        )
        # slot 1 zeroed across rows, codes and scales; slot 0 untouched
        for key in ("k", "v", "k_codes", "k_scale"):
            assert float(jnp.abs(reset[key][:, 1].astype(jnp.float32)).max()) == 0.0
            np.testing.assert_array_equal(
                np.asarray(reset[key][:, 0]), np.asarray(cache[key][:, 0])
            )


class TestServeEngine:
    def _engine(self, energon=None, **kw):
        cfg, model, params = _model(
            energon or EnergonConfig(impl="mpmrf_block", pruning_ratio=2.0,
                                     decode_key_block=16, min_prune_layer=1)
        )
        kw.setdefault("eos_token", cfg.vocab_size - 1)
        return cfg, ServeLoop(model, params, **kw)

    def test_dispatch_count_for_long_prompt(self):
        """Admitting a 256-token prompt with chunk 64 costs ≤ 5 jitted
        model calls (the seed engine issued ~256 decode steps). The
        whole-wave-in-one-tick shape is the *sync* scheduler's contract;
        the hybrid scheduler's one-chunk-per-tick budget has its own
        test (test_hybrid_scheduler.py)."""
        cfg, engine = self._engine(
            batch_slots=2, max_len=512, prefill_chunk=64,
            scheduler="sync",
        )
        calls = {"prefill": 0, "decode": 0}
        orig_prefill, orig_step = engine.prefill_fn, engine.step_fn

        def counting_prefill(*a, **k):
            calls["prefill"] += 1
            return orig_prefill(*a, **k)

        def counting_step(*a, **k):
            calls["decode"] += 1
            return orig_step(*a, **k)

        engine.prefill_fn = counting_prefill
        engine.step_fn = counting_step
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, cfg.vocab_size - 1, size=256).tolist()
        engine.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        engine.tick()
        assert calls["prefill"] <= 5, calls
        assert calls["prefill"] == engine.metrics.prefill_dispatches == 4
        assert calls["decode"] == 1
        assert engine.metrics.prefill_tokens == 256

    def test_batched_admission_shares_prefill_dispatches(self):
        """All slots admitted in one tick prefill together: an admission
        wave costs ceil(max_L/chunk) dispatches, not sum(ceil(L_i/chunk)).
        (Sync scheduler: the hybrid tick shares dispatches the same way
        but spreads them one chunk wave per tick.)"""
        cfg, engine = self._engine(
            batch_slots=4, max_len=128, prefill_chunk=16,
            scheduler="sync",
        )
        rng = np.random.default_rng(2)
        for uid, L in enumerate((48, 33, 20)):
            engine.submit(Request(
                uid=uid,
                prompt=rng.integers(1, cfg.vocab_size - 1, size=L).tolist(),
                max_new_tokens=2,
            ))
        engine.tick()
        assert engine.metrics.prefill_dispatches == 3  # ceil(48/16)
        assert engine.metrics.prefill_tokens == 48 + 33 + 20
        done = engine.run_until_drained()
        assert len(done) == 3
        for r in done:
            assert 1 <= len(r.tokens_out) <= 2

    def test_drains_mixed_traffic(self):
        cfg, engine = self._engine(
            batch_slots=4, max_len=96, prefill_chunk=8
        )
        rng = np.random.default_rng(0)
        n_req = 7
        for uid in range(n_req):
            engine.submit(Request(
                uid=uid,
                prompt=rng.integers(1, cfg.vocab_size - 1,
                                    size=int(rng.integers(1, 20))).tolist(),
                max_new_tokens=6,
                temperature=0.9 if uid % 2 else 0.0,
            ))
        done = engine.run_until_drained()
        assert len(done) == n_req
        for r in done:
            assert 1 <= len(r.tokens_out) <= 6
            assert all(0 <= t < cfg.vocab_size for t in r.tokens_out)

    def test_greedy_slot_immune_to_stochastic_neighbour(self):
        """The seed engine sampled the whole batch at max(temps): one hot
        request made every greedy request stochastic. Per-slot sampling
        must keep the greedy continuation bit-identical."""
        prompt = list(range(1, 11))

        def greedy_tokens(with_neighbour):
            cfg, engine = self._engine(
                batch_slots=2, max_len=64, prefill_chunk=8
            )
            engine.submit(Request(uid=0, prompt=prompt, max_new_tokens=6,
                                  temperature=0.0))
            if with_neighbour:
                engine.submit(Request(uid=1, prompt=[11, 12, 13],
                                      max_new_tokens=6, temperature=1.5))
            done = engine.run_until_drained()
            return [r for r in done if r.uid == 0][0].tokens_out

        assert greedy_tokens(False) == greedy_tokens(True)

    def test_per_request_rng_is_reproducible(self):
        """Same uid + same rng seed ⇒ same stochastic sample, regardless
        of submission order."""
        def sample(order):
            cfg, engine = self._engine(
                batch_slots=2, max_len=64, prefill_chunk=8
            )
            reqs = {
                7: Request(uid=7, prompt=[1, 2, 3, 4], max_new_tokens=5,
                           temperature=1.0),
                8: Request(uid=8, prompt=[5, 6, 7], max_new_tokens=5,
                           temperature=1.0),
            }
            for uid in order:
                engine.submit(reqs[uid])
            done = engine.run_until_drained()
            return {r.uid: r.tokens_out for r in done}

        a, b = sample([7, 8]), sample([8, 7])
        assert a[7] == b[7]
        assert a[8] == b[8]

    def _ssm_model(self):
        cfg = ModelConfig(
            name="ssm-serve", family="ssm", num_layers=2, d_model=32,
            num_heads=2, num_kv_heads=2, head_dim=16, d_ff=0,
            vocab_size=32, dtype="float32", remat="none",
            xlstm_group=(1, 1), energon=EnergonConfig(impl="dense"),
        )
        model = LMModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        return cfg, model, params

    def test_sequential_fallback_for_recurrent_family(self):
        """ssm models have no chunked prefill; the engine must fall back
        to token-by-token admission and still drain."""
        cfg, model, params = self._ssm_model()
        assert not model.supports_prefill
        engine = ServeLoop(model, params, batch_slots=2, max_len=48,
                           eos_token=cfg.vocab_size - 1, prefill_chunk=8)
        assert engine.prefill_fn is None
        for uid in range(3):
            engine.submit(Request(uid=uid, prompt=[1, 2, 3, 4],
                                  max_new_tokens=4))
        done = engine.run_until_drained()
        assert len(done) == 3
        for r in done:
            assert 1 <= len(r.tokens_out) <= 4

    def test_sequential_admission_wave_shares_dispatches(self):
        """Recurrent-family admission marches all admitted prompts
        together: a wave of prompts costs max(L)-1 decode dispatches."""
        cfg, model, params = self._ssm_model()
        engine = ServeLoop(model, params, batch_slots=2, max_len=48,
                           eos_token=cfg.vocab_size - 1)
        engine.submit(Request(uid=0, prompt=[1, 2, 3, 4, 5, 6, 7],
                              max_new_tokens=2))
        engine.submit(Request(uid=1, prompt=[8, 9, 10],
                              max_new_tokens=2))
        engine.tick()
        assert engine.metrics.prefill_dispatches == 6  # max(7,3) - 1
        assert engine.metrics.prefill_tokens == 6 + 2

    def test_recurrent_state_isolated_from_neighbour_admission(self):
        """A mid-decode recurrent slot must not see its state advanced
        by a neighbour's sequential prefill (whole-batch decode steps),
        nor inherit state from a slot's previous occupant."""
        cfg, model, params = self._ssm_model()

        def greedy_tokens(with_neighbour):
            engine = ServeLoop(model, params, batch_slots=2, max_len=48,
                               eos_token=cfg.vocab_size - 1)
            engine.submit(Request(uid=0, prompt=[1, 2, 3, 4, 5, 6],
                                  max_new_tokens=8, temperature=0.0))
            engine.tick()
            engine.tick()  # uid 0 is mid-decode…
            if with_neighbour:
                # …when a neighbour's token-by-token prefill arrives
                engine.submit(Request(uid=1, prompt=[7, 8, 9, 10, 11],
                                      max_new_tokens=8, temperature=0.0))
            done = engine.run_until_drained()
            return [r for r in done if r.uid == 0][0].tokens_out

        assert greedy_tokens(False) == greedy_tokens(True)

    def test_reset_decode_slots_recurrent_polarity(self):
        """reset_decode_slots must zero exactly the *masked* slots (the
        pre-filter-cache revision zeroed the complement: every slot
        except the admitted one, which kept its previous occupant's
        accumulated state)."""
        cfg, model, params = self._ssm_model()
        cache = model.init_cache(2, 16)
        cache = jax.tree.map(jnp.ones_like, cache)
        out = model.reset_decode_slots(cache, jnp.asarray([False, True]))
        for leaf in jax.tree.leaves(out["mlstm"]):   # batch axis 2
            assert float(jnp.abs(leaf[:, :, 0]).max()) > 0
            assert float(jnp.abs(leaf[:, :, 1]).max()) == 0
        for leaf in jax.tree.leaves(out["slstm"]):   # batch axis 1
            assert float(jnp.abs(leaf[:, 0]).max()) > 0
            assert float(jnp.abs(leaf[:, 1]).max()) == 0

    def test_engine_metrics_split(self):
        # an EOS id no model can emit: the request decodes all its
        # tokens whatever the random weights sample first
        cfg, engine = self._engine(
            batch_slots=2, max_len=64, prefill_chunk=4, eos_token=-1
        )
        engine.submit(Request(uid=0, prompt=[1, 2, 3, 4, 5, 6],
                              max_new_tokens=3))
        engine.run_until_drained()
        m = engine.metrics
        assert m.prefill_tokens == 6
        assert m.prefill_dispatches == 2          # ceil(6/4)
        assert m.decode_tokens >= 1
        assert m.prefill_time > 0 and m.decode_time > 0
        assert m.prefill_tokens_per_sec > 0
        assert m.decode_tokens_per_sec > 0
        assert "prefill" in m.summary() and "decode" in m.summary()


class TestSubmitCapacity:
    """Regression: `submit` must validate against the *real* cache row
    count. The old check (`len(prompt) >= max_len`) rejected prompts
    the rounded-up cache could hold — a length-L prompt prefills L rows
    and samples its first token straight off the prefill logits, so
    L == rows is admissible; `_commit_token` then caps generation at
    rows - L + 1 tokens (a request generating m tokens writes only
    L + m - 1 rows)."""

    def _engine(self, max_len, **kw):
        cfg, model, params = _model(
            EnergonConfig(impl="mpmrf_block", pruning_ratio=2.0,
                          decode_key_block=16, min_prune_layer=1)
        )
        return cfg, ServeLoop(model, params, eos_token=cfg.vocab_size - 1,
                              max_len=max_len, **kw)

    def test_full_row_prompt_accepted_and_drains(self):
        cfg, engine = self._engine(max_len=60, batch_slots=1,
                                   prefill_chunk=16)
        rows = engine.max_len
        assert rows == 64  # rounded up to whole decode blocks
        rng = np.random.default_rng(0)
        # the old check rejected anything >= 60; every length up to the
        # real row count must be admissible and produce ≥ 1 token
        for uid, L in enumerate((60, 63, rows)):
            engine.submit(Request(
                uid=uid,
                prompt=rng.integers(1, cfg.vocab_size - 1, size=L).tolist(),
                max_new_tokens=8,
            ))
        done = engine.run_until_drained()
        assert len(done) == 3
        for r in done:
            L = len(r.prompt)
            assert 1 <= len(r.tokens_out) <= min(8, rows - L + 1)

    def test_oversized_prompt_rejected(self):
        cfg, engine = self._engine(max_len=60, batch_slots=1)
        with pytest.raises(ValueError, match="does not fit"):
            engine.submit(Request(uid=0, prompt=[1] * (engine.max_len + 1)))

    def test_generation_never_writes_past_last_row(self):
        """A near-full prompt with a large max_new_tokens budget must be
        clamped so decode writes stay inside the cache (the engine's
        sentinel value == rows; writing *at* rows would be dropped and
        the stream would silently corrupt)."""
        cfg, engine = self._engine(max_len=32, batch_slots=1,
                                   prefill_chunk=8)
        rows = engine.max_len
        prompt = list(range(1, rows - 1))  # rows-2 tokens
        engine.submit(Request(uid=0, prompt=prompt, max_new_tokens=50))
        done = engine.run_until_drained()
        assert len(done) == 1
        # limit = rows - L + 1 = 3
        assert len(done[0].tokens_out) <= 3


class TestFilterCacheCrossoverGate:
    """The context-length crossover gate (DESIGN.md §3): below the
    threshold the resident filter planes cost more HBM traffic than
    they save, so short caches must not allocate them at all — the
    decode step's HLO is then byte-identical to the fresh-requantize
    engine. The gate acts at plane *allocation*; every consumer keys on
    plane presence, so one switch covers decode, prefill and paged."""

    def _model(self, **energon_kw):
        return _model(EnergonConfig(
            impl="mpmrf_block", pruning_ratio=2.0, query_block=8,
            key_block=16, decode_key_block=16, min_prune_layer=1,
            **energon_kw,
        ))

    def test_auto_threshold_dispatch_both_sides(self):
        from repro.core import FILTER_CACHE_AUTO_MIN_LEN

        cfg, model, _ = self._model()
        below = model.init_cache(1, FILTER_CACHE_AUTO_MIN_LEN // 2)
        at = model.init_cache(1, FILTER_CACHE_AUTO_MIN_LEN)
        assert "k_codes" not in below and "k_scale" not in below
        assert "k_codes" in at and "k_scale" in at

    def test_custom_threshold_honoured(self):
        cfg, model, _ = self._model(filter_cache_min_len=256)
        assert "k_codes" not in model.init_cache(1, 128)
        assert "k_codes" in model.init_cache(1, 256)
        # 0 pins the gate open at any length
        _, model0, _ = self._model(filter_cache_min_len=0)
        assert "k_codes" in model0.init_cache(1, 32)

    def test_paged_pool_gated_by_capacity(self):
        from repro.core import FILTER_CACHE_AUTO_MIN_LEN

        cfg, model, _ = self._model()
        bk = cfg.energon.decode_key_block
        small = model.init_paged_cache(8)           # 128 rows
        big = model.init_paged_cache(
            FILTER_CACHE_AUTO_MIN_LEN // bk)        # threshold rows
        assert "k_codes" not in small
        assert "k_codes" in big

    def test_filter_cache_off_overrides_threshold(self):
        cfg, model, _ = self._model(filter_cache=False,
                                    filter_cache_min_len=0)
        assert "k_codes" not in model.init_cache(1, 2048)

    def test_streams_identical_gated_vs_pinned_open(self):
        """Selection off fresh quantization ≡ selection off resident
        planes (the PR 2 invariant), so gating the planes away must not
        change a single sampled token."""
        def run(**kw):
            cfg, model, params = self._model(**kw)
            engine = ServeLoop(model, params, batch_slots=2, max_len=96,
                               eos_token=cfg.vocab_size - 1,
                               prefill_chunk=8)
            rng = np.random.default_rng(7)
            for uid in range(4):
                engine.submit(Request(
                    uid=uid,
                    prompt=rng.integers(
                        1, cfg.vocab_size - 1,
                        size=int(rng.integers(4, 30))).tolist(),
                    max_new_tokens=10,
                    temperature=0.8 if uid % 2 else 0.0,
                ))
            done = engine.run_until_drained()
            return {r.uid: r.tokens_out for r in done}, engine.cache

        gated_toks, gated_cache = run()               # auto: no planes
        pinned_toks, pinned_cache = run(filter_cache_min_len=0)
        assert "k_codes" not in gated_cache
        assert "k_codes" in pinned_cache
        assert gated_toks == pinned_toks
