"""Pallas kernel sweeps: shapes × dtypes, assert_allclose vs ref.py.

Kernels run in interpret mode on CPU (the kernel body executes in
Python) — this validates the exact numerical contract the TPU build
compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qlib
from repro.kernels import mpmrf_filter as fk
from repro.kernels import ops, ref


def _mk(shape, seed, dtype=jnp.float32):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), dtype
    )


def _identity_pages(x, block):
    """An unpaged ``[bh, n, d]`` cache as the kernels address it:
    ``[bh·n/block, block, d]`` pages plus the identity ``[bh, n/block]``
    block table (row r's block j is page r·n/block + j)."""
    bh, n, d = x.shape
    nb = n // block
    table = jnp.arange(bh * nb, dtype=jnp.int32).reshape(bh, nb)
    return x.reshape(bh * nb, block, d), table


SHAPES = [
    # (bh, n_q, n_k, d, block_q, block_k)
    (1, 128, 128, 32, 64, 64),
    (2, 256, 256, 64, 128, 128),
    (3, 384, 256, 64, 128, 64),
    (1, 256, 512, 128, 128, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


class TestFlashAttention:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_ref(self, shape, dtype, causal):
        bh, n_q, n_k, d, bq, bk = shape
        q = _mk((bh, n_q, d), 1, dtype)
        k = _mk((bh, n_k, d), 2, dtype)
        v = _mk((bh, n_k, d), 3, dtype)
        out = ops.flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True
        )
        expected = ref.flash_attention_ref(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            out.astype(jnp.float32), expected.astype(jnp.float32), atol=tol
        )

    def test_q_offset_decode_chunk(self):
        q = _mk((1, 128, 32), 4)
        k = _mk((1, 256, 32), 5)
        v = _mk((1, 256, 32), 6)
        out = ops.flash_attention(
            q, k, v, causal=True, q_offset=128, block_q=64, block_k=64,
            interpret=True,
        )
        expected = ref.flash_attention_ref(q, k, v, causal=True, q_offset=128)
        np.testing.assert_allclose(out, expected, atol=1e-5)


class TestMPMRFFilterKernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_ref(self, shape, causal):
        bh, n_q, n_k, d, bq, bk = shape
        q = _mk((bh, n_q, d), 7)
        k = _mk((bh, n_k, d), 8)
        q16 = qlib.quantize_int16(q, axis=-1)
        k16 = qlib.quantize_int16(k, axis=(-2, -1))
        qp = q16.bit_plane(4).astype(jnp.int8)
        km = k16.bit_plane(2).astype(jnp.int8)
        kr = k16.lsb_remainder(2, 4).astype(jnp.int8)
        s0, s1 = fk.mpmrf_filter_scores(
            qp, km, kr, q16.scale, shift=2, query_block=bq, key_block=bk,
            causal=causal, interpret=True,
        )
        r0, r1 = ref.mpmrf_filter_ref(
            qp, km, kr, q16.scale, query_block=bq, key_block=bk, shift=2,
            causal=causal,
        )
        np.testing.assert_allclose(s0, r0, rtol=1e-6)
        np.testing.assert_allclose(s1, r1, rtol=1e-6)


class TestBlockSparseAttention:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_ref_with_selected_blocks(self, shape, dtype):
        bh, n_q, n_k, d, bq, bk = shape
        q = _mk((bh, n_q, d), 9, dtype)
        k = _mk((bh, n_k, d), 10, dtype)
        v = _mk((bh, n_k, d), 11, dtype)
        idx, val = ops.mpmrf_select_blocks(
            q.astype(jnp.float32), k.astype(jnp.float32),
            block_budget=max(1, (n_k // bk) // 2),
            query_block=bq, key_block=bk, causal=True, interpret=True,
        )
        out = ops.block_sparse_attention(
            q, k, v, idx, val, query_block=bq, key_block=bk, causal=True,
            interpret=True,
        )
        expected = ref.block_sparse_attention_ref(
            q, k, v, idx, val, query_block=bq, key_block=bk, causal=True
        )
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            out.astype(jnp.float32), expected.astype(jnp.float32), atol=tol
        )

    def test_full_budget_equals_flash(self):
        bh, n, d = 2, 256, 64
        q, k, v = (_mk((bh, n, d), s) for s in (12, 13, 14))
        n_b = n // 64
        idx = jnp.broadcast_to(
            jnp.arange(n_b), (bh, n_b, n_b)
        ).astype(jnp.int32)
        val = jnp.ones_like(idx)
        out = ops.block_sparse_attention(
            q, k, v, idx, val, query_block=64, key_block=64, causal=True,
            interpret=True,
        )
        expected = ops.flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True
        )
        np.testing.assert_allclose(out, expected, atol=1e-5)


class TestFusedDecodeKernel:
    """Fused decode pipeline off the resident filter cache (l = 1)."""

    def _setup(self, B=2, H=2, G=4, n=128, d=16, bk=16, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, H, G, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, n, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, n, d)), jnp.float32)
        cl = jnp.asarray(rng.integers(1, n + 1, size=B), jnp.int32)
        codes, scales = qlib.quantize_int16_blocks(k, bk)
        return q, k, v, cl, codes, scales, bk

    @pytest.mark.parametrize("seed", [0, 5])
    def test_filter_scores_vs_ref(self, seed):
        q, k, _, cl, codes, scales, bk = self._setup(seed=seed)
        from repro.kernels import mpmrf_decode as dk

        B, H, G, d = q.shape
        n = k.shape[-2]
        bh = B * H
        q16 = qlib.quantize_int16(q, axis=-1)
        qp = q16.bit_plane(4)
        qs = q16.scale
        cl_bh = jnp.repeat(cl, H)
        # per-slot caches: S = B code slots, identity table per slot
        table = jnp.tile(jnp.arange(n // bk, dtype=jnp.int32), (B, 1))
        s0, s1 = dk.mpmrf_paged_filter_scores(
            qp.reshape(B, H * G, d), qs.reshape(B, H * G, 1), codes,
            scales, table, cl, round_bits=(2, 4), key_block=bk,
            interpret=True,
        )
        r0, r1 = ref.mpmrf_decode_filter_ref(
            qp.reshape(bh, G, d), qs.reshape(bh, G, 1),
            codes.reshape(bh, n, d), scales.reshape(bh, n // bk),
            cl_bh, round_bits=(2, 4), key_block=bk,
        )
        np.testing.assert_allclose(
            np.asarray(s0).reshape(bh, -1), np.asarray(r0), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(s1).reshape(bh, -1), np.asarray(r1), rtol=1e-6
        )

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_fused_matches_xla_decode_path(self, ratio):
        """Selection glue is shared, so fused == XLA block decode up to
        flash-vs-flat softmax rounding."""
        from repro.core import energon_decode_attention, EnergonConfig

        q, k, v, cl, codes, scales, bk = self._setup(seed=3)
        fc = {"codes": codes, "scale": scales}
        cfg_x = EnergonConfig(impl="mpmrf_block", pruning_ratio=ratio,
                              decode_key_block=bk, min_prune_layer=0)
        cfg_p = EnergonConfig(impl="pallas", pruning_ratio=ratio,
                              decode_key_block=bk, min_prune_layer=0)
        out_x = energon_decode_attention(
            q, k, v, cl, cfg_x, layer_index=5, filter_cache=fc
        )
        out_p = energon_decode_attention(
            q, k, v, cl, cfg_p, layer_index=5, filter_cache=fc
        )
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_x), atol=1e-5
        )

    def test_reuse_partial_false_falls_back_to_xla_path(self):
        """The fused kernel hard-codes Fig. 7 result reuse; the
        independent-rescore variant must dispatch to the XLA block path
        and therefore match it exactly."""
        from repro.core import energon_decode_attention, EnergonConfig

        q, k, v, cl, codes, scales, bk = self._setup(seed=11)
        fc = {"codes": codes, "scale": scales}
        outs = []
        for impl in ("pallas", "mpmrf_block"):
            cfg = EnergonConfig(impl=impl, pruning_ratio=2.0,
                                decode_key_block=bk, min_prune_layer=0,
                                reuse_partial=False)
            outs.append(energon_decode_attention(
                q, k, v, cl, cfg, layer_index=5, filter_cache=fc
            ))
        np.testing.assert_array_equal(
            np.asarray(outs[0]), np.asarray(outs[1])
        )

    def test_keep_all_budget_is_exactly_dense(self):
        from repro.core import energon_decode_attention, EnergonConfig

        q, k, v, cl, codes, scales, bk = self._setup(seed=7)
        fc = {"codes": codes, "scale": scales}
        cfg_p = EnergonConfig(impl="pallas", pruning_ratio=1.0,
                              decode_key_block=bk, min_prune_layer=0)
        out_p = energon_decode_attention(
            q, k, v, cl, cfg_p, layer_index=5, filter_cache=fc
        )
        dense = energon_decode_attention(
            q, k, v, cl, EnergonConfig(impl="dense"), layer_index=5
        )
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(dense), atol=1e-5
        )

    def test_gather_kernel_masks_invalid_slots_and_padding(self):
        """A survivor table with padded slots and a short cache_length
        must equal the XLA gather oracle."""
        from repro.core import sparse_attention as spa
        from repro.kernels import mpmrf_decode as dk

        q, k, v, cl, _, _, bk = self._setup(seed=9)
        B, H, G, d = q.shape
        n = k.shape[-2]
        bh = B * H
        rng = np.random.default_rng(2)
        budget = 4
        n_live = np.maximum((np.asarray(cl) + bk - 1) // bk, 1)
        idx = np.zeros((B, H, budget), np.int32)
        val = np.zeros((B, H, budget), np.int32)
        for b in range(B):
            for h in range(H):
                m = int(min(budget, n_live[b]))
                idx[b, h, :m] = rng.choice(n_live[b], size=m, replace=False)
                val[b, h, :m] = 1
        k_pages, table = _identity_pages(k.reshape(bh, n, d), bk)
        v_pages, _ = _identity_pages(v.reshape(bh, n, d), bk)
        out_k = dk.paged_decode_gather_attention(
            q.reshape(bh, G, d), k_pages, v_pages,
            jnp.asarray(idx).reshape(bh, budget),
            jnp.asarray(val).reshape(bh, budget),
            table, jnp.repeat(cl, H), key_block=bk, interpret=True,
        ).reshape(B, H, G, d)
        out_ref = spa.decode_block_gather_attention(
            q, k, v,
            jnp.asarray(idx)[:, :, None, :], jnp.asarray(val)[:, :, None, :],
            cl, bk,
        )
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_ref), atol=1e-5
        )


class TestEndToEndEnergonKernelPipeline:
    def test_matches_xla_chunked_selection_semantics(self):
        """Kernel pipeline (FU kernel + AU kernel) vs the XLA chunked
        implementation: same selection rule ⇒ allclose outputs."""
        from repro.core import chunked_attention as chk

        bh, n, d = 2, 512, 64
        q, k, v = (_mk((bh, n, d), s) for s in (20, 21, 22))
        out_kernel = ops.energon_block_attention(q, k, v, 2, 128, 128, True)
        q4 = q.reshape(1, bh, n, d)
        out_xla = chk.energon_block_attention_chunked(
            q4, k.reshape(1, bh, n, d), v.reshape(1, bh, n, d),
            pruning_ratio=2.0, causal=True,
        ).reshape(bh, n, d)
        np.testing.assert_allclose(out_kernel, out_xla, atol=1e-4)

    def test_gradients_flow(self):
        bh, n, d = 1, 256, 32
        q, k, v = (_mk((bh, n, d), s) for s in (30, 31, 32))
        grads = jax.grad(
            lambda q, k, v: ops.energon_block_attention(
                q, k, v, 2, 64, 64, True
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for g in grads:
            assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.abs(grads[2]).sum()) > 0  # dV nonzero


class TestFusedDecodePaged:
    """Paged fused decode: the kernels address the page pool through
    the block table (two-level scalar-prefetch indirection) and must
    stay bit-identical to the unpaged fused path on the same logical
    contents."""

    def _setup(self, B=2, H=2, G=4, mb=6, d=16, bk=16, seed=0,
               num_pages=15):
        rng = np.random.default_rng(seed)
        n = mb * bk
        q = _mk((B, H, G, d), seed)
        k = _mk((B, H, n, d), seed + 1)
        v = _mk((B, H, n, d), seed + 2)
        cl = jnp.asarray(rng.integers(1, n + 1, size=B), jnp.int32)
        # unpaged padding rows are zeros; pool pages are zeroed on alloc
        mask = (jnp.arange(n)[None, :] < cl[:, None])[:, None, :, None]
        k, v = k * mask, v * mask
        codes, scales = qlib.quantize_int16_blocks(k, bk)
        # disjoint shuffled page assignment per slot
        perm = rng.permutation(num_pages)
        tables = np.asarray(
            [perm[b * mb:(b + 1) * mb] for b in range(B)], np.int32
        )
        kp = np.zeros((H, num_pages * bk, d), np.float32)
        vp = np.zeros_like(kp)
        cp = np.zeros((H, num_pages * bk, d), np.int16)
        sp = np.zeros((H, num_pages), np.float32)
        for b in range(B):
            for j in range(mb):
                pg = int(tables[b, j])
                sl = slice(pg * bk, (pg + 1) * bk)
                src = slice(j * bk, (j + 1) * bk)
                kp[:, sl] = np.asarray(k[b, :, src])
                vp[:, sl] = np.asarray(v[b, :, src])
                cp[:, sl] = np.asarray(codes[b, :, src])
                sp[:, pg] = np.asarray(scales[b, :, j])
        pool = dict(
            k=jnp.asarray(kp), v=jnp.asarray(vp),
            codes=jnp.asarray(cp), scale=jnp.asarray(sp),
        )
        return q, k, v, cl, codes, scales, tables, pool, bk

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_paged_fused_bit_identical_to_unpaged_fused(self, ratio):
        q, k, v, cl, codes, scales, tables, pool, bk = self._setup()
        import math

        mb = tables.shape[-1]
        budget = max(1, math.ceil(mb / ratio))
        from repro.core import decode_live_budget

        lb = decode_live_budget(cl, bk, ratio)
        ref_out = ops.fused_decode_attention(
            q, k, v, codes, scales, cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        out = ops.fused_paged_decode_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))

    def test_paged_filter_scores_vs_unpaged_kernel(self, seed=4):
        from repro.kernels import mpmrf_decode as dk

        q, k, _, cl, codes, scales, tables, pool, bk = self._setup(seed=seed)
        B, H, G, d = q.shape
        n = k.shape[-2]
        mb = n // bk
        q16 = qlib.quantize_int16(q, axis=-1)
        qp = q16.bit_plane(4).reshape(B, H * G, d)
        qs = q16.scale.reshape(B, H * G, 1)
        identity = jnp.tile(jnp.arange(mb, dtype=jnp.int32), (B, 1))
        r0, r1 = dk.mpmrf_paged_filter_scores(
            qp, qs, codes, scales, identity, cl,
            round_bits=(2, 4), key_block=bk, interpret=True,
        )
        s0, s1 = dk.mpmrf_paged_filter_scores(
            qp, qs, pool["codes"][None], pool["scale"][None],
            jnp.asarray(tables), cl,
            round_bits=(2, 4), key_block=bk, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(s0))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(s1))

    @pytest.mark.parametrize("name,kv,g,d", [
        ("phi3-mini", 32, 1, 96), ("qwen3-14b-gqa", 8, 5, 128),
    ])
    def test_filter_scores_bit_identical_to_xla_scoring(self, name, kv, g, d):
        """One grid step per (slot, page) for all KV heads, at the
        serving widths: slots share prefix pages, lengths are ragged (an
        idle slot, one ending mid-page, one full), and table entries past
        a slot's length alias arbitrary pages — the scores equal the XLA
        scoring path's bit for bit."""
        from repro.core import filtering as flt
        from repro.kernels import mpmrf_decode as dk
        from repro.runtime import paged_cache as pgc

        B, bk, mb, num_pages = 4, 64, 5, 14
        rng = np.random.default_rng(kv)
        codes = jnp.asarray(
            rng.integers(-2 ** 15, 2 ** 15, size=(kv, num_pages * bk, d)),
            jnp.int16,
        )
        scale = jnp.asarray(rng.uniform(1e-3, 1e-1, (kv, num_pages)),
                            jnp.float32)
        # pages 0-1 hold a prefix every slot shares; the rest are owned
        tables = rng.integers(0, num_pages, size=(B, mb)).astype(np.int32)
        tables[:, :2] = [0, 1]
        tables[1, 2:] = [2, 3, 4]
        tables[2, 2:] = [5, 6, 7]
        tables[3, 2:4] = [8, 9]
        lengths = jnp.asarray([0, mb * bk, 2 * bk + 17, 3 * bk], jnp.int32)
        q16 = qlib.quantize_int16(_mk((B, kv, g, d), kv + 1), axis=-1)

        s0, s1 = dk.mpmrf_paged_filter_scores(
            q16.bit_plane(4).reshape(B, kv * g, d),
            q16.scale.reshape(B, kv * g, 1),
            codes[None], scale[None], jnp.asarray(tables), lengths,
            round_bits=(2, 4), key_block=bk, interpret=True,
        )

        table = jnp.asarray(tables)
        k16 = qlib.blockwise_quantized_view(
            pgc.gather_logical_rows(codes, table, bk),
            pgc.gather_logical_scales(scale, table), bk,
        )
        valid = (jnp.arange(mb * bk)[None, :] < lengths[:, None])
        valid = jnp.broadcast_to(valid[:, None, None, :], (B, kv, g, mb * bk))
        cfg = flt.MPMRFConfig(round_bits=(2, 4), alphas=(0.0, 0.0),
                              granularity="block", key_block=bk)
        xla = [
            flt.pool_block_scores(sc, g, bk, valid)[0][:, :, 0, :]
            for sc in flt._round_score_planes(q16, k16, cfg)
        ]
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(xla[0]))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(xla[1]))
        assert np.all(np.asarray(s1)[0] == -1e30)          # the idle slot
        assert np.all(np.asarray(s1)[2, :, 3:] == -1e30)   # past mid-page

    def test_paged_gather_vs_xla_paged_oracle(self):
        from repro.core import sparse_attention as spa
        from repro.kernels import mpmrf_decode as dk

        q, k, v, cl, _, _, tables, pool, bk = self._setup(seed=8)
        B, H, G, d = q.shape
        mb = tables.shape[-1]
        bh = B * H
        num_pages = pool["scale"].shape[-1]
        rng = np.random.default_rng(1)
        budget = 3
        n_live = np.maximum((np.asarray(cl) + bk - 1) // bk, 1)
        idx = np.zeros((B, H, budget), np.int32)
        val = np.zeros((B, H, budget), np.int32)
        for b in range(B):
            for h in range(H):
                m = int(min(budget, n_live[b]))
                idx[b, h, :m] = rng.choice(n_live[b], size=m, replace=False)
                val[b, h, :m] = 1
        head_off = (jnp.arange(H, dtype=jnp.int32) * num_pages)
        bt_bh = (
            jnp.asarray(tables)[:, None, :] + head_off[None, :, None]
        ).reshape(bh, mb)
        out_k = dk.paged_decode_gather_attention(
            q.reshape(bh, G, d),
            pool["k"].reshape(H * num_pages, bk, d),
            pool["v"].reshape(H * num_pages, bk, d),
            jnp.asarray(idx).reshape(bh, budget),
            jnp.asarray(val).reshape(bh, budget),
            bt_bh, jnp.repeat(cl, H),
            key_block=bk, interpret=True,
        ).reshape(B, H, G, d)
        out_ref = spa.paged_decode_block_gather_attention(
            q, pool["k"], pool["v"],
            jnp.asarray(idx)[:, :, None, :], jnp.asarray(val)[:, :, None, :],
            jnp.asarray(tables), cl, bk,
        )
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_ref), atol=1e-5
        )


class TestFusedDecodePagedEdges:
    """Fused paged-kernel edge cases: a slot with exactly one mapped
    page, a live budget exceeding the mapped pages, and survivor
    tables referencing the highest physical page index — all must stay
    bit-identical to the unpaged fused path / XLA oracle."""

    def _pool_of(self, k, v, codes, scales, tables, num_pages, bk):
        B, H, n, d = k.shape
        mb = n // bk
        kp = np.zeros((H, num_pages * bk, d), np.float32)
        vp = np.zeros_like(kp)
        cp = np.zeros((H, num_pages * bk, d), np.int16)
        sp = np.zeros((H, num_pages), np.float32)
        for b in range(B):
            for j in range(mb):
                pg = int(tables[b, j])
                sl = slice(pg * bk, (pg + 1) * bk)
                src = slice(j * bk, (j + 1) * bk)
                kp[:, sl] = np.asarray(k[b, :, src])
                vp[:, sl] = np.asarray(v[b, :, src])
                cp[:, sl] = np.asarray(codes[b, :, src])
                sp[:, pg] = np.asarray(scales[b, :, j])
        return dict(k=jnp.asarray(kp), v=jnp.asarray(vp),
                    codes=jnp.asarray(cp), scale=jnp.asarray(sp))

    def _operands(self, cl_rows, tables, num_pages, B=2, H=2, G=4,
                  mb=4, d=16, bk=16, seed=11):
        n = mb * bk
        q = _mk((B, H, G, d), seed)
        k = _mk((B, H, n, d), seed + 1)
        v = _mk((B, H, n, d), seed + 2)
        cl = jnp.asarray(cl_rows, jnp.int32)
        mask = (jnp.arange(n)[None, :] < cl[:, None])[:, None, :, None]
        k, v = k * mask, v * mask
        codes, scales = qlib.quantize_int16_blocks(k, bk)
        pool = self._pool_of(k, v, codes, scales, tables, num_pages, bk)
        return q, k, v, cl, codes, scales, pool, bk

    def test_exactly_one_mapped_page(self):
        """cache_length within the first block: each slot maps exactly
        one real page; every other table entry is the compacted-table
        filler (page 0) and must never influence the output."""
        import math
        from repro.core import decode_live_budget

        num_pages, mb, bk = 9, 4, 16
        # slot 0's single real page is NOT page 0; fillers alias 0
        tables = np.array(
            [[7, 0, 0, 0], [3, 0, 0, 0]], np.int32
        )
        q, k, v, cl, codes, scales, pool, bk = self._operands(
            [5, 16], tables, num_pages
        )
        budget = max(1, math.ceil(mb / 2.0))
        lb = decode_live_budget(cl, bk, 2.0)
        assert int(jnp.max(lb)) == 1          # exactly one live block
        ref_out = ops.fused_decode_attention(
            q, k, v, codes, scales, cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        out = ops.fused_paged_decode_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))

    def test_live_budget_exceeding_mapped_pages(self):
        """A per-slot live budget larger than the slot's mapped pages:
        the surplus survivor entries carry dead valid bits and the
        masked gather must not read past the mapped region (unmapped
        entries alias page 0 — a foreign slot's live page)."""
        import math

        num_pages, mb, bk = 9, 4, 16
        tables = np.array(
            [[4, 5, 0, 0], [1, 2, 6, 0]], np.int32
        )
        q, k, v, cl, codes, scales, pool, bk = self._operands(
            [20, 40], tables, num_pages
        )
        budget = mb                            # gather width = all blocks
        lb = jnp.asarray([mb, mb], jnp.int32)  # ≫ mapped pages (2 / 3)
        ref_out = ops.fused_decode_attention(
            q, k, v, codes, scales, cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        out = ops.fused_paged_decode_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))

    def test_survivor_table_hits_highest_physical_page(self):
        """A survivor entry whose block table maps the pool's *last*
        physical page: the composed index map must address the final
        page without clamping or wrapping."""
        from repro.core import sparse_attention as spa
        from repro.kernels import mpmrf_decode as dk

        num_pages, mb, bk = 9, 4, 16
        last = num_pages - 1
        tables = np.array(
            [[2, last, 1, 0], [last, 4, 5, 6]], np.int32
        )
        n = mb * bk
        q, k, v, cl, _, _, pool, bk = self._operands(
            [n, n], tables, num_pages
        )
        B, H, G, d = q.shape
        bh = B * H
        # survivors pick exactly the logical blocks mapped to `last`
        idx = np.array([[1, 0], [0, 2]], np.int32)[:, None, :].repeat(
            H, axis=1
        )
        val = np.ones_like(idx)
        budget = idx.shape[-1]
        head_off = jnp.arange(H, dtype=jnp.int32) * num_pages
        bt_bh = (
            jnp.asarray(tables)[:, None, :] + head_off[None, :, None]
        ).reshape(bh, mb)
        out_k = dk.paged_decode_gather_attention(
            q.reshape(bh, G, d),
            pool["k"].reshape(H * num_pages, bk, d),
            pool["v"].reshape(H * num_pages, bk, d),
            jnp.asarray(idx).reshape(bh, budget),
            jnp.asarray(val).reshape(bh, budget),
            bt_bh, jnp.repeat(cl, H),
            key_block=bk, interpret=True,
        ).reshape(B, H, G, d)
        out_ref = spa.paged_decode_block_gather_attention(
            q, pool["k"], pool["v"],
            jnp.asarray(idx)[:, :, None, :],
            jnp.asarray(val)[:, :, None, :],
            jnp.asarray(tables), cl, bk,
        )
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_ref), atol=1e-5
        )
        # and end-to-end through the fused dispatcher with the same
        # tables: bit-identical to the unpaged fused path
        import math
        from repro.core import decode_live_budget

        codes, scales = qlib.quantize_int16_blocks(k, bk)
        budget = max(1, math.ceil(mb / 2.0))
        lb = decode_live_budget(cl, bk, 2.0)
        ref_out = ops.fused_decode_attention(
            q, k, v, codes, scales, cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        out = ops.fused_paged_decode_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), cl,
            key_block=bk, block_budget=budget, live_budget=lb,
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))


class TestFusedPrefillKernel:
    """Fused prefill pipeline off the resident filter cache: the filter
    kernel's in-register plane derivation must match the jnp oracle,
    and — the prefix-sharing contract — its selection must be
    bit-identical to the XLA ``mpmrf_block_select`` consuming the same
    resident planes."""

    def _setup(self, B=2, H=2, n_q=16, n_k=128, d=16, bq=8, bk=16,
               seed=0, offsets=(40, 8), ragged=4):
        """Chunk rows at per-slot offsets; slot 1's tail rows are
        position sentinels (≥ n_k) — a ragged final chunk."""
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, H, n_q, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, n_k, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, n_k, d)), jnp.float32)
        pos = np.zeros((B, n_q), np.int32)
        pos[0] = offsets[0] + np.arange(n_q)
        pos[1] = offsets[1] + np.arange(n_q)
        pos[1, n_q - ragged:] = n_k  # sentinels
        qpos = jnp.asarray(pos)
        # padded cache: rows past each slot's extent hold zeros
        extent = jnp.max(jnp.where(qpos < n_k, qpos + 1, 0), axis=1)
        mask = (jnp.arange(n_k)[None, :] < extent[:, None])[:, None, :, None]
        k, v = k * mask, v * mask
        codes, scales = qlib.quantize_int16_blocks(k, bk)
        return q, k, v, qpos, codes, scales, bq, bk

    def _diag_blocks(self, qpos, bq, bk, n_k):
        from repro.core.energon_attention import _prefill_diag_blocks

        return _prefill_diag_blocks(qpos, bq, bk, n_k)

    @pytest.mark.parametrize("seed", [0, 5])
    def test_filter_scores_vs_ref(self, seed):
        from repro.kernels import mpmrf_prefill as pk

        q, k, _, qpos, codes, scales, bq, bk = self._setup(seed=seed)
        B, H, n_q, d = q.shape
        n_k = k.shape[-2]
        bh = B * H
        q16 = qlib.quantize_int16(q, axis=-1)
        qp = q16.bit_plane(4).reshape(bh, n_q, d)
        qs = q16.scale.reshape(bh, n_q, 1)
        qpos_bh = jnp.repeat(qpos, H, axis=0)
        ks_row = jnp.repeat(scales, bk, axis=-1).reshape(bh, n_k)
        pages, table = _identity_pages(codes.reshape(bh, n_k, d), bk)
        s0, s1 = pk.mpmrf_paged_prefill_filter_scores(
            qp, qs, qpos_bh, pages, scales.reshape(bh * (n_k // bk), 1),
            table, round_bits=(2, 4), query_block=bq, key_block=bk,
            interpret=True,
        )
        r0, r1 = ref.mpmrf_prefill_filter_ref(
            qp, qs, qpos_bh, codes.reshape(bh, n_k, d), ks_row,
            round_bits=(2, 4), query_block=bq, key_block=bk,
        )
        np.testing.assert_allclose(np.asarray(s0), np.asarray(r0),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(r1),
                                   rtol=1e-6)

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_selection_bit_identical_to_xla(self, ratio):
        """Kernel scores + shared selection helper ≡
        ``mpmrf_block_select`` on the resident planes — exact survivor
        tables, incl. the sentinel rows of the ragged tail chunk."""
        import math

        from repro.core import filtering as flt
        from repro.kernels import mpmrf_prefill as pk

        q, k, _, qpos, codes, scales, bq, bk = self._setup(seed=3)
        B, H, n_q, d = q.shape
        n_k = k.shape[-2]
        bh = B * H
        n_kb = n_k // bk
        budget = max(1, math.ceil(n_kb / ratio))
        db = self._diag_blocks(qpos, bq, bk, n_k)

        kpos = jnp.arange(n_k)[None, None, :]
        valid = jnp.broadcast_to(
            jnp.logical_and(kpos <= qpos[:, :, None],
                            qpos[:, :, None] < n_k)[:, None],
            (B, H, n_q, n_k),
        )
        mcfg = flt.MPMRFConfig(
            round_bits=(2, 4), alphas=(0.0, 0.0), granularity="block",
            query_block=bq, key_block=bk, block_budget=budget,
            keep_first=True, keep_diagonal=True, reuse_partial=True,
        )
        res = flt.mpmrf_block_select(
            q, k, mcfg, valid=valid, diag_blocks=db,
            k_quant=qlib.blockwise_quantized_view(codes, scales, bk),
        )

        q16 = qlib.quantize_int16(q, axis=-1)
        pages, table = _identity_pages(codes.reshape(bh, n_k, d), bk)
        s0, s1 = pk.mpmrf_paged_prefill_filter_scores(
            q16.bit_plane(4).reshape(bh, n_q, d),
            q16.scale.reshape(bh, n_q, 1),
            jnp.repeat(qpos, H, axis=0),
            pages, scales.reshape(bh * n_kb, 1), table,
            round_bits=(2, 4), query_block=bq, key_block=bk,
            interpret=True,
        )
        idx, val, _ = ops._fused_prefill_select(
            s0, s1, round_bits=(2, 4), alphas=(0.0, 0.0),
            query_block=bq, key_block=bk, block_budget=budget,
            keep_all=False, keep_first=True, keep_diagonal=True,
            diag_blocks=db, heads=H,
        )
        np.testing.assert_array_equal(
            np.asarray(res.block_indices).reshape(bh, n_q // bq, -1),
            np.asarray(idx),
        )
        np.testing.assert_array_equal(
            np.asarray(res.block_valid).reshape(bh, n_q // bq, -1),
            np.asarray(val),
        )

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_fused_matches_xla_prefill_path(self, ratio):
        """Dispatch-level parity: selection glue is shared, so fused ==
        XLA block prefill up to flash-vs-flat softmax rounding."""
        from repro.core import EnergonConfig, energon_attention

        q, k, v, qpos, codes, scales, bq, bk = self._setup(seed=7)
        fc = {"codes": codes, "scale": scales}
        kw = dict(pruning_ratio=ratio, query_block=bq, key_block=bk,
                  decode_key_block=bk, min_prune_layer=0)
        out_x = energon_attention(
            q, k, v, EnergonConfig(impl="mpmrf_block", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
        )
        out_p = energon_attention(
            q, k, v, EnergonConfig(impl="pallas", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
        )
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_x), atol=1e-5
        )

    @pytest.mark.parametrize("dead", ["tail_block", "idle_slot"])
    def test_dead_query_blocks_read_as_masked(self, dead):
        """Query blocks with no live row are skipped by the filter and
        gather grids; they must still read what a masked block reads
        (NEG_INF planes, zero rows), and the live blocks beside them
        must be unchanged — against the jnp oracle and the XLA path."""
        from repro.core import EnergonConfig, energon_attention
        from repro.kernels import mpmrf_prefill as pk

        q, k, v, qpos, codes, scales, bq, bk = self._setup(
            seed=11, ragged=12 if dead == "tail_block" else 4
        )
        B, H, n_q, d = q.shape
        n_k = k.shape[-2]
        if dead == "idle_slot":
            qpos = qpos.at[0].set(n_k)
        bh = B * H
        live = np.asarray(qpos).reshape(B, n_q // bq, bq).min(-1) < n_k
        assert not live.all() and live.any()

        q16 = qlib.quantize_int16(q, axis=-1)
        qp = q16.bit_plane(4).reshape(bh, n_q, d)
        qs = q16.scale.reshape(bh, n_q, 1)
        qpos_bh = jnp.repeat(qpos, H, axis=0)
        pages, table = _identity_pages(codes.reshape(bh, n_k, d), bk)
        s0, s1 = pk.mpmrf_paged_prefill_filter_scores(
            qp, qs, qpos_bh, pages, scales.reshape(bh * (n_k // bk), 1),
            table, round_bits=(2, 4), query_block=bq, key_block=bk,
            interpret=True,
        )
        r0, r1 = ref.mpmrf_prefill_filter_ref(
            qp, qs, qpos_bh, codes.reshape(bh, n_k, d),
            jnp.repeat(scales, bk, axis=-1).reshape(bh, n_k),
            round_bits=(2, 4), query_block=bq, key_block=bk,
        )
        np.testing.assert_allclose(np.asarray(s0), np.asarray(r0),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(r1),
                                   rtol=1e-6)
        dead_bh = ~np.repeat(live, H, axis=0)
        assert (np.asarray(s1)[dead_bh] <= pk.NEG_INF / 2).all()

        fc = {"codes": codes, "scale": scales}
        kw = dict(pruning_ratio=2.0, query_block=bq, key_block=bk,
                  decode_key_block=bk, min_prune_layer=0)
        out_x = energon_attention(
            q, k, v, EnergonConfig(impl="mpmrf_block", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
        )
        out_p = energon_attention(
            q, k, v, EnergonConfig(impl="pallas", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
        )
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_x), atol=1e-5
        )
        rows_dead = np.repeat(~live, bq, axis=1)          # [B, n_q]
        assert (np.asarray(out_p).transpose(0, 2, 1, 3)[rows_dead] == 0).all()

    def test_key_tile_of_two_scale_blocks_matches_xla(self, monkeypatch):
        """Unpaged cache whose key tiles span two resident scale blocks:
        the fused path pools block pairs and must select exactly as the
        XLA path does on the same planes."""
        from repro.core import EnergonConfig, energon_attention

        q, k, v, qpos, codes, scales, bq, bk = self._setup(seed=15)
        fc = {"codes": codes, "scale": scales}
        calls = []
        fused = ops.fused_prefill_attention
        monkeypatch.setattr(
            ops, "fused_prefill_attention",
            lambda *a, **kw: calls.append(kw["key_block"]) or fused(*a, **kw),
        )
        kw = dict(pruning_ratio=2.0, query_block=bq, key_block=2 * bk,
                  decode_key_block=bk, min_prune_layer=0)
        out_x, st_x = energon_attention(
            q, k, v, EnergonConfig(impl="mpmrf_block", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
            telemetry=True,
        )
        out_p, st_p = energon_attention(
            q, k, v, EnergonConfig(impl="pallas", **kw),
            q_positions=qpos, layer_index=5, filter_cache=fc,
            telemetry=True,
        )
        assert calls == [2 * bk]             # only the pallas impl fused
        np.testing.assert_array_equal(np.asarray(st_p), np.asarray(st_x))
        np.testing.assert_allclose(
            np.asarray(out_p), np.asarray(out_x), atol=1e-5
        )

    def test_keep_all_budget_is_exactly_dense(self):
        """ρ ≤ 1 ⇒ every live block survives: the fused pipeline must
        reproduce dense attention (sentinel rows excluded)."""
        from repro.core import EnergonConfig, energon_attention

        q, k, v, qpos, codes, scales, bq, bk = self._setup(seed=9)
        fc = {"codes": codes, "scale": scales}
        out_p = energon_attention(
            q, k, v,
            EnergonConfig(impl="pallas", pruning_ratio=1.0,
                          query_block=bq, key_block=bk,
                          decode_key_block=bk, min_prune_layer=0),
            q_positions=qpos, layer_index=5, filter_cache=fc,
        )
        dense = energon_attention(
            q, k, v, EnergonConfig(impl="dense"),
            q_positions=qpos, layer_index=5,
        )
        real = np.asarray(qpos < k.shape[-2])[:, None, :, None]
        np.testing.assert_allclose(
            np.asarray(out_p) * real, np.asarray(dense) * real, atol=1e-5
        )

    def test_no_resident_planes_falls_back_to_xla_path(self):
        """Without the filter cache the pallas impl must downgrade to
        the XLA block path (same selection from fresh quantization)."""
        from repro.core import EnergonConfig, energon_attention

        q, k, v, qpos, _, _, bq, bk = self._setup(seed=11)
        outs = []
        for impl in ("pallas", "mpmrf_block"):
            cfg = EnergonConfig(impl=impl, pruning_ratio=2.0,
                                query_block=bq, key_block=bk,
                                decode_key_block=bk, min_prune_layer=0)
            outs.append(energon_attention(
                q, k, v, cfg, q_positions=qpos, layer_index=5
            ))
        np.testing.assert_array_equal(
            np.asarray(outs[0]), np.asarray(outs[1])
        )

    def test_interpret_flag_parity(self):
        """Explicit interpret=True equals the host-default dispatch —
        the CPU fallback runs the same kernel body."""
        import math

        q, k, v, qpos, codes, scales, bq, bk = self._setup(seed=13)
        n_kb = k.shape[-2] // bk
        kw = dict(
            round_bits=(2, 4), alphas=(0.0, 0.0), query_block=bq,
            key_block=bk, filter_block=bk,
            block_budget=max(1, math.ceil(n_kb / 2.0)),
            diag_blocks=self._diag_blocks(qpos, bq, bk, k.shape[-2]),
        )
        out_auto = ops.fused_prefill_attention(
            q, k, v, codes, scales, qpos, **kw
        )
        out_explicit = ops.fused_prefill_attention(
            q, k, v, codes, scales, qpos, interpret=True, **kw
        )
        np.testing.assert_array_equal(
            np.asarray(out_auto), np.asarray(out_explicit)
        )


class TestFusedPrefillPaged:
    """Paged fused prefill: both kernels address the page pool through
    the block table (filter: page-per-key-tile; gather: survivor ∘
    block-table composition) and must stay bit-identical to the
    unpaged fused path on the same logical contents."""

    def _pool_of(self, k, v, codes, scales, tables, num_pages, bk):
        B, H, n, d = k.shape
        mb = n // bk
        kp = np.zeros((H, num_pages * bk, d), np.float32)
        vp = np.zeros_like(kp)
        cp = np.zeros((H, num_pages * bk, d), np.int16)
        sp = np.zeros((H, num_pages), np.float32)
        for b in range(B):
            for j in range(mb):
                pg = int(tables[b, j])
                sl = slice(pg * bk, (pg + 1) * bk)
                src = slice(j * bk, (j + 1) * bk)
                kp[:, sl] = np.asarray(k[b, :, src])
                vp[:, sl] = np.asarray(v[b, :, src])
                cp[:, sl] = np.asarray(codes[b, :, src])
                sp[:, pg] = np.asarray(scales[b, :, j])
        return dict(k=jnp.asarray(kp), v=jnp.asarray(vp),
                    codes=jnp.asarray(cp), scale=jnp.asarray(sp))

    def _setup(self, B=2, H=2, n_q=16, mb=6, d=16, bq=8, bk=16, seed=0,
               num_pages=15, offsets=(24, 70), ragged=4):
        rng = np.random.default_rng(seed)
        n = mb * bk
        q = _mk((B, H, n_q, d), seed)
        k = _mk((B, H, n, d), seed + 1)
        v = _mk((B, H, n, d), seed + 2)
        pos = np.zeros((B, n_q), np.int32)
        pos[0] = offsets[0] + np.arange(n_q)
        pos[1] = offsets[1] + np.arange(n_q)
        pos[1, n_q - ragged:] = n  # sentinels
        qpos = jnp.asarray(pos)
        extent = jnp.max(jnp.where(qpos < n, qpos + 1, 0), axis=1)
        mask = (jnp.arange(n)[None, :] < extent[:, None])[:, None, :, None]
        k, v = k * mask, v * mask
        codes, scales = qlib.quantize_int16_blocks(k, bk)
        perm = rng.permutation(num_pages)
        tables = np.asarray(
            [perm[b * mb:(b + 1) * mb] for b in range(B)], np.int32
        )
        pool = self._pool_of(k, v, codes, scales, tables, num_pages, bk)
        return q, k, v, qpos, codes, scales, tables, pool, bq, bk

    def _fused_kwargs(self, qpos, bq, bk, n_k, ratio=2.0):
        import math

        from repro.core.energon_attention import _prefill_diag_blocks

        n_kb = n_k // bk
        return dict(
            round_bits=(2, 4), alphas=(0.0, 0.0), query_block=bq,
            key_block=bk,
            block_budget=max(1, math.ceil(n_kb / ratio)),
            diag_blocks=_prefill_diag_blocks(qpos, bq, bk, n_k),
        )

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_paged_fused_bit_identical_to_unpaged_fused(self, ratio):
        q, k, v, qpos, codes, scales, tables, pool, bq, bk = self._setup()
        kw = self._fused_kwargs(qpos, bq, bk, k.shape[-2], ratio)
        ref_out = ops.fused_prefill_attention(
            q, k, v, codes, scales, qpos, filter_block=bk, **kw
        )
        out = ops.fused_paged_prefill_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), qpos, **kw
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))

    def test_paged_filter_scores_vs_unpaged_kernel(self, seed=4):
        from repro.kernels import mpmrf_prefill as pk

        q, k, _, qpos, codes, scales, tables, pool, bq, bk = self._setup(
            seed=seed
        )
        B, H, n_q, d = q.shape
        n = k.shape[-2]
        mb = n // bk
        bh = B * H
        num_pages = pool["scale"].shape[-1]
        q16 = qlib.quantize_int16(q, axis=-1)
        qp = q16.bit_plane(4).reshape(bh, n_q, d)
        qs = q16.scale.reshape(bh, n_q, 1)
        qpos_bh = jnp.repeat(qpos, H, axis=0)
        pages, table = _identity_pages(codes.reshape(bh, n, d), bk)
        r0, r1 = pk.mpmrf_paged_prefill_filter_scores(
            qp, qs, qpos_bh, pages, scales.reshape(bh * mb, 1), table,
            round_bits=(2, 4), query_block=bq, key_block=bk,
            interpret=True,
        )
        head_off = jnp.arange(H, dtype=jnp.int32) * num_pages
        bt_bh = (
            jnp.asarray(tables)[:, None, :] + head_off[None, :, None]
        ).reshape(bh, mb)
        s0, s1 = pk.mpmrf_paged_prefill_filter_scores(
            qp, qs, qpos_bh,
            pool["codes"].reshape(H * num_pages, bk, d),
            pool["scale"].reshape(H * num_pages, 1),
            bt_bh, round_bits=(2, 4), query_block=bq, key_block=bk,
            interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(r0), np.asarray(s0))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(s1))

    def test_paged_dispatch_matches_xla_fallback(self):
        """``energon_paged_prefill_attention``: fused (impl='pallas')
        vs the transient-gather XLA fallback (impl='mpmrf_block') on
        identical pool contents — same selection, allclose outputs."""
        from repro.core import (
            EnergonConfig,
            energon_paged_prefill_attention,
        )

        q, k, v, qpos, codes, scales, tables, pool, bq, bk = self._setup(
            seed=6
        )
        cache = dict(k=pool["k"], v=pool["v"], k_codes=pool["codes"],
                     k_scale=pool["scale"])
        outs = {}
        for impl in ("pallas", "mpmrf_block"):
            cfg = EnergonConfig(impl=impl, pruning_ratio=2.0,
                                query_block=bq, key_block=bk,
                                decode_key_block=bk, min_prune_layer=0,
                                filter_cache_min_len=0)
            outs[impl] = energon_paged_prefill_attention(
                q, cache, jnp.asarray(tables), qpos, cfg, layer_index=5
            )
        np.testing.assert_allclose(
            np.asarray(outs["pallas"]), np.asarray(outs["mpmrf_block"]),
            atol=1e-5,
        )

    @pytest.mark.parametrize("ratio", [2.0, 4.0])
    def test_key_tile_of_two_pages_matches_xla_fallback(
        self, ratio, monkeypatch
    ):
        """Key tiles twice the page size (phi3-mini's 128-row tiles over
        64-row pages): the fused path engages, max-pools page pairs and
        gathers page pairs, and its selection counts equal the XLA
        fallback's exactly."""
        from repro.core import (
            EnergonConfig,
            energon_paged_prefill_attention,
        )

        q, k, v, qpos, codes, scales, tables, pool, bq, bk = self._setup(
            seed=12
        )
        cache = dict(k=pool["k"], v=pool["v"], k_codes=pool["codes"],
                     k_scale=pool["scale"])
        calls = []
        fused = ops.fused_paged_prefill_attention
        monkeypatch.setattr(
            ops, "fused_paged_prefill_attention",
            lambda *a, **kw: calls.append(kw["key_block"]) or fused(*a, **kw),
        )
        outs, stats = {}, {}
        for impl in ("pallas", "mpmrf_block"):
            cfg = EnergonConfig(impl=impl, pruning_ratio=ratio,
                                query_block=bq, key_block=2 * bk,
                                decode_key_block=bk, min_prune_layer=0,
                                filter_cache_min_len=0)
            outs[impl], stats[impl] = energon_paged_prefill_attention(
                q, cache, jnp.asarray(tables), qpos, cfg, layer_index=5,
                telemetry=True,
            )
        assert calls == [2 * bk]             # only the pallas impl fused
        np.testing.assert_array_equal(
            np.asarray(stats["pallas"]), np.asarray(stats["mpmrf_block"])
        )
        np.testing.assert_allclose(
            np.asarray(outs["pallas"]), np.asarray(outs["mpmrf_block"]),
            atol=1e-5,
        )

    def test_single_mapped_page(self):
        """A chunk whose positions all land in logical block 0: every
        other table entry is the compacted-table filler (page 0 — a
        foreign slot's live page) and must never influence the
        output."""
        q, k, v, qpos, codes, scales, _, _, bq, bk = self._setup(
            seed=8, mb=4, num_pages=9, offsets=(0, 2), ragged=10
        )
        # slot 0 writes rows 0..15 (exactly page 0's block);
        # slot 1 rows 2..7 + sentinels — both within one page
        tables = np.array([[7, 0, 0, 0], [3, 0, 0, 0]], np.int32)
        pool = self._pool_of(k, v, codes, scales, tables,
                             num_pages=9, bk=bk)
        kw = self._fused_kwargs(qpos, bq, bk, k.shape[-2])
        ref_out = ops.fused_prefill_attention(
            q, k, v, codes, scales, qpos, filter_block=bk, **kw
        )
        out = ops.fused_paged_prefill_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), qpos, **kw
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))

    def test_survivor_table_hits_highest_physical_page(self):
        """Survivor entries whose block table maps the pool's *last*
        physical page: the composed filter/gather index maps must
        address the final page without clamping or wrapping."""
        num_pages = 9
        last = num_pages - 1
        q, k, v, qpos, codes, scales, _, _, bq, bk = self._setup(
            seed=10, mb=4, num_pages=num_pages, offsets=(47, 30),
            ragged=2,
        )
        tables = np.array([[2, last, 1, 0], [3, 4, 5, 6]], np.int32)
        pool = self._pool_of(k, v, codes, scales, tables,
                             num_pages=num_pages, bk=bk)
        kw = self._fused_kwargs(qpos, bq, bk, k.shape[-2])
        ref_out = ops.fused_prefill_attention(
            q, k, v, codes, scales, qpos, filter_block=bk, **kw
        )
        out = ops.fused_paged_prefill_attention(
            q, pool["k"], pool["v"], pool["codes"], pool["scale"],
            jnp.asarray(tables), qpos, **kw
        )
        np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(out))


class TestPagedPrefillDense:
    """The dense layers' streamed prefill (``paged_prefill_dense``): one
    grid step per (slot, page) for all KV heads, online softmax over
    the slot's pages read in place. It must equal the materialized
    dense ``q_positions`` path to f32 rounding on the same pool."""

    def _setup(self, kv, g, *, C=8, d=16, bk=8, mb=6, num_pages=20,
               seed=0):
        """Four slots over one pool: slots 0 and 1 share their first two
        pages (a prefix); slot 0 writes up to a mid-page extent, slot 1
        has no live row (sentinels only), slot 2 ends mid-page after a
        ragged tail, slot 3 reaches max_len; entries past each slot's
        extent alias arbitrary pages, other slots' live ones included."""
        rng = np.random.default_rng(seed)
        n = mb * bk
        B = 4
        tables = rng.integers(0, num_pages, size=(B, mb)).astype(np.int32)
        tables[:2, :2] = [3, 7]
        tables[0, 2:4] = [11, 2]
        tables[2, :3] = [5, 9, 12]
        tables[3] = [13, 14, 15, 16, 17, 18]
        pos = np.full((B, C), n, np.int32)
        pos[0] = 21 + np.arange(C)                   # extent 29: mid-page
        pos[2, :5] = 14 + np.arange(5)               # ragged tail, extent 19
        pos[3] = n - C + np.arange(C)                # the last row is n - 1
        qpos = jnp.asarray(np.tile(pos, (1, g)))     # folded (g, c) rows
        q = _mk((B, kv, g * C, d), seed + 1)
        k_pool = _mk((kv, num_pages * bk, d), seed + 2)
        v_pool = _mk((kv, num_pages * bk, d), seed + 3)
        return q, k_pool, v_pool, jnp.asarray(tables), qpos, bk

    @pytest.mark.parametrize("kv,g", [(4, 1), (2, 5)], ids=["G1", "G5"])
    def test_matches_materialized_dense_path(self, kv, g):
        from repro.core import EnergonConfig, energon_paged_prefill_attention

        q, k_pool, v_pool, table, qpos, bk = self._setup(kv, g)
        out = ops.dense_paged_prefill_attention(
            q, k_pool, v_pool, table, qpos, page=bk, interpret=True
        )
        # the XLA fallback: zeroed logical views, [n_q, n_k] scores
        cfg = EnergonConfig(impl="mpmrf_block", decode_key_block=bk,
                            min_prune_layer=2)
        ref = energon_paged_prefill_attention(
            q, {"k": k_pool, "v": v_pool}, table, qpos, cfg, layer_index=0
        )
        n = table.shape[-1] * bk
        live = np.broadcast_to(np.asarray(qpos < n)[:, None, :, None],
                               out.shape)
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(ref)[live],
                                   rtol=1e-5, atol=1e-6)
        assert np.all(np.asarray(out)[~live] == 0)    # sentinels read 0
        assert np.all(np.asarray(out)[1] == 0)        # the idle slot

    @pytest.mark.parametrize("kv,g", [(4, 1), (2, 5)], ids=["G1", "G5"])
    def test_paged_dispatch_and_unpaged_cache(self, kv, g, monkeypatch):
        """``impl="pallas"`` with resident planes sends a dense layer's
        chunk to the kernel, and the unpaged cache (each slot's own
        cache, identity table) gives the paged result bit for bit."""
        from repro.core import (
            EnergonConfig,
            energon_attention,
            energon_paged_prefill_attention,
        )
        from repro.runtime import paged_cache as pgc

        q, k_pool, v_pool, table, qpos, bk = self._setup(kv, g, seed=3)
        n = table.shape[-1] * bk
        codes, scale = qlib.quantize_int16_blocks(k_pool, bk)
        cache = {"k": k_pool, "v": v_pool, "k_codes": codes,
                 "k_scale": scale[..., 0]}
        calls = []
        real = ops.dense_paged_prefill_attention
        monkeypatch.setattr(
            ops, "dense_paged_prefill_attention",
            lambda *a, **kw: calls.append(kw["page"]) or real(*a, **kw),
        )
        cfg = EnergonConfig(impl="pallas", query_block=8, key_block=2 * bk,
                            decode_key_block=bk, min_prune_layer=2,
                            filter_cache_min_len=0)
        paged = energon_paged_prefill_attention(
            q, cache, table, qpos, cfg, layer_index=1
        )
        assert calls == [bk]
        extent = jnp.max(jnp.where(qpos < n, qpos + 1, 0), axis=1)
        row_ok = (jnp.arange(n)[None, :] < extent[:, None])[:, None, :, None]
        k_log = pgc.gather_logical_rows(k_pool, table, bk) * row_ok
        v_log = pgc.gather_logical_rows(v_pool, table, bk) * row_ok
        c_log, s_log = qlib.quantize_int16_blocks(k_log, bk)
        unpaged = energon_attention(
            q, k_log, v_log, cfg, layer_index=1, q_positions=qpos,
            filter_cache={"codes": c_log, "scale": s_log[..., 0]},
        )
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(unpaged))

    def test_threshold_no_longer_binds_served_prefill(self):
        """A tiny Qwen3-shaped model (QK-norm, G = 5, 2 dense + 2 pruned
        layers, impl "pallas") serves the same prefill-then-decode
        logits with ``chunk_threshold`` below its dense layers'
        ``G·C·max_len`` as with the default: neither the dense nor the
        fused prefill consults it."""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.models import LMModel

        B, C, max_len, bk = 2, 16, 128, 16
        g = 5

        def serve(threshold):
            kw = {} if threshold is None else {"chunk_threshold": threshold}
            cfg = ModelConfig(
                name="qwen3-tiny", family="dense", num_layers=4,
                d_model=40, num_heads=2 * g, num_kv_heads=2, head_dim=8,
                d_ff=64, vocab_size=64, use_qk_norm=True,
                dtype="float32", remat="none",
                energon=EnergonConfig(
                    impl="pallas", pruning_ratio=2.0, query_block=16,
                    key_block=2 * bk, decode_key_block=bk,
                    min_prune_layer=2, filter_cache_min_len=0, **kw),
            )
            model = LMModel(cfg)
            params = model.init(jax.random.PRNGKey(0))
            mb = max_len // bk
            cache = model.init_paged_cache(B * mb, max_len=max_len)
            table = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
            toks = jax.random.randint(jax.random.PRNGKey(1), (B, C), 1, 63)
            lens = np.array([C, 11])
            pos = np.where(np.arange(C)[None, :] < lens[:, None],
                           np.arange(C)[None, :], max_len)
            logits, cache = jax.jit(model.prefill)(
                params, cache,
                {"tokens": toks, "positions": jnp.asarray(pos, jnp.int32),
                 "block_table": table},
                jnp.zeros((B,), jnp.int32),
            )
            step, _ = jax.jit(model.decode_step)(
                params, cache,
                {"tokens": toks[:, :1], "active": jnp.ones((B,), bool),
                 "block_table": table},
                jnp.asarray(lens, jnp.int32),
            )
            return np.asarray(logits), np.asarray(step)

        assert g * C * max_len > 1024
        low, default = serve(1024), serve(None)
        np.testing.assert_array_equal(low[0], default[0])
        np.testing.assert_array_equal(low[1], default[1])
        assert np.all(np.isfinite(low[1]))
