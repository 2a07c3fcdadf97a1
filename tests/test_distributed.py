"""Distribution-layer tests. Multi-device cases run in a subprocess so
the 8 fake CPU devices never leak into the rest of the suite."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str) -> dict:
    """Run `body` under 8 fake devices; it must print one JSON line."""
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestShardingRules:
    def test_param_specs_cover_all_archs(self):
        from repro.configs.registry import ARCH_NAMES, get_smoke_config
        from repro.distributed import sharding as shd
        from repro.models import LMModel

        mesh = make_mesh(
            (1, 1), ("data", "model")
        )
        for arch in ARCH_NAMES:
            model = LMModel(get_smoke_config(arch))
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            specs = shd.param_shardings(shapes, mesh)
            assert len(jax.tree.leaves(specs)) == len(jax.tree.leaves(shapes))

    def test_divisibility_guard(self):
        from repro.distributed import sharding as shd

        mesh = make_mesh(
            (1, 1), ("data", "model")
        )

        class Leaf:
            ndim = 3
            shape = (32, 36, 128)  # 36 heads not divisible by 16

        # synthesize a path ending in 'wq' under 'attn'
        path = tuple(
            jax.tree_util.DictKey(k) for k in ("blocks", "attn", "wq")
        )
        spec = shd.param_pspec(path, Leaf(), mesh)
        assert spec is not None  # no exception; replicates uneven dims


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        result = run_subprocess("""
        from repro.distributed.pipeline import (
            pipeline_forward, split_layers_to_stages)
        mesh = make_mesh((4,), ("pod",))
        L, d = 8, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * d**-0.5
        def stage_fn(params, x):
            y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, params)
            return y
        stages = split_layers_to_stages(ws, 4)
        mb = jax.random.normal(jax.random.PRNGKey(1), (6, 3, d))
        out = pipeline_forward(stage_fn, stages, mb, mesh, axis="pod")
        ref = jax.vmap(lambda x: stage_fn(ws, x))(mb)
        print(json.dumps({"err": float(jnp.max(jnp.abs(out - ref)))}))
        """)
        assert result["err"] < 1e-5


class TestGradientCompression:
    def test_error_feedback_telescopes(self):
        result = run_subprocess("""
        from repro.distributed import compression as comp
        mesh = make_mesh((4,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (128,))
        acc = jnp.zeros_like(g); err = jnp.zeros_like(g)
        for _ in range(25):
            s, (err,) = comp.compressed_psum_shard_map(
                (g,), (err,), mesh, ("data",))
            acc = acc + s[0]
        exact = 25 * 4.0 * g
        drift = float(jnp.max(jnp.abs(acc - exact)) / jnp.max(jnp.abs(exact)))
        one, _ = comp.compressed_psum_shard_map(
            (g,), (jnp.zeros_like(g),), mesh, ("data",))
        one_err = float(jnp.max(jnp.abs(one[0] - 4*g)) / jnp.max(jnp.abs(4*g)))
        print(json.dumps({"drift": drift, "one_err": one_err}))
        """)
        assert result["one_err"] < 0.02          # single-step int8 error
        assert result["drift"] < result["one_err"]  # feedback telescopes

    def test_compress_roundtrip_bounds(self):
        from repro.distributed import compression as comp

        g = jnp.asarray(np.random.default_rng(0).normal(size=(256,)),
                        jnp.float32)
        codes, scale, err = comp.compress(g, jnp.zeros_like(g))
        assert codes.dtype == jnp.int8
        recon = comp.decompress(codes, scale)
        np.testing.assert_allclose(
            np.asarray(recon + err), np.asarray(g), atol=1e-6
        )


class TestShardedTrainStep:
    def test_sharded_equals_single_device(self):
        """Loss from the mesh-sharded train step must match the
        unsharded step bit-for-bit-ish (same math, different layout)."""
        result = run_subprocess("""
        from repro.configs.registry import get_smoke_config
        from repro.models import LMModel
        from repro.optim import adamw
        from repro.distributed import sharding as shd
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_smoke_config("phi3-mini-3.8b")
        model = LMModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(0, 256, (8, 64)), jnp.int32),
            "targets": jnp.asarray(rng.integers(0, 256, (8, 64)), jnp.int32),
        }
        loss_ref = float(model.loss(params, batch)[0])

        mesh = make_mesh((4, 2), ("data", "model"))
        shd.set_active_mesh(mesh)
        p_shard = shd.param_shardings(params, mesh)
        b_shard = shd.batch_shardings(batch, mesh)
        p_dev = jax.device_put(params, p_shard)
        b_dev = jax.device_put(batch, b_shard)
        loss_sharded = float(jax.jit(
            lambda p, b: model.loss(p, b)[0],
            in_shardings=(p_shard, b_shard),
        )(p_dev, b_dev))
        shd.set_active_mesh(None)
        print(json.dumps({"ref": loss_ref, "sharded": loss_sharded}))
        """)
        assert result["sharded"] == pytest.approx(result["ref"], rel=2e-3)

    def test_sharded_moe_equals_reference(self):
        result = run_subprocess("""
        from repro.models import moe as M
        from repro.distributed import sharding as shd
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = M.MoEConfig(num_experts=8, experts_per_token=2, d_model=32,
                          d_ff=16, capacity_factor=8.0)
        p = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        ref, _ = M._apply_moe_reference(p, x, cfg)
        shd.set_active_mesh(mesh)
        out, _ = jax.jit(lambda p, x: M.apply_moe(p, x, cfg))(p, x)
        shd.set_active_mesh(None)
        print(json.dumps({"err": float(jnp.max(jnp.abs(out - ref)))}))
        """)
        assert result["err"] < 1e-5


class TestElastic:
    def test_reshard_roundtrip(self):
        from repro.distributed import elastic

        params = {"w": np.random.default_rng(0).normal(size=(8, 4)).astype(
            np.float32)}
        mesh = make_mesh(
            (1, 1), ("data", "model")
        )
        dev = elastic.reshard_params(params, mesh)
        back = elastic.gather_params(dev)
        np.testing.assert_array_equal(back["w"], params["w"])
        assert elastic.mesh_fingerprint(mesh) == "data=1xmodel=1"


class TestPagedCacheSharding:
    def test_pool_pspecs(self):
        """Page pools carry no batch axis: KV heads shard over 'model'
        when divisible; per-page scales follow; block tables replicate
        (they ride `inputs`, not the cache pytree)."""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.distributed import sharding as shd
        from repro.models import LMModel

        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = ModelConfig(
            name="paged-shard", family="dense", num_layers=2, d_model=32,
            num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
            vocab_size=64, dtype="float32", remat="none",
            energon=EnergonConfig(impl="mpmrf_block", decode_key_block=16,
                                  filter_cache_min_len=0),
        )
        model = LMModel(cfg)
        shapes = jax.eval_shape(lambda: model.init_paged_cache(8))
        specs = shd.paged_cache_shardings(shapes, mesh, 16)
        for key in ("k", "v", "k_codes"):
            assert specs[key].spec[1] == "model", (key, specs[key].spec)
        assert specs["k_scale"].spec[1] == "model"

    def test_row_shard_must_be_page_aligned(self):
        """With KV heads indivisible by the model axis, the page-row
        axis may shard over 'model' only when the shard boundary lands
        on a page edge — a page split across devices would break the
        scalar-prefetch page streaming."""
        from repro.distributed import sharding as shd

        class FakeMesh:
            axis_names = ("data", "model")
            shape = {"data": 1, "model": 2}

        class Leaf:
            ndim = 4
            dtype = jnp.float32

        mesh = FakeMesh()
        path = (jax.tree_util.DictKey("k"),)
        heads_win = Leaf()
        heads_win.shape = (2, 4, 8 * 16, 8)   # KV=4 % 2 == 0 → heads
        assert shd.paged_pool_pspec(path, heads_win, mesh, 16)[1] == "model"

        aligned = Leaf()
        aligned.shape = (2, 3, 8 * 16, 8)     # KV=3; (128/2) % 16 == 0
        spec = shd.paged_pool_pspec(path, aligned, mesh, 16)
        assert spec[1] is None and spec[2] == "model"

        misaligned = Leaf()
        misaligned.shape = (2, 3, 3 * 16, 8)  # rows=48; 48/2=24 % 16 != 0
        spec = shd.paged_pool_pspec(path, misaligned, mesh, 16)
        # misaligned shard boundary ⇒ the pool replicates instead
        assert spec[1] is None and spec[2] is None

    def test_prefix_sharing_leaves_pool_pspec_unchanged(self):
        """Prefix sharing lives entirely in the host-side block tables
        (which replicate as `inputs`, aliased entries or not): the pool
        pspec derivation takes only shapes, so a sharing engine's cache
        shards exactly like a non-sharing one."""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.distributed import sharding as shd
        from repro.models import LMModel

        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = ModelConfig(
            name="paged-shard-share", family="dense", num_layers=2,
            d_model=32, num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
            vocab_size=64, dtype="float32", remat="none",
            energon=EnergonConfig(impl="mpmrf_block", decode_key_block=16,
                                  filter_cache_min_len=0),
        )
        model = LMModel(cfg)
        shapes = jax.eval_shape(lambda: model.init_paged_cache(8))
        specs = shd.paged_cache_shardings(shapes, mesh, 16)
        # no per-page refcount/trie state ever reaches the device tree
        assert set(shapes.keys()) == {"k", "v", "k_codes", "k_scale"}
        for key, leaf in shapes.items():
            respec = shd.paged_pool_pspec(
                (jax.tree_util.DictKey(key),), leaf, mesh, 16
            )
            assert specs[key].spec == respec

    def test_paged_sharded_step_runs_with_aliased_tables(self):
        """A block table whose slots alias the *same* physical pages
        (the prefix-sharing attach) lowers and runs through the sharded
        serve step unchanged — sharing is invisible to the device."""
        result = run_subprocess("""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.distributed import sharding as shd
        from repro.models import LMModel
        from repro.runtime import make_serve_step
        cfg = ModelConfig(
            name="mesh-paged-alias", family="dense", num_layers=2,
            d_model=32, num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
            vocab_size=64, dtype="float32", remat="none",
            energon=EnergonConfig(impl="mpmrf_block", pruning_ratio=2.0,
                                  query_block=8, key_block=16,
                                  decode_key_block=16, min_prune_layer=1))
        model = LMModel(cfg)
        mesh = make_mesh((2, 2), ("data", "model"))
        with mesh:
            shd.set_active_mesh(mesh)
            step = make_serve_step(model, mesh, num_pages=8)
            params = model.init(jax.random.PRNGKey(0))
            cache = model.init_paged_cache(8)
            # slot 1 aliases slot 0's prefix pages (0, 1); its own
            # tail diverges to private pages (4, 5)
            bt = jnp.asarray([[0, 1, 2, 3], [0, 1, 4, 5]], jnp.int32)
            inputs = {"tokens": jnp.asarray([[3], [5]], jnp.int32),
                      "active": jnp.asarray([True, True]),
                      "block_table": bt}
            logits, cache = step(
                params, cache,
                jax.tree.map(lambda a: a, inputs),
                jnp.asarray([40, 36], jnp.int32))
            shd.set_active_mesh(None)
        print(json.dumps({
            "shape": list(logits.shape),
            "kv_spec": str(cache["k"].sharding.spec),
            "finite": bool(jnp.all(jnp.isfinite(logits))),
        }))
        """)
        assert result["shape"] == [2, 1, 64]
        assert result["finite"]

    def test_paged_sharded_serve_step_runs(self):
        result = run_subprocess("""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.distributed import sharding as shd
        from repro.models import LMModel
        from repro.runtime import make_serve_step
        cfg = ModelConfig(
            name="mesh-paged", family="dense", num_layers=2, d_model=32,
            num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
            vocab_size=64, dtype="float32", remat="none",
            energon=EnergonConfig(impl="mpmrf_block", pruning_ratio=2.0,
                                  query_block=8, key_block=16,
                                  decode_key_block=16, min_prune_layer=1))
        model = LMModel(cfg)
        mesh = make_mesh((2, 2), ("data", "model"))
        with mesh:
            shd.set_active_mesh(mesh)
            step = make_serve_step(model, mesh, num_pages=8)
            params = model.init(jax.random.PRNGKey(0))
            cache = model.init_paged_cache(8)
            bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
            inputs = {"tokens": jnp.asarray([[3], [5]], jnp.int32),
                      "active": jnp.asarray([True, True]),
                      "block_table": bt}
            logits, cache = step(
                params, cache, inputs, jnp.zeros((2,), jnp.int32))
            shd.set_active_mesh(None)
        print(json.dumps({
            "shape": list(logits.shape),
            "kv_spec": str(cache["k"].sharding.spec),
            "finite": bool(jnp.all(jnp.isfinite(logits))),
        }))
        """)
        assert result["shape"] == [2, 1, 64]
        assert result["finite"]
        assert "model" in result["kv_spec"]


class TestFusedPrefillSharded:
    def test_sharded_serve_drains_with_fused_prefill(self):
        """The fused Pallas prefill path (impl="pallas", planes pinned
        resident) must drain a sharded paged engine and produce the
        same greedy streams as the XLA block path — the prefill kernels
        read the same pool leaves `paged_pool_pspec` routes (`k_codes`
        KV-head-sharded, `k_scale` following, tables replicated), so
        engaging them must not disturb the sharded serve step."""
        result = run_subprocess("""
        from repro.configs.base import ModelConfig
        from repro.core import EnergonConfig
        from repro.distributed import sharding as shd
        from repro.models import LMModel
        from repro.runtime import Request, ServeLoop

        def drain(impl):
            cfg = ModelConfig(
                name=f"mesh-fused-prefill-{impl}", family="dense",
                num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                head_dim=8, d_ff=64, vocab_size=64, dtype="float32",
                remat="none",
                energon=EnergonConfig(impl=impl, pruning_ratio=2.0,
                                      query_block=8, key_block=16,
                                      decode_key_block=16,
                                      min_prune_layer=1,
                                      filter_cache_min_len=0))
            model = LMModel(cfg)
            params = model.init(jax.random.PRNGKey(0))
            engine = ServeLoop(model, params, batch_slots=2, max_len=64,
                               eos_token=cfg.vocab_size - 1,
                               prefill_chunk=16, paged=True, num_pages=10)
            rng = np.random.default_rng(3)
            for uid, L in enumerate((24, 40, 9)):
                engine.submit(Request(
                    uid=uid,
                    prompt=rng.integers(1, 63, size=L).tolist(),
                    max_new_tokens=6))
            done = engine.run_until_drained()
            return {r.uid: list(r.tokens_out) for r in done}

        mesh = make_mesh((2, 2), ("data", "model"))
        with mesh:
            shd.set_active_mesh(mesh)
            fused = drain("pallas")
            xla = drain("mpmrf_block")
            shd.set_active_mesh(None)
        print(json.dumps({
            "completed": len(fused),
            "identical": fused == xla,
        }))
        """)
        assert result["completed"] == 3
        assert result["identical"]


_SERVE_HELPERS = """
from repro.configs.base import ModelConfig
from repro.core import EnergonConfig
from repro.models import LMModel
from repro.runtime import ReplicatedServeLoop, Request, ServeLoop

def build(impl="pallas"):
    cfg = ModelConfig(
        name=f"mesh-serve-{impl}", family="dense", num_layers=2,
        d_model=32, num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
        vocab_size=64, dtype="float32", remat="none",
        energon=EnergonConfig(impl=impl, pruning_ratio=2.0,
                              query_block=8, key_block=8,
                              decode_key_block=8, min_prune_layer=1,
                              filter_cache_min_len=0))
    model = LMModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))

def trace():
    rng = np.random.default_rng(1)
    reqs = [(u, rng.integers(1, 63, size=int(L)).tolist(),
             0.7 if u % 3 == 0 else 0.0)
            for u, L in enumerate((12, 24, 6, 40, 17, 9, 30, 21))]
    shared = rng.integers(1, 63, size=16).tolist()
    reqs += [(100 + i, shared + rng.integers(1, 63, size=4).tolist(), 0.0)
             for i in range(2)]
    return reqs

def drain(engine):
    for u, prompt, temp in trace():
        engine.submit(Request(uid=u, prompt=list(prompt),
                              max_new_tokens=6, temperature=temp))
    engine.run_until_drained()
    return {str(r.uid): list(r.tokens_out) for r in engine.completed}
"""


class TestMeshServeBitIdentity:
    def test_tp_mesh_streams_bit_identical(self):
        """A lone engine on a TP mesh (head-sharded pools, shard_map
        fused kernels, all-gathered outputs) must stream bit-identically
        to the single-device paged run — greedy *and* stochastic, with
        prefix sharing on, and both ample and preempting pools. The
        preempted mesh run must also equal the ample single-device run
        (preempted ≡ ample composes with sharded ≡ unsharded)."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        model, params = build("pallas")
        kw = dict(batch_slots=4, max_len=64, rng=jax.random.PRNGKey(7))
        mesh = make_mesh((1, 2), ("data", "model"))
        ref = drain(ServeLoop(model, params, **kw))
        tp = drain(ServeLoop(model, params, mesh=mesh, **kw))
        ref_pre = drain(ServeLoop(model, params, num_pages=12, **kw))
        tp_pre_eng = ServeLoop(model, params, mesh=mesh, num_pages=12,
                               **kw)
        tp_pre = drain(tp_pre_eng)
        print(json.dumps({
            "tp_eq_single": tp == ref,
            "tp_preempt_eq_single_preempt": tp_pre == ref_pre,
            "preempted_eq_ample_on_mesh": tp_pre == ref,
            "preemptions": tp_pre_eng.metrics.preemptions,
        }))
        """))
        assert result["tp_eq_single"]
        assert result["tp_preempt_eq_single_preempt"]
        assert result["preempted_eq_ample_on_mesh"]
        assert result["preemptions"] > 0  # the contract was exercised

    def test_tp_steps_reduce_no_partial_sums(self):
        """Weights are replicated, so no contraction may be split over
        the model axis: an all-reduce of partial matmul sums rounds
        differently from the one-device contraction (on a v5e in bf16
        it flipped greedy tokens). The only all-reduce allowed is the
        vocab-sharded embedding lookup's, which adds exact zeros."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        import re
        from repro.distributed import sharding as shd
        model, params = build("pallas")
        mesh = make_mesh((1, 2), ("data", "model"))
        e = ServeLoop(model, params, mesh=mesh, batch_slots=4,
                      max_len=64, prefill_chunk=8)
        table = e._device_block_table()
        shd.set_active_mesh(mesh)
        texts = [
            e.step_fn.lower(e.params, e.cache, {
                "tokens": jnp.zeros((4, 1), jnp.int32),
                "active": jnp.zeros((4,), bool), "block_table": table,
            }, e.cache_index).compile().as_text(),
            e.prefill_fn.lower(e.params, e.cache, {
                "tokens": jnp.zeros((4, 8), jnp.int32),
                "positions": jnp.full((4, 8), e.max_len, jnp.int32),
                "block_table": table,
            }, e.cache_index).compile().as_text(),
        ]
        reduces = [l for t in texts for l in t.splitlines()
                   if re.search(r" all-reduce(-start)?\\(", l)]
        print(json.dumps({
            "reduces": len(reduces),
            "from_contractions": [l.strip()[:200] for l in reduces
                                  if "dot_general" in l],
        }))
        """))
        assert result["reduces"] > 0   # the embedding lookup's psum
        assert result["from_contractions"] == []

    def test_shared_equals_unshared_on_tp_mesh(self):
        """Prefix sharing must stay invisible to outputs under the
        sharded pools: shared ≡ unshared streams on a TP mesh, with
        sharing actually engaged (hits > 0)."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        model, params = build("pallas")
        # 2 slots + 3 prefix families: later family members admit only
        # after an earlier one prefilled and registered its pages
        def shared_trace():
            tok = lambda fam, j: (fam * 97 + j * 31) % 61 + 1
            return [(u, [tok(u % 3, j) for j in range(40)]
                        + [tok(u % 3 + 5, u * 17 + j)
                           for j in range((u * 7) % 13)],
                     0.8 if u % 2 else 0.0)
                    for u in range(6)]
        def drain2(engine):
            for u, prompt, temp in shared_trace():
                engine.submit(Request(uid=u, prompt=list(prompt),
                                      max_new_tokens=6,
                                      temperature=temp))
            engine.run_until_drained()
            return {str(r.uid): list(r.tokens_out)
                    for r in engine.completed}
        # num_pages > slots*max_blocks: headroom so finished requests'
        # registered pages survive as cached (the default exactly-full
        # pool evicts them before the next family member admits)
        kw = dict(batch_slots=2, max_len=64, prefill_chunk=8,
                  num_pages=32, rng=jax.random.PRNGKey(7))
        mesh = make_mesh((1, 2), ("data", "model"))
        shared_eng = ServeLoop(model, params, mesh=mesh,
                               prefix_sharing=True, **kw)
        shared = drain2(shared_eng)
        unshared = drain2(ServeLoop(model, params, mesh=mesh,
                                    prefix_sharing=False, **kw))
        print(json.dumps({
            "identical": shared == unshared,
            "hits": shared_eng.metrics.prefix_hits,
            "skipped": shared_eng.metrics.prefill_tokens_skipped,
        }))
        """))
        assert result["identical"]
        assert result["hits"] > 0
        assert result["skipped"] > 0

    def test_lone_engine_rejects_data_axis(self):
        """One engine = one replica: a lone ServeLoop must refuse a
        mesh with data > 1 (batch-sharding a lone engine's slots over
        'data' changes XLA's local reduction shapes and would break
        bit-identity); ReplicatedServeLoop is the way to span it."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        model, params = build("mpmrf_block")
        mesh = make_mesh((2, 2), ("data", "model"))
        try:
            ServeLoop(model, params, batch_slots=2, max_len=64,
                      mesh=mesh)
            msg = ""
        except ValueError as e:
            msg = str(e)
        print(json.dumps({"msg": msg}))
        """))
        assert "ReplicatedServeLoop" in result["msg"]


class TestReplicatedServe:
    def test_replica_streams_placement_invariant(self):
        """RNG streams fold from the shared base key by uid, so a
        request's tokens cannot depend on which replica ran it: the
        same trace through 1 (single engine), 2×TP2 and 4×TP1 replica
        layouts must produce bit-identical streams — while the
        placements themselves genuinely differ between layouts."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        model, params = build("mpmrf_block")
        kw = dict(batch_slots=4, max_len=64, rng=jax.random.PRNGKey(7))
        ref = drain(ServeLoop(model, params, **kw))
        r2 = ReplicatedServeLoop(
            model, params,
            mesh=make_mesh((2, 2), ("data", "model")), **kw)
        s2 = drain(r2)
        r4 = ReplicatedServeLoop(
            model, params,
            mesh=make_mesh((4, 1), ("data", "model")), **kw)
        s4 = drain(r4)
        print(json.dumps({
            "two_eq_single": s2 == ref,
            "four_eq_single": s4 == ref,
            "placements_differ": r2.placement != r4.placement,
            "spread2": len(set(r2.placement.values())),
            "spread4": len(set(r4.placement.values())),
        }))
        """))
        assert result["two_eq_single"]
        assert result["four_eq_single"]
        assert result["placements_differ"]  # invariance is non-vacuous
        assert result["spread2"] == 2       # both replicas saw work
        assert result["spread4"] >= 3

    def test_merged_metrics_and_registry(self):
        """Cross-replica accounting: counters sum, peak pages take the
        per-replica max (disjoint pools — a sum would fabricate memory
        pressure), and the merged registry carries both the namespaced
        per-replica series and the stripped aggregates."""
        result = run_subprocess(_SERVE_HELPERS + textwrap.dedent("""
        model, params = build("mpmrf_block")
        loop = ReplicatedServeLoop(
            model, params,
            mesh=make_mesh((2, 2), ("data", "model")),
            batch_slots=4, max_len=64, rng=jax.random.PRNGKey(7))
        drain(loop)
        m = loop.merged_metrics()
        per = [e.metrics for e in loop.engines]
        reg = loop.merged_registry()
        names = reg.names()
        print(json.dumps({
            "decode_sum_ok": m.decode_tokens == sum(
                x.decode_tokens for x in per),
            "peak_is_max": m.peak_pages_in_use == max(
                x.peak_pages_in_use for x in per),
            "peak_not_sum": m.peak_pages_in_use < sum(
                x.peak_pages_in_use for x in per),
            "has_ns": any(n.startswith("replica1/serve_")
                          for n in names),
            "has_agg": "serve_decode_tokens" in names,
            "agg_ok": reg.counter("serve_decode_tokens").value
                == m.decode_tokens,
            "agg_peak_ok": reg.gauge("serve_peak_pages_in_use").value
                == m.peak_pages_in_use,
        }))
        """))
        assert all(result.values()), result


class TestReplicaPlacementHost:
    """Host-side placement + metrics-merge units (no devices needed)."""

    def test_replica_home_stable_and_spread(self):
        from repro.runtime import replica_home

        homes = [replica_home(u, 4) for u in range(256)]
        assert homes == [replica_home(u, 4) for u in range(256)]
        counts = [homes.count(r) for r in range(4)]
        # the multiplicative hash must not starve a replica
        assert min(counts) > 0.15 * len(homes) / 2, counts

    def test_registry_merge_semantics(self):
        from repro.observability.metrics import (
            MetricsRegistry, strip_replica_prefix,
        )

        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("replica0/serve_x").value = 3
        b.counter("replica1/serve_x").value = 4
        a.gauge("replica0/serve_peak").set(7)
        b.gauge("replica1/serve_peak").set(5)
        a.histogram("replica0/serve_h", (1.0, 2.0)).observe(0.5)
        b.histogram("replica1/serve_h", (1.0, 2.0)).observe(1.5)

        merged = MetricsRegistry()
        for src in (a, b):
            merged.merge(src)
            merged.merge(src, rename=lambda n: (
                strip_replica_prefix(n)
                if strip_replica_prefix(n) != n else None
            ))
        assert merged.counter("serve_x").value == 7
        assert merged.counter("replica0/serve_x").value == 3
        assert merged.gauge("serve_peak").value == 7  # max, not 12
        h = merged.histogram("serve_h", (1.0, 2.0))
        assert h.count == 2 and h.counts[0] == 1 and h.counts[1] == 1
        assert h.min == 0.5 and h.max == 1.5

    def test_merge_rejects_mismatched_bounds(self):
        from repro.observability.metrics import MetricsRegistry

        a, b = MetricsRegistry(), MetricsRegistry()
        a.histogram("h", (1.0, 2.0)).observe(0.5)
        b.histogram("h", (1.0, 3.0)).observe(0.5)
        with pytest.raises(ValueError):
            a.merge(b)

    def test_engine_metrics_replica_namespace(self):
        from repro.observability.metrics import MetricsRegistry
        from repro.runtime import EngineMetrics

        reg = MetricsRegistry()
        m0 = EngineMetrics(registry=reg, replica=0)
        m1 = EngineMetrics(registry=reg, replica=1)
        plain = EngineMetrics(registry=reg)
        m0.decode_tokens += 5
        m1.decode_tokens += 7
        plain.decode_tokens += 1
        assert reg.counter("replica0/serve_decode_tokens").value == 5
        assert reg.counter("replica1/serve_decode_tokens").value == 7
        assert reg.counter("serve_decode_tokens").value == 1
