"""Substrate tests: optimizer, data pipeline, checkpointing, fault
tolerance, HLO cost parser."""

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.analysis.hlo_costs import compute_costs, shape_bytes
from repro.data import PrefetchIterator, TokenDataset
from repro.optim import adamw
from repro.runtime.fault_tolerance import (
    FaultInjector,
    FaultSpec,
    PreemptionHandler,
    RetryPolicy,
    StepFailure,
    StragglerMonitor,
    TransientStepError,
    retry_step,
)


class TestAdamW:
    def _quad(self):
        target = jnp.asarray([1.0, -2.0, 3.0])
        loss = lambda p: jnp.sum((p["w"] - target) ** 2)
        return loss, {"w": jnp.zeros(3)}

    def test_converges_on_quadratic(self):
        loss, p = self._quad()
        cfg = adamw.AdamWConfig(learning_rate=0.1, weight_decay=0.0)
        st = adamw.init(p, cfg)
        for _ in range(200):
            g = jax.grad(loss)(p)
            p, st, _ = adamw.update(g, st, p, cfg)
        assert float(loss(p)) < 1e-2

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((4,), 100.0)}
        clipped, norm = adamw.clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(200.0)
        assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)

    def test_accumulation_equals_full_batch(self):
        """Σµbatch-grads/k == full-batch grad, exactly (linear loss)."""
        w = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 4)),
                              jnp.float32)}
        x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)),
                        jnp.float32)

        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"]) ** 2), {}

        full_loss, full_grads, _ = adamw.accumulate_gradients(
            loss_fn, w, {"x": x}, 1
        )
        acc_loss, acc_grads, _ = adamw.accumulate_gradients(
            loss_fn, w, {"x": x}, 4
        )
        np.testing.assert_allclose(float(full_loss), float(acc_loss),
                                   rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(full_grads["w"]), np.asarray(acc_grads["w"]),
            rtol=1e-5,
        )

    def test_warmup_cosine_shape(self):
        sched = adamw.warmup_cosine(1.0, 10, 100)
        assert float(sched(jnp.asarray(0))) == pytest.approx(0.0)
        assert float(sched(jnp.asarray(10))) == pytest.approx(1.0)
        assert float(sched(jnp.asarray(100))) == pytest.approx(0.1, rel=1e-2)

    def test_factored_matches_dense_direction(self):
        """Factored v preserves the sign/rough magnitude of updates."""
        g = {"w": jnp.asarray(
            np.random.default_rng(2).normal(size=(6, 5)), jnp.float32)}
        p = {"w": jnp.zeros((6, 5))}
        for factored in (False, True):
            cfg = adamw.AdamWConfig(learning_rate=0.1, weight_decay=0.0,
                                    factored_second_moment=factored)
            st = adamw.init(p, cfg)
            newp, _, _ = adamw.update(g, st, p, cfg)
            assert bool(jnp.all(jnp.sign(newp["w"]) == -jnp.sign(g["w"])))


class TestDataPipeline:
    def test_determinism(self):
        ds1 = TokenDataset(256, 32, 8, seed=7, corpus_tokens=5000)
        ds2 = TokenDataset(256, 32, 8, seed=7, corpus_tokens=5000)
        b1, b2 = next(ds1), next(ds2)
        np.testing.assert_array_equal(b1["inputs"], b2["inputs"])

    def test_targets_are_shifted_inputs(self):
        ds = TokenDataset(256, 32, 4, seed=1, corpus_tokens=5000)
        b = ds.batch_at(3)
        # targets[i] == corpus-next-token of inputs[i]
        assert b["inputs"].shape == (4, 32)
        assert b["targets"].shape == (4, 32)
        # verify shift property through the corpus
        np.testing.assert_array_equal(
            b["inputs"][:, 1:], b["targets"][:, :-1]
        )

    def test_shards_are_disjoint_and_cover(self):
        full = TokenDataset(256, 16, 8, seed=3, corpus_tokens=5000)
        shards = [
            TokenDataset(256, 16, 8, seed=3, corpus_tokens=5000,
                         shard_index=i, num_shards=4)
            for i in range(4)
        ]
        b_full = full.batch_at(0)["inputs"]
        b_shards = np.concatenate(
            [s.batch_at(0)["inputs"] for s in shards], axis=0
        )
        np.testing.assert_array_equal(b_full, b_shards)

    def test_state_restore(self):
        ds = TokenDataset(256, 16, 4, seed=5, corpus_tokens=5000)
        for _ in range(5):
            next(ds)
        state = ds.state
        b6 = next(ds)
        ds2 = TokenDataset(256, 16, 4, seed=5, corpus_tokens=5000)
        ds2.restore(state)
        np.testing.assert_array_equal(next(ds2)["inputs"], b6["inputs"])

    def test_prefetch_preserves_order(self):
        ds = TokenDataset(256, 16, 4, seed=9, corpus_tokens=5000)
        ref = [ds.batch_at(i)["inputs"] for i in range(5)]
        it = PrefetchIterator(
            TokenDataset(256, 16, 4, seed=9, corpus_tokens=5000), prefetch=3
        )
        got = [next(it)["inputs"] for _ in range(5)]
        it.close()
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_zipf_corpus_is_learnable(self):
        """Bigram entropy well below unigram entropy ⇒ structure."""
        from repro.data.synthetic import zipf_ngram_corpus

        c = zipf_ngram_corpus(64, 20000, seed=0)
        uni = np.bincount(c, minlength=64) / len(c)
        h_uni = -np.sum(uni[uni > 0] * np.log(uni[uni > 0]))
        # the chain is order-2: condition on (prev, cur) pairs
        pair_counts = {}
        for a, b, n in zip(c[:-2], c[1:-1], c[2:]):
            pair_counts.setdefault((int(a), int(b)), []).append(int(n))
        h_cond = 0.0
        total = len(c) - 2
        for ctx, succs in pair_counts.items():
            p_ctx = len(succs) / total
            dist = np.bincount(succs, minlength=64) / len(succs)
            h_cond += p_ctx * -np.sum(dist[dist > 0] * np.log(dist[dist > 0]))
        assert h_cond < 0.75 * h_uni


class TestCheckpoint:
    def _tree(self, seed=0):
        r = np.random.default_rng(seed)
        return {
            "params": {"w": jnp.asarray(r.normal(size=(4, 4)), jnp.float32)},
            "step": jnp.asarray(seed, jnp.int32),
        }

    def test_roundtrip(self):
        with tempfile.TemporaryDirectory() as d:
            tree = self._tree(3)
            ckpt.save_checkpoint(d, 3, tree)
            res = ckpt.restore_latest(d, jax.tree.map(jnp.zeros_like, tree))
            assert res is not None
            step, restored, manifest = res
            assert step == 3
            np.testing.assert_array_equal(
                restored["params"]["w"], tree["params"]["w"]
            )

    def test_corrupt_checkpoint_falls_back(self):
        with tempfile.TemporaryDirectory() as d:
            ckpt.save_checkpoint(d, 1, self._tree(1))
            ckpt.save_checkpoint(d, 2, self._tree(2))
            # corrupt the newest
            path = ckpt.step_dir(d, 2)
            with open(os.path.join(path, "arrays.npz"), "wb") as f:
                f.write(b"garbage")
            res = ckpt.restore_latest(
                d, jax.tree.map(jnp.zeros_like, self._tree())
            )
            assert res is not None and res[0] == 1

    def test_retention(self):
        with tempfile.TemporaryDirectory() as d:
            for s in range(1, 8):
                ckpt.save_checkpoint(d, s, self._tree(s))
            ckpt.retain(d, keep_last=2, keep_every=3)
            steps = [s for s, _ in ckpt.list_checkpoints(d)]
            assert steps == [3, 6, 7]

    def test_async_checkpointer(self):
        with tempfile.TemporaryDirectory() as d:
            ac = ckpt.AsyncCheckpointer(d, keep_last=2)
            for s in (1, 2, 3):
                ac.save(s, self._tree(s))
            ac.wait()
            steps = [s for s, _ in ckpt.list_checkpoints(d)]
            assert steps[-1] == 3 and len(steps) <= 2

    def test_shape_mismatch_raises(self):
        with tempfile.TemporaryDirectory() as d:
            ckpt.save_checkpoint(d, 1, {"w": jnp.zeros((2, 2))})
            with pytest.raises((ValueError, KeyError)):
                from repro.checkpoint.checkpointer import _unflatten_like
                import numpy as _np
                with _np.load(os.path.join(
                    ckpt.step_dir(d, 1), "arrays.npz"
                )) as z:
                    flat = {k: z[k] for k in z.files}
                _unflatten_like({"w": jnp.zeros((3, 3))}, flat)


class TestFaultTolerance:
    def test_retry_recovers(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientStepError("transient")
            return "ok"

        assert retry_step(flaky, base_delay=0.0) == "ok"
        assert calls["n"] == 3

    def test_retry_exhausts(self):
        def always_fails():
            raise TransientStepError("down")

        with pytest.raises(StepFailure):
            retry_step(always_fails, max_retries=2, base_delay=0.0)

    def test_device_runtime_error_is_not_retried(self):
        """JAX's runtime errors subclass RuntimeError; they surface after
        donation, so the default policy lets them propagate on the first
        attempt instead of retrying against freed buffers."""
        calls = {"n": 0}

        def device_error():
            calls["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            retry_step(device_error, base_delay=0.0)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            retry_step(device_error, policy=RetryPolicy(base_delay=0.0))
        assert calls["n"] == 2

    def test_straggler_detection(self):
        mon = StragglerMonitor(window=20, threshold=3.0)
        for _ in range(15):
            assert not mon.record(0.1)
        assert mon.record(1.0)  # 10x median
        assert mon.median_step_time == pytest.approx(0.1)

    def test_preemption_flag(self):
        h = PreemptionHandler()
        assert not h.should_stop
        h.request_stop()
        assert h.should_stop

    def test_retry_policy_overrides_kwargs(self):
        """A RetryPolicy wins over the loose keyword parameters — the
        shared serving+training configuration object is authoritative."""
        calls = {"n": 0}

        def always_fails():
            calls["n"] += 1
            raise TransientStepError("down")

        with pytest.raises(StepFailure):
            retry_step(
                always_fails, max_retries=9,
                policy=RetryPolicy(max_retries=1, base_delay=0.0),
            )
        assert calls["n"] == 2  # 1 attempt + 1 retry, not 10

    def test_retry_policy_non_retriable_propagates(self):
        def raises_value_error():
            raise ValueError("not transient")

        with pytest.raises(ValueError):
            retry_step(
                raises_value_error,
                policy=RetryPolicy(max_retries=3, base_delay=0.0,
                                   retriable=(RuntimeError,)),
            )

    def test_retry_on_retry_callback(self):
        seen = []

        def flaky():
            if len(seen) < 2:
                raise TransientStepError("transient")
            return "ok"

        out = retry_step(
            flaky, base_delay=0.0,
            on_retry=lambda attempt, exc: seen.append(attempt),
        )
        assert out == "ok"
        assert seen == [0, 1]


class TestFaultInjector:
    """The seeded chaos source must be a pure function of its seed:
    same seed ⇒ same fault schedule, different seed ⇒ (almost surely)
    different, zero rates ⇒ no draws at all."""

    _SPEC = FaultSpec(
        alloc_failure=0.3, step_exception=0.3, step_exception_burst=2,
        nan_logits=0.2, nan_prefill=0.2, delay=0.1, preempt_storm=0.2,
    )

    def _schedule(self, seed):
        inj = FaultInjector(seed=seed, spec=self._SPEC)
        out = []
        for i in range(50):
            out.append((
                inj.alloc_failure(),
                inj.step_fault(fresh=True),
                tuple(inj.poison_decode([1, 2, 3])),
                tuple(inj.poison_prefill([4, 5])),
                inj.step_delay(),
                inj.preempt_storm(3),
            ))
        return out, dict(inj.counts)

    def test_same_seed_replays_exactly(self):
        s1, c1 = self._schedule(42)
        s2, c2 = self._schedule(42)
        assert s1 == s2
        assert c1 == c2
        assert sum(c1.values()) > 0

    def test_different_seed_differs(self):
        s1, _ = self._schedule(42)
        s2, _ = self._schedule(43)
        assert s1 != s2

    def test_zero_rates_inject_nothing(self):
        inj = FaultInjector(seed=0)  # default FaultSpec: all zeros
        for _ in range(100):
            assert not inj.alloc_failure()
            assert not inj.step_fault(fresh=True)
            assert inj.poison_decode([1, 2]) == []
            assert inj.step_delay() == 0.0
            assert inj.preempt_storm(4) == 0
        assert inj.total_injected == 0

    def test_burst_bounded_by_spec(self):
        """Consecutive injected step failures per dispatch never exceed
        1 + step_exception_burst, so a retry budget ≥ that always
        converges."""
        inj = FaultInjector(
            seed=7, spec=FaultSpec(step_exception=1.0,
                                   step_exception_burst=2),
        )
        for _ in range(30):
            run = 0
            while inj.step_fault(fresh=(run == 0)):
                run += 1
                assert run <= 2  # ≤ step_exception_burst consecutive
            assert run >= 1  # rate 1.0: every fresh dispatch faults


class TestHloCostParser:
    def test_scan_trip_count(self):
        def f(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y

        x = jnp.zeros((64, 64))
        c = jax.jit(f).lower(x, x).compile()
        costs = compute_costs(c.as_text())
        assert costs.flops == pytest.approx(7 * 2 * 64**3, rel=0.01)

    def test_nested_scan(self):
        def f(x, w):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ w, None
                c, _ = jax.lax.scan(inner, c, None, length=3)
                return c, None
            y, _ = jax.lax.scan(outer, x, None, length=5)
            return y

        x = jnp.zeros((32, 32))
        c = jax.jit(f).lower(x, x).compile()
        costs = compute_costs(c.as_text())
        assert costs.flops == pytest.approx(15 * 2 * 32**3, rel=0.01)

    def test_shape_bytes(self):
        assert shape_bytes("f32[4,4]{1,0}") == 64
        assert shape_bytes("bf16[2,3]{1,0}") == 12
        assert shape_bytes("(s32[], f32[8]{0})") == 36
        assert shape_bytes("pred[10]") == 10
