"""Hybrid chunk/decode scheduler: the prefill head-of-line-stall fix.

The contract under test is the strongest one the engine offers: the
hybrid tick — at most one prefill chunk wave interleaved with the
decode step — produces per-uid token streams **bit-identical** to the
synchronous whole-wave-per-admission schedule, across attention impls
(dense and mpmrf_block), the paged pool with prefix sharing and
preemption, chaos injection, and meshless DP replication. On top of the
core: mid-prefill cancellation/expiry containment, the per-token
streaming callback, admission lookahead + tenant/priority fairness, the
decode-attributed ITL split, and the amortized-O(1) pending queue at
5k-request depth.
"""

import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import EnergonConfig
from repro.models import LMModel
from repro.runtime import (
    FaultInjector,
    FaultSpec,
    PendingQueue,
    ReplicatedServeLoop,
    Request,
    ServeLoop,
)


#: an EOS id no model can emit (sampled ids are ≥ 0): streams run to
#: max_new_tokens whatever tokens the random weights produce
NO_EOS = -1


def _model(impl="mpmrf_block"):
    energon = (
        EnergonConfig(impl="dense") if impl == "dense"
        else EnergonConfig(
            impl="mpmrf_block", pruning_ratio=1.0, query_block=8,
            key_block=16, decode_key_block=16, min_prune_layer=1,
        )
    )
    cfg = ModelConfig(
        name=f"hybrid-test-{impl}", family="dense", num_layers=2,
        d_model=32, num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
        vocab_size=64, dtype="float32", remat="none", energon=energon,
    )
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def mt():
    """Shared block-attention model (paged-capable)."""
    return _model("mpmrf_block")


@pytest.fixture(scope="module")
def mt_dense():
    return _model("dense")


def _trace(n_req=8, seed=11, max_new=6, long_every=None):
    """Mixed trace: two shared-prefix families, ragged suffixes, greedy
    and stochastic temperatures; ``long_every`` makes every k-th prompt
    long enough to span many chunks (the head-of-line stressor)."""
    rng = np.random.default_rng(seed)
    trace = []
    for uid in range(n_req):
        fam = uid % 2
        prefix = [(fam * 43 + j * 13) % 61 + 1 for j in range(16)]
        n_suf = int(rng.integers(1, 12))
        if long_every and uid % long_every == 0:
            n_suf = 64 + int(rng.integers(0, 16))
        suffix = [int(t) for t in rng.integers(1, 62, size=n_suf)]
        trace.append(dict(
            uid=uid, prompt=prefix + suffix,
            max_new_tokens=max_new,
            temperature=0.8 if uid % 2 else 0.0,
        ))
    return trace


def _drain(mt, trace, **kw):
    cfg, model, params = mt
    kw.setdefault("batch_slots", 3)
    kw.setdefault("max_len", 160)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("eos_token", cfg.vocab_size - 1)
    e = ServeLoop(model, params, **kw)
    for r in trace:
        e.submit(Request(**r))
    done = e.run_until_drained(max_ticks=40_000)
    return e, {r.uid: tuple(r.tokens_out) for r in done}


class TestHybridSyncEquivalence:
    """Per-uid streams: hybrid ≡ sync, bit for bit."""

    def test_paged_sharing_preemption(self, mt):
        """Tight pool (preemption fires), prefix sharing on, mixed
        temperatures, long prompts puncturing live decode streams."""
        # max_new=20 makes decode appends cross page boundaries while
        # the 10-page pool (= one max-length resident) is saturated —
        # that exhaustion path is what fires preemption
        trace = _trace(n_req=10, max_new=20, long_every=3)
        eh, h = _drain(mt, trace, scheduler="hybrid", num_pages=10,
                       audit=True)
        es, s = _drain(mt, trace, scheduler="sync", num_pages=10,
                       audit=True)
        assert h == s
        assert set(h) == {r["uid"] for r in trace}
        # the schedule really was different (hybrid spreads the waves)
        assert eh.metrics.ticks > es.metrics.ticks
        assert eh.metrics.preemptions > 0  # the pool was actually tight
        assert eh.allocator.pages_in_use == 0

    def test_dense_unpaged(self, mt_dense):
        trace = _trace(n_req=8, long_every=4)
        _, h = _drain(mt_dense, trace, scheduler="hybrid")
        _, s = _drain(mt_dense, trace, scheduler="sync")
        assert h == s

    def test_replicated_meshless(self, mt):
        """DP replicas behind the shared queue: hybrid replicas stream
        identically to sync replicas (and placement is unchanged)."""
        cfg, model, params = mt

        def run(scheduler):
            loop = ReplicatedServeLoop(
                model, params, replicas=2, batch_slots=2, max_len=160,
                prefill_chunk=16, eos_token=cfg.vocab_size - 1,
                scheduler=scheduler,
            )
            trace = _trace(n_req=8, long_every=4)
            for r in trace:
                loop.submit(Request(**r))
            done = loop.run_until_drained(max_ticks=40_000)
            return (
                {r.uid: tuple(r.tokens_out) for r in done},
                dict(loop.placement),
            )

        h, place_h = run("hybrid")
        s, place_s = run("sync")
        assert h == s
        assert place_h == place_s

    def test_chaos_fault_invisibility_inside_hybrid_ticks(self, mt):
        """The fault-invisibility contract is scheduler-independent:
        with chaos sites firing between chunk waves and on interleaved
        decode steps, every hybrid survivor streams bit-identically to
        the fault-free run and no healthy request is lost."""
        trace = _trace(n_req=8, long_every=3)
        clean, ref = _drain(mt, trace, scheduler="hybrid", num_pages=21,
                            audit=True)
        inj = FaultInjector(seed=5, spec=FaultSpec(
            nan_logits=0.02, nan_prefill=0.05, alloc_failure=0.05,
            preempt_storm=0.05, preempt_storm_size=1,
        ))
        chaos, surv = _drain(mt, trace, scheduler="hybrid", num_pages=21,
                             audit=True, fault_injector=inj)
        assert inj.total_injected > 0
        killed = {r.uid for r in chaos.terminated}
        lost = [u for u in ref if u not in surv and u not in killed]
        assert lost == []
        for uid, toks in surv.items():
            assert toks == ref[uid], uid


class TestBoundedBudget:
    """The tentpole property: a tick dispatches at most one prefill
    chunk wave + one decode step, so long admissions cost live streams
    chunk-sized stalls instead of a whole-wave freeze."""

    def test_one_chunk_wave_per_tick(self, mt):
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=256,
                      prefill_chunk=16, eos_token=cfg.vocab_size - 1)
        rng = np.random.default_rng(0)
        e.submit(Request(
            uid=0,
            prompt=[int(t) for t in rng.integers(1, 62, size=160)],
            max_new_tokens=4,
        ))
        pf_prev = dec_prev = 0
        for _ in range(12):
            e.tick()
            pf, dec = e.metrics.prefill_dispatches, \
                e.metrics.decode_dispatches
            assert pf - pf_prev <= 1, "more than one chunk wave in a tick"
            assert dec - dec_prev <= 1
            pf_prev, dec_prev = pf, dec
        # 160 tokens / chunk 16 → the job really did span many ticks
        assert e.metrics.prefill_dispatches >= 10

    def test_decode_advances_during_long_admission(self, mt):
        """A live stream keeps committing tokens while a 128-token
        neighbour prefills — the exact stall the sync tick exhibits."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=256,
                      prefill_chunk=16, eos_token=NO_EOS)
        e.submit(Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=32))
        e.tick()  # uid 0 admits + finishes its single-chunk prefill
        assert e.slots[0].state == "decode"
        rng = np.random.default_rng(1)
        e.submit(Request(
            uid=1,
            prompt=[int(t) for t in rng.integers(1, 62, size=128)],
            max_new_tokens=2,
        ))
        before = len(e.slots[0].tokens_out)
        # uid 1 needs ceil(128/16) = 8 chunk ticks; uid 0 must commit
        # a token on every one of them
        for _ in range(8):
            e.tick()
        # uid 1 either still has its job, reached decode, or (its last
        # chunk + the same-tick decode step covering max_new_tokens=2)
        # already finished and released the slot
        assert (
            1 in e._prefill_jobs
            or (e.slots[1] is not None and e.slots[1].state == "decode")
            or any(r.uid == 1 for r in e.completed)
        )
        assert len(e.slots[0].tokens_out) == before + 8
        e.run_until_drained()

    def test_tick_counts_every_call(self, mt):
        """Idle, prefill-only, and decode ticks all count: the
        observability per-tick series contract (len(series) == ticks)
        must hold under the hybrid schedule too."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=128,
                      prefill_chunk=16, eos_token=cfg.vocab_size - 1)
        e.tick()  # idle
        assert e.metrics.ticks == 1
        rng = np.random.default_rng(2)
        e.submit(Request(
            uid=0,
            prompt=[int(t) for t in rng.integers(1, 62, size=48)],
            max_new_tokens=2,
        ))
        e.tick()  # admit + first chunk, prefill-only
        assert e.metrics.ticks == 2
        e.run_until_drained()


class TestMidPrefillLifecycle:
    """cancel(uid) and deadline expiry can now land *between* chunk
    waves: pages must come home, the prefix trie must stay attachable,
    and survivors must stream bit-identically."""

    def _start_long_job(self, mt, **kw):
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=256,
                      prefill_chunk=16, eos_token=NO_EOS,
                      audit=True, **kw)
        e.submit(Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=6,
                         temperature=0.7))
        e.tick()
        prompt = [(j * 11) % 61 + 1 for j in range(96)]
        e.submit(Request(uid=1, prompt=list(prompt), max_new_tokens=4))
        e.tick()  # uid 1 admits; its job is mid-flight
        assert 1 in e._prefill_jobs
        assert e.slots[1] is not None and e.slots[1].state == "prefill"
        return e, prompt

    def test_cancel_mid_prefill(self, mt):
        ref_e, ref = _drain(
            mt, [dict(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=6,
                      temperature=0.7)],
            scheduler="hybrid", batch_slots=2, max_len=256,
            eos_token=NO_EOS,
        )
        e, prompt = self._start_long_job(mt)
        assert e.cancel(1)
        assert 1 not in e._prefill_jobs      # job died with the slot
        assert e.slots[1] is None
        # the trie stays attachable: an identical prompt re-registers
        # and completes (the cancelled job never registered its pages)
        e.submit(Request(uid=2, prompt=list(prompt), max_new_tokens=4))
        done = e.run_until_drained(max_ticks=40_000)
        assert {r.uid for r in done} == {0, 2}
        # the survivor never noticed: bit-identical to an undisturbed run
        assert next(
            tuple(r.tokens_out) for r in done if r.uid == 0
        ) == ref[0]
        assert e.terminated[0].uid == 1
        assert e.terminated[0].state == "cancelled"
        assert e.allocator.pages_in_use == 0

    def test_deadline_expires_mid_prefill(self, mt):
        e, _ = self._start_long_job(mt)
        e.slots[1].deadline_s = 1e-9  # lapses before the next tick
        done = e.run_until_drained(max_ticks=40_000)
        assert {r.uid for r in done} == {0}
        assert e.terminated[0].uid == 1
        assert e.terminated[0].state == "expired"
        assert 1 not in e._prefill_jobs
        assert e.allocator.pages_in_use == 0

    def test_preempt_mid_prefill_resumes_exactly(self, mt):
        """A slot preempted between chunk waves re-admits as fresh (no
        token was ever sampled) and its final stream is unchanged."""
        e, prompt = self._start_long_job(mt)
        e._preempt(1)
        assert 1 not in e._prefill_jobs
        assert e.pending[0].uid == 1
        assert e.pending[0].state == "preempted"
        done = e.run_until_drained(max_ticks=40_000)
        _, ref = _drain(
            mt, [dict(uid=1, prompt=list(prompt), max_new_tokens=4)],
            scheduler="hybrid", batch_slots=2, max_len=256,
            eos_token=NO_EOS,
        )
        assert next(
            tuple(r.tokens_out) for r in done if r.uid == 1
        ) == ref[1]


class TestStreaming:
    def test_tokens_surface_as_committed(self, mt):
        """on_token fires at commit time — strictly increasing tick
        stamps, not one burst at drain — and replays tokens_out."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=128,
                      prefill_chunk=16, eos_token=cfg.vocab_size - 1)
        got = []

        def on_token(req, tok):
            got.append((req.uid, tok, e.metrics.ticks))

        e.submit(Request(uid=0, prompt=[1, 2, 3, 4], max_new_tokens=6,
                         on_token=on_token))
        done = e.run_until_drained()
        assert [t for _, t, _ in got] == list(done[0].tokens_out)
        ticks = [k for _, _, k in got]
        assert ticks == sorted(ticks)
        # the prefill-completion commit and the same tick's decode step
        # may share one stamp (a tick's budget is one chunk wave + one
        # decode); beyond that pair every commit lands on its own tick
        assert len(set(ticks)) >= len(ticks) - 1
        assert len(set(ticks[1:])) == len(ticks[1:])
        assert ticks[0] < e.metrics.ticks     # first token pre-drain

    def test_streaming_callback_does_not_perturb_streams(self, mt):
        trace = _trace(n_req=6)
        _, base = _drain(mt, trace)
        seen = {}
        cb_trace = [
            dict(r, on_token=lambda q, t: seen.setdefault(
                q.uid, []).append(t))
            for r in trace
        ]
        _, cb = _drain(mt, cb_trace)
        assert cb == base
        assert {u: tuple(t) for u, t in seen.items()} == cb


class TestItlAttribution:
    def test_decode_itl_excludes_prefill_stalls(self, mt):
        """The decode-attributed gap strips engine prefill time spent
        between a stream's commits; with long-prompt admissions
        puncturing live streams the raw p95 must exceed the
        decode-attributed p95 (the stall the metric used to hide)."""
        trace = _trace(n_req=10, max_new=12, long_every=3)
        e, _ = _drain(mt, trace, scheduler="hybrid", num_pages=48,
                      batch_slots=2)
        stats = e.metrics.latency_stats()
        assert stats["itl_decode_p95"] > 0.0
        assert stats["itl_decode_p95"] <= stats["itl_p95"]
        # per-request: every decode-attributed sample is bounded by its
        # raw counterpart (the subtraction can only shrink a gap)
        for rec in e.metrics.request_records:
            for raw, dec in zip(rec["itl"], rec["itl_decode"]):
                assert dec <= raw + 1e-9


class TestAdmissionPolicy:
    def test_lookahead_admits_small_request_behind_big_head(self, mt):
        """A head needing more pages than the pool can free must not
        starve a small request behind it when lookahead > 0 — and the
        ordering metadata stays consistent (the big head still admits
        first once pages free up)."""
        cfg, model, params = mt

        def run(lookahead):
            e = ServeLoop(model, params, batch_slots=2, max_len=256,
                          prefill_chunk=16, num_pages=16, audit=True,
                          eos_token=cfg.vocab_size - 1,
                          admission_lookahead=lookahead)
            # occupy most of the 16-page pool: a live 64-token slot
            # holds 4+ pages and decodes for a while
            e.submit(Request(uid=0, prompt=[(j * 7) % 61 + 1
                                            for j in range(64)],
                             max_new_tokens=24))
            for _ in range(6):
                e.tick()
            assert e.slots[0] is not None and e.slots[0].uid == 0
            # big head: needs 192 rows = 12 pages — more than the ~11
            # the pool has free while uid 0 is live
            e.submit(Request(uid=1, prompt=[(j * 5) % 61 + 1
                                            for j in range(192)],
                             max_new_tokens=2))
            # small request behind it: 2 pages, fits immediately
            e.submit(Request(uid=2, prompt=[9, 8, 7, 6],
                             max_new_tokens=2))
            e.tick()
            # a tiny request can admit *and* finish inside this one
            # tick (single chunk + same-tick decode covers max_new=2),
            # so count completions as "admitted" too
            admitted_now = {
                s.uid for s in e.slots if s is not None
            } | {r.uid for r in e.completed}
            done = e.run_until_drained(max_ticks=40_000)
            assert {r.uid for r in done} == {0, 1, 2}
            order = sorted(
                (r._t_admit, r.uid) for r in done if r.uid in (1, 2)
            )
            return admitted_now, [u for _, u in order]

        strict_now, strict_order = run(lookahead=0)
        ahead_now, ahead_order = run(lookahead=1)
        assert 2 not in strict_now          # old semantics: head blocks
        assert 2 in ahead_now               # lookahead admits the small
        assert strict_order == [1, 2]
        assert ahead_order == [2, 1]

    def test_tenant_round_robin_and_priority(self, mt):
        """Within a priority class tenants alternate; a higher class
        preempts the whole rotation."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=1, max_len=64,
                      prefill_chunk=8, eos_token=cfg.vocab_size - 1)
        # tenant A floods; tenant B submits one; C outranks everyone
        for k in range(4):
            e.submit(Request(uid=10 + k, prompt=[1 + k, 2, 3],
                             max_new_tokens=1, tenant="A"))
        e.submit(Request(uid=20, prompt=[4, 5, 6], max_new_tokens=1,
                         tenant="B"))
        e.submit(Request(uid=30, prompt=[7, 8, 9], max_new_tokens=1,
                         tenant="C", priority=5))
        done = e.run_until_drained()
        order = [u for _, u in sorted(
            (r._t_admit, r.uid) for r in done
        )]
        # priority 5 first; then A/B alternate until B drains
        assert order[0] == 30
        assert order[1:3] in ([10, 20], [20, 10])
        assert set(order[3:]) == {11, 12, 13}

    def test_single_tenant_default_stays_fifo(self, mt):
        """Defaults (priority 0, tenant "") must reproduce exact FIFO —
        the compatibility spine for every pre-fairness trace."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=1, max_len=64,
                      prefill_chunk=8, eos_token=cfg.vocab_size - 1)
        for uid in range(5):
            e.submit(Request(uid=uid, prompt=[uid + 1, 2, 3],
                             max_new_tokens=1))
        done = e.run_until_drained()
        order = [u for _, u in sorted(
            (r._t_admit, r.uid) for r in done
        )]
        assert order == [0, 1, 2, 3, 4]


class TestPendingQueueScaling:
    """The O(n²)-queue fix: 5k queued requests admit/expire/shed with
    amortized O(1) queue operations."""

    def _churn(self, n):
        q = PendingQueue()
        now = 1000.0
        for uid in range(n):
            r = Request(uid=uid, prompt=[1], priority=uid % 3,
                        tenant=f"t{uid % 7}")
            r._submit_seq = uid
            if uid % 4 == 0:
                r.deadline_s = 0.5
                r._t_submit = now
            q.append(r)
        t0 = time.perf_counter()
        # interleave the hot-path ops the engine issues per tick
        for k in range(n):
            if k % 3 == 0:
                for req in q.admission_order(4):
                    q.remove(req.uid)
                    q.note_admitted(req)
            elif k % 3 == 1:
                v = q.shed_victim()
                if v is not None:
                    q.remove(v.uid)
            else:
                q.pop_expired(now + (k / n))
        while q:
            for req in q.admission_order(8):
                q.remove(req.uid)
            q.pop_expired(now + 10.0)
        return time.perf_counter() - t0

    def test_5k_queue_no_quadratic_blowup(self):
        small, big = 1000, 5000
        t_small = max(self._churn(small), 1e-4)
        t_big = self._churn(big)
        ratio = t_big / t_small
        # O(n) ⇒ ~5×, O(n²) ⇒ ~25×; generous slack for timer noise
        assert ratio < 15.0, (t_small, t_big, ratio)
        assert t_big < 5.0, t_big

    def test_5k_engine_submissions_expire_in_one_pass(self, mt):
        """Engine-level integration: 5k queued requests with lapsed
        deadlines drain through the O(expired·log n) heap path — no
        per-tick full-queue scan, no quadratic host time."""
        cfg, model, params = mt
        e = ServeLoop(model, params, batch_slots=2, max_len=64,
                      prefill_chunk=8, eos_token=cfg.vocab_size - 1,
                      default_deadline_s=1e-9)
        t0 = time.perf_counter()
        for uid in range(5000):
            e.submit(Request(uid=uid, prompt=[1 + uid % 60],
                             max_new_tokens=1))
        done = e.run_until_drained(max_ticks=50)
        host = time.perf_counter() - t0
        assert done == []
        assert e.metrics.expired_requests == 5000
        assert len(e.terminated) == 5000
        assert host < 10.0, host

    def test_queue_list_compat_surface(self):
        """The observable list API tests and tools rely on: iteration
        order (preempted requeues first, then arrival), indexing, len,
        membership, shed-victim choice."""
        q = PendingQueue()
        reqs = []
        for uid in range(4):
            r = Request(uid=uid, prompt=[1], priority=uid % 2)
            r._submit_seq = uid
            q.append(r)
            reqs.append(r)
        assert len(q) == 4 and 2 in q and 99 not in q
        assert [r.uid for r in q] == [0, 1, 2, 3]
        assert q[0].uid == 0 and q[-1].uid == 3
        q.remove(1)
        assert [r.uid for r in q] == [0, 2, 3]
        q.requeue_front(reqs[3])  # simulate preemption requeue
        assert q[0].uid == 3
        # shed victim: lowest priority (0), youngest of the tie → uid 2
        assert q.shed_victim().uid == 2
