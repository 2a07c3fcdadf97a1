"""The engine's host spans and the model's named scopes (DESIGN.md §8).

* ``span`` opens a profiler TraceMe, times the block once, and that one
  reading feeds the ``EventTrace`` span events and the engine's
  ``decode_time`` / ``prefill_time`` counters;
* one ``ServeLoop`` tick under ``jax.profiler.trace`` writes every
  ``serve.*`` span, each nested under ``serve.tick``, itself inside the
  caller's span, on the profiler's host plane;
* the lowered decode and prefill programs carry the scopes
  ``attention``, ``attention/page_write``, ``attention/select``,
  ``attention/gather``, ``mlp`` and ``head`` in their op metadata.
"""

import glob
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core import EnergonConfig
from repro.models import LMModel
from repro.observability import EventTrace, Observability, span
from repro.runtime import Request, ServeLoop

SERVE_SPANS = ("serve.admit", "serve.prefill", "serve.prefill.sync",
               "serve.decode", "serve.decode.inputs",
               "serve.decode.dispatch", "serve.decode.register",
               "serve.decode.sync", "serve.commit", "serve.end")
SCOPES = ("attention/", "attention/page_write/", "attention/select/",
          "attention/gather/", "mlp/", "head/")


@pytest.fixture(scope="module")
def mt():
    """A tiny dense model on the fused (Pallas, interpret-mode) paged
    path, so every scope of the serving step is traced."""
    cfg = ModelConfig(
        name="span-test", family="dense", num_layers=2, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
        dtype="float32", remat="none",
        energon=EnergonConfig(
            impl="pallas", pruning_ratio=2.0, query_block=8,
            key_block=16, decode_key_block=16, min_prune_layer=1,
            filter_cache_min_len=0,
        ),
    )
    model = LMModel(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(mt, **kw):
    cfg, model, params = mt
    return ServeLoop(model, params, batch_slots=2, max_len=64,
                     prefill_chunk=8, eos_token=cfg.vocab_size - 1, **kw)


class TestSpanHelper:
    def test_seconds_fixed_at_close_and_shared_with_the_event(self):
        trace = EventTrace()
        with span("serve.decode", trace) as sp:
            time.sleep(0.002)
            dur = sp.emit("decode_tick", site="decode", live=2)
            assert sp.seconds >= dur
        closed = sp.seconds
        time.sleep(0.002)
        assert sp.seconds == closed >= dur >= 0.002
        (ev,) = trace.events
        assert ev.name == "decode_tick" and ev.dur == dur
        assert ev.signature() == ("decode_tick", 0, None, None, "decode",
                                  (("live", 2),))

    def test_without_a_trace_it_only_times(self):
        with span("serve.prefill", None, uids=[3]) as sp:
            sp.annotate(finished=1)
            dur = sp.emit("prefill_tick", site="prefill")
        assert 0 <= dur <= sp.seconds


class TestEngineSpans:
    def test_one_tick_writes_every_span_nested(self, mt, tmp_path):
        """A tick that admits a one-chunk prompt prefills it, commits
        its first token and decodes in the same tick: every phase span
        lands on the profiler's host plane, inside ``serve.tick``,
        inside the caller's span."""
        from jax.profiler import ProfileData

        engine = _engine(mt)
        engine.submit(Request(uid=7, prompt=[5, 6, 7, 8, 9],
                              max_new_tokens=4))
        warm = _engine(mt)                       # compile outside the trace
        warm.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
        warm.tick()
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("caller"):
                engine.tick()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[-1]
        spans = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "caller" or ev.name.startswith("serve."):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             {k: v for k, v in ev.stats}))
        assert set(SERVE_SPANS) | {"serve.tick", "caller"} <= set(spans)
        (c0, c1, _), = spans["caller"]
        (t0, t1, _), = spans["serve.tick"]
        assert c0 <= t0 <= t1 <= c1

        def inside(name, parent):
            (p0, p1, _), = spans[parent]
            return all(p0 <= s <= e <= p1 for s, e, _ in spans[name])

        for name in SERVE_SPANS:
            assert inside(name, "serve.tick"), name
        assert inside("serve.prefill.sync", "serve.prefill")
        for name in SERVE_SPANS:
            if name.startswith("serve.decode."):
                assert inside(name, "serve.decode"), name
        assert spans["serve.admit"][0][2]["uids"] == "[7]"
        assert spans["serve.prefill"][0][2]["uids"] == "[7]"
        assert engine.slots[0].tokens_out    # first token and one decode

    def test_chunk_spans_carry_their_wave(self, mt, tmp_path):
        """Each chunk wave's dispatch is a ``serve.prefill.chunk`` inside
        ``serve.prefill``, whose args give the live slots' first
        positions and live query rows, space-separated."""
        from jax.profiler import ProfileData

        engine = _engine(mt)
        engine.submit(Request(uid=1, prompt=list(range(1, 12)),
                              max_new_tokens=2))
        engine.submit(Request(uid=2, prompt=[5, 6, 7, 8, 9],
                              max_new_tokens=2))
        warm = _engine(mt)                       # compile outside the trace
        warm.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=4))
        warm.tick()
        with jax.profiler.trace(str(tmp_path)):
            engine.tick()
            engine.tick()
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[-1]
        spans = {"serve.prefill": [], "serve.prefill.chunk": []}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             {k: str(v) for k, v in ev.stats}))
        chunks = sorted(spans["serve.prefill.chunk"], key=lambda s: s[0])
        waves = spans["serve.prefill"]
        assert len(chunks) == len(waves) == 2
        for c0, c1, _ in chunks:
            assert any(p0 <= c0 <= c1 <= p1 for p0, p1, _ in waves)
        assert chunks[0][2] == {"starts": "0 0", "rows": "8 5"}
        assert chunks[1][2] == {"starts": "8", "rows": "3"}

    def test_events_and_counters_share_the_span_clock(self, mt):
        """``decode_tick`` / ``prefill_tick`` durations are the very
        readings ``decode_time`` and ``prefill_time`` add up."""
        obs = Observability(device_telemetry=False)
        engine = _engine(mt, observability=obs)
        for uid in range(3):
            engine.submit(Request(uid=uid, prompt=list(range(1, 12 + uid)),
                                  max_new_tokens=5))
        engine.run_until_drained()
        evs = obs.trace.events
        decode = [e.dur for e in evs if e.name == "decode_tick"]
        prefill = [e.dur for e in evs if e.name == "prefill_tick"]
        assert decode and prefill
        assert sum(decode) == pytest.approx(engine.metrics.decode_time,
                                            rel=1e-12)
        assert sum(prefill) == pytest.approx(engine.metrics.prefill_time,
                                             rel=1e-12)

    def test_sync_scheduler_wave_shares_the_span_clock(self, mt):
        obs = Observability(device_telemetry=False)
        engine = _engine(mt, observability=obs, scheduler="sync")
        engine.submit(Request(uid=1, prompt=list(range(1, 20)),
                              max_new_tokens=3))
        engine.run_until_drained()
        waves = [e.dur for e in obs.trace.events
                 if e.name == "prefill_wave"]
        assert len(waves) == 1
        assert waves[0] == pytest.approx(engine.metrics.prefill_time,
                                         rel=1e-12)


def _scope_names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


class TestNamedScopes:
    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_lowered_programs_carry_every_scope(self, mt, program):
        engine = _engine(mt)
        B = engine.batch_slots
        table = engine._device_block_table()
        if program == "decode":
            fn = engine.step_fn
            inputs = {"tokens": jnp.zeros((B, 1), jnp.int32),
                      "active": jnp.ones((B,), bool),
                      "block_table": table}
        else:
            fn = engine.prefill_fn
            inputs = {"tokens": jnp.zeros((B, 8), jnp.int32),
                      "positions": jnp.zeros((B, 8), jnp.int32),
                      "block_table": table}
        names = _scope_names(fn.lower(engine.params, engine.cache, inputs,
                                      engine.cache_index))
        for scope in SCOPES:
            assert any(scope in n for n in names), scope
        # the jitted filter kernel is called inside the select scope
        assert any(n.startswith("attention/select/jit(mpmrf_paged_")
                   for n in names)

    def test_prefill_program_streams_dense_layers_under_their_scope(
            self, mt):
        """The layer below ``min_prune_layer`` attends through the
        streamed dense kernel, called inside ``attention/dense``."""
        engine = _engine(mt)
        B = engine.batch_slots
        inputs = {"tokens": jnp.zeros((B, 8), jnp.int32),
                  "positions": jnp.zeros((B, 8), jnp.int32),
                  "block_table": engine._device_block_table()}
        names = _scope_names(engine.prefill_fn.lower(
            engine.params, engine.cache, inputs, engine.cache_index))
        assert any(n.startswith("attention/dense/jit(paged_prefill_dense")
                   for n in names)
