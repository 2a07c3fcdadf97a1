"""The comparison that decides ``correct``, at a size a test run holds.

A whole run is driven on the CPU (the harness's look for a chip is
skipped): the engine serves a tiny float32 model with the fused kernels
in interpret mode, the window closes, and the plain reference scores
the served tokens. A sound run passes; the float8 control, and each
fault a serving cell can have, come out not correct.
"""

import copy
import json
import os
import time

import jax.numpy as jnp
import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 17
#: mean logit gap allowed at this size: the float32 program meets the
#: float32 reference to rounding (gap 0 wherever the argmax agrees),
#: while the float8 control reads about 0.1 and the faults 0.03 to 0.7
LIMIT = 1e-2


def _spec():
    with open(os.path.join(ROOT, "bench", "configs",
                           "qwen3-14b-8l.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=2048, torch_dtype="float32", pool_pages=24,
               initializer_range=0.25)
    cfg["energon"] = dict(cfg["energon"], filter_cache_min_len=0)
    mix = {"loop": "closed", "clients": 2, "shared_prefix": 256,
           "prompt": {"dist": "uniform", "min": 8, "max": 40},
           "output": {"dist": "uniform", "min": 16, "max": 32},
           "strata": 8, "sample": 6,
           "engine": {"batch_slots": 2, "max_len": 512,
                      "prefill_chunk": 64}}
    e2e = [{"name": n, "unit": "x"} for n in
           ("setup_s", "tok_s", "ttft_p90_ms", "itl_p95_ms")]
    return {"name": "tiny", "cfg": cfg, "traffic": mix,
            "limits": {"logit_gap_mean": LIMIT}, "end_to_end": e2e,
            "per_layer": [], "chips": 1}


def _run(hooks=None, control=False, **kw):
    return harness.run(_spec(), SEED, 1.5, False, time.perf_counter(),
                       hooks=hooks, control=control, log=lambda m: None,
                       **kw)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    gap = sound["compared"]["logit_gap_mean"]["value"]
    ctrl = sound["compared"]["control_gap_mean"]["value"]
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert gap <= LIMIT
    assert ctrl > 3 * LIMIT            # the float8 control fails
    assert list(sound)[-1] == "compared"
    m = sound["metrics"]
    assert m["tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0


def _keep_state(engine):
    """Decode step whose cache writes are all dropped."""
    real = engine.step_fn

    def step(params, cache, inputs, cache_index):
        inputs = dict(inputs, active=jnp.zeros_like(inputs["active"]))
        return real(params, cache, inputs, cache_index)

    engine.step_fn = step


def _half_batch(engine):
    """Decode step that computes only the first half of the slots."""
    real = engine.step_fn

    def step(params, cache, inputs, cache_index):
        logits, cache = real(params, cache, inputs, cache_index)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:1]), cache

    engine.step_fn = step


@pytest.mark.parametrize("fault", [_keep_state, _half_batch])
def test_broken_step_is_not_correct(fault):
    out = _run(hooks=fault)
    assert not out["correct"]
    assert out["compared"]["logit_gap_mean"]["value"] > LIMIT


def test_altered_token_is_not_correct(monkeypatch):
    from repro.runtime import serve_loop

    real = serve_loop._sample_step

    def altered(logits, temps, keys, active):
        toks, keys, finite = real(logits, temps, keys, active)
        return (toks + 1) % logits.shape[-1], keys, finite

    monkeypatch.setattr(serve_loop, "_sample_step", altered)
    out = _run()
    assert not out["correct"]
    assert out["compared"]["logit_gap_mean"]["value"] > LIMIT
