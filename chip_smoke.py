#!/usr/bin/env python3
"""Chip smoke test: phi3-mini-3.8b at full published width, served on
one TPU through ``ServeLoop`` with the fused MP-MRF Pallas kernels.

    python3 chip_smoke.py [--seed N]     # one chip
    python3 chip_smoke.py --four-chips   # 2 replicas x 2-way TP vs 1 chip

The model is the registry ``phi3-mini-3.8b`` config with one change,
``energon.impl = "pallas"``; weights are random, drawn from ``--seed``.
One process, no children. The one-chip run:

1. prints the device and exits non-zero unless JAX sees a TPU;
2. turns on the persistent compile cache and builds the weights on the
   device;
3. compiles the engine's decode and prefill steps and checks that both
   contain the fused filter and gather kernels as ``tpu_custom_call``s
   (no interpret mode, no XLA fallback);
4. serves 8 requests (a shared 128-token system prompt plus 100-800
   user tokens, 32 new tokens, an EOS id no model emits) through the
   paged pool, prefix sharing and the hybrid scheduler;
5. prefills the same prompts on the fused path and on the XLA
   ``mpmrf_block`` path with the same weights and compares their
   per-layer selection counts and last-position logits, then feeds
   both attention dispatches the same keys and queries at every sparse
   layer, where their selection counts must agree exactly.

Earlier lines report compile seconds, peak device memory, the path
each phase took and a smoke-size wall rate (not a benchmark). The last
line is the result JSON; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 8
SYSTEM_LEN = 128
USER_LEN = (100, 800)
NEW_TOKENS = 32
SLOTS = 4
MAX_LEN = 1024
PREFILL_CHUNK = 128
#: sampled ids are >= 0, so no request stops early
NO_EOS = -1
#: last-position logits, fused vs XLA, may differ by at most this share
#: of the largest |logit|: the two paths accumulate attention in a
#: different order (page-by-page flash vs one softmax), which moves a
#: bf16 activation by an ulp (2^-8 relative) here and there; 32 residual
#: layers carry such moves to the logits at a few ulps, and a score that
#: straddles a selection threshold in a deep layer adds more. 2^-4 is
#: 16 ulps — far below the O(1) error of a wrong kernel.
LOGIT_TOL = 2.0 ** -4

DECODE_KERNELS = ("mpmrf_decode_filter", "mpmrf_decode_gather")
PREFILL_KERNELS = ("mpmrf_prefill_filter", "mpmrf_prefill_gather")


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    counts its load time) and counts cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def custom_call_kernels(hlo_text: str):
    """Names of the Pallas kernels a compiled HLO module calls."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w\-]+?)(?:\.\d+)?\s*=", line)
            if m:
                names.add(m.group(1))
    return names


def make_requests(seed: int, vocab: int):
    """8 prompts: one shared system prompt plus ragged user turns."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, size=SYSTEM_LEN).tolist()
    lens = rng.integers(USER_LEN[0], USER_LEN[1] + 1, size=N_REQUESTS)
    return [system + rng.integers(0, vocab, size=int(n)).tolist()
            for n in lens]


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def build_engine(model, params, *, seed, observability=None, mesh=None,
                 replicated=False):
    import jax

    from repro.runtime import ReplicatedServeLoop, ServeLoop

    kw = dict(batch_slots=SLOTS, max_len=MAX_LEN, eos_token=NO_EOS,
              prefill_chunk=PREFILL_CHUNK, rng=jax.random.PRNGKey(seed),
              observability=observability)
    if replicated:
        return ReplicatedServeLoop(model, params, mesh=mesh, **kw)
    return ServeLoop(model, params, mesh=mesh, **kw)


def compile_engine_steps(engine):
    """AOT-compile the engine's decode and prefill steps (the calls
    that follow reuse them) and return which Pallas kernels each
    calls."""
    import jax
    import jax.numpy as jnp

    b, c = engine.batch_slots, engine.prefill_chunk
    table = engine._device_block_table()
    decode_in = {
        "tokens": jnp.zeros((b, 1), jnp.int32),
        "active": jnp.zeros((b,), bool),
        "block_table": table,
    }
    prefill_in = {
        "tokens": jnp.zeros((b, c), jnp.int32),
        "positions": jnp.full((b, c), engine.max_len, jnp.int32),
        "block_table": table,
    }
    found = {}
    for phase, fn, inputs in (
        ("decode", engine.step_fn_t, decode_in),
        ("prefill", engine.prefill_fn_t, prefill_in),
    ):
        t0 = time.perf_counter()
        compiled = fn.lower(
            engine.params, engine.cache, inputs, engine.cache_index
        ).compile()
        found[phase] = custom_call_kernels(compiled.as_text())
        log(f"[compile] {phase} step: {time.perf_counter() - t0:.1f} s "
            f"wall, kernels {sorted(found[phase]) or 'none'}")
    return found


def serve(engine, prompts):
    from repro.runtime import Request

    for uid, prompt in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=list(prompt),
                              max_new_tokens=NEW_TOKENS))
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    wall = time.perf_counter() - t0
    check(not engine.terminated,
          "requests ended abnormally: " + ", ".join(
              f"uid {r.uid} {r.state} ({r.error})"
              for r in engine.terminated))
    check(len(done) == len(prompts),
          f"{len(done)} of {len(prompts)} requests completed")
    for r in done:
        check(r.state == "done" and len(r.tokens_out) == NEW_TOKENS,
              f"uid {r.uid}: state {r.state}, {len(r.tokens_out)} tokens")
    return {r.uid: tuple(r.tokens_out) for r in done}, wall


def prefill_probe(prefill_fn, model, params, prompts):
    """Prefill every prompt from an empty pool through ``prefill_fn`` (a
    jitted ``model.prefill(..., telemetry=True)``), ``SLOTS`` prompts
    per wave on the engine's chunk grid. Returns the selection counts
    recorded into an ``Observability``, each prompt's last-position
    logits (float32 ``[n, V]``) and the pool the last wave wrote."""
    import jax.numpy as jnp
    import numpy as np

    from repro.observability import Observability

    obs = Observability()
    ps = model.cfg.energon.decode_key_block
    mb = MAX_LEN // ps
    table = jnp.arange(SLOTS * mb, dtype=jnp.int32).reshape(SLOTS, mb)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    last = [None] * len(prompts)
    for w0 in range(0, len(prompts), SLOTS):
        wave = prompts[w0:w0 + SLOTS]
        cache = model.init_paged_cache(SLOTS * mb, max_len=MAX_LEN)
        n_chunks = -(-max(len(p) for p in wave) // PREFILL_CHUNK)
        for ci in range(n_chunks):
            lo = ci * PREFILL_CHUNK
            toks = np.zeros((SLOTS, PREFILL_CHUNK), np.int32)
            pos = np.full((SLOTS, PREFILL_CHUNK), MAX_LEN, np.int32)
            for i, p in enumerate(wave):
                part = p[lo:lo + PREFILL_CHUNK]
                toks[i, :len(part)] = part
                pos[i, :len(part)] = lo + np.arange(len(part))
            logits, cache, stats = prefill_fn(
                params, cache,
                {"tokens": jnp.asarray(toks), "positions": jnp.asarray(pos),
                 "block_table": table},
                zeros,
            )
            obs.record_prefill_stats(np.asarray(stats))
            for i, p in enumerate(wave):
                if lo <= len(p) - 1 < lo + PREFILL_CHUNK:
                    last[w0 + i] = np.asarray(
                        logits[i, len(p) - 1 - lo], np.float32
                    )
    return obs.sparsity.layer_blocks("prefill"), np.stack(last), cache


def selection_on_shared_inputs(cfg_fused, cfg_xla, cache, wave, seed):
    """Both paths' prefill attention dispatch on *identical* inputs at
    every sparse layer: the pool ``cache`` holds the ``wave`` prompts'
    keys, and one seeded block of queries per slot covers each prompt's
    last chunk. Returns int32 ``[sparse layers, SLOTS, 4]`` selection
    counts per config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import energon_paged_prefill_attention

    e = cfg_fused.energon
    mb = MAX_LEN // e.decode_key_block
    table = jnp.arange(SLOTS * mb, dtype=jnp.int32).reshape(SLOTS, mb)
    qpos = jnp.asarray(np.stack([
        np.arange(len(p) - PREFILL_CHUNK, len(p)) for p in wave
    ]), jnp.int32)
    q = jax.random.normal(
        jax.random.PRNGKey(seed),
        (SLOTS, cfg_fused.num_kv_heads, PREFILL_CHUNK, cfg_fused.head_dim),
        jnp.bfloat16,
    )

    @functools.partial(jax.jit, static_argnums=0)
    def counts(ecfg, cache, q, qpos, table):
        def layer(i):
            pool = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                cache,
            )
            return energon_paged_prefill_attention(
                q, pool, table, qpos, ecfg,
                layer_index=ecfg.min_prune_layer, telemetry=True,
            )[1]

        return jax.lax.map(
            layer, jnp.arange(e.min_prune_layer, cfg_fused.num_layers)
        )

    return tuple(
        np.asarray(counts(ecfg, cache, q, qpos, table))
        for ecfg in (e, cfg_xla.energon)
    )


def compare_prefill(model_fused, prefill_fused, model_xla, params, prompts,
                    clock):
    """Step 5: fused vs XLA ``mpmrf_block`` prefill on the same prompts
    and weights — selection counts per layer, last-position logits."""
    import jax
    import numpy as np

    from repro.observability import STAT_FIELDS

    prefill_xla = jax.jit(
        functools.partial(model_xla.prefill, telemetry=True),
        donate_argnums=(1,),
    )
    counts_f, logits_f, cache = prefill_probe(prefill_fused, model_fused,
                                              params, prompts)
    del cache
    t0 = clock.seconds
    counts_x, logits_x, cache = prefill_probe(prefill_xla, model_xla,
                                              params, prompts)
    log(f"[compile] XLA mpmrf_block prefill step: "
        f"{clock.seconds - t0:.1f} s")

    check(np.isfinite(logits_f).all() and np.isfinite(logits_x).all(),
          "non-finite prefill logits")
    first = model_fused.cfg.energon.min_prune_layer
    same = (counts_f == counts_x).all(axis=1)
    log(f"[compare] prefill selection counts {STAT_FIELDS} summed over "
        f"layers: fused {counts_f.sum(0).tolist()} / XLA "
        f"{counts_x.sum(0).tolist()}")
    log(f"[compare] layers with identical counts: {int(same.sum())}/"
        f"{len(same)}; first sparse layer {first}: fused "
        f"{counts_f[first].tolist()} / XLA {counts_x[first].tolist()}")
    if not same.all():
        k = int(np.argmin(same))
        log(f"[compare] first differing layer {k}: fused "
            f"{counts_f[k].tolist()} / XLA {counts_x[k].tolist()}")
    check(counts_f[first:, 1].sum() > 0, "no live blocks were selected from")
    # up to and including the first sparse layer both paths see
    # bit-identical activations, so their selections must agree exactly
    check(bool(same[:first + 1].all()),
          "fused and XLA selection counts differ where their inputs agree")
    # past the first sparse layer the two forwards' activations differ
    # by bf16 rounding; feed both dispatches the same keys and queries
    # at every sparse layer instead
    shared_f, shared_x = selection_on_shared_inputs(
        model_fused.cfg, model_xla.cfg, cache, prompts[-SLOTS:], seed=1
    )
    del cache
    n_same = int((shared_f == shared_x).all(axis=(1, 2)).sum())
    log(f"[compare] same keys and queries at every sparse layer: "
        f"selection counts identical in {n_same}/{len(shared_f)} layers "
        f"(fused {shared_f.sum((0, 1)).tolist()} / XLA "
        f"{shared_x.sum((0, 1)).tolist()})")
    check(n_same == len(shared_f),
          "fused and XLA selection counts differ on identical inputs")
    scale = np.abs(logits_x).max(axis=-1)
    rel = np.abs(logits_f - logits_x).max(axis=-1) / scale
    agree = int((logits_f.argmax(-1) == logits_x.argmax(-1)).sum())
    log(f"[compare] last-position logits: max |fused - XLA| / max |XLA| "
        f"per request {[f'{r:.2e}' for r in rel]} (limit {LOGIT_TOL}); "
        f"argmax agrees on {agree}/{len(rel)}")
    check(bool((rel <= LOGIT_TOL).all()),
          f"logits differ by {rel.max():.4g} of their scale")


def one_chip(args, clock, device):
    import jax

    from repro.configs import phi3_mini_3_8b
    from repro.kernels.ops import _default_interpret
    from repro.models import LMModel
    from repro.observability import Observability

    base = phi3_mini_3_8b.CONFIG
    cfg = dataclasses.replace(
        base, energon=dataclasses.replace(base.energon, impl="pallas")
    )
    model = LMModel(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[init] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads} heads x {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
        f"{n_params / 1e9:.3f} B params built on device in "
        f"{time.perf_counter() - t0:.1f} s")

    check(not _default_interpret(), "Pallas kernels would run interpreted")
    obs = Observability()
    engine = build_engine(model, params, seed=args.seed, observability=obs)
    check(engine.paged and engine.sharing and engine.scheduler == "hybrid",
          "engine is not paged + prefix-shared + hybrid")
    check("k_codes" in engine.cache,
          "no resident filter planes: the fused kernels cannot engage")
    found = compile_engine_steps(engine)
    for phase, want in (("decode", DECODE_KERNELS),
                        ("prefill", PREFILL_KERNELS)):
        missing = [k for k in want if k not in found[phase]]
        check(not missing,
              f"{phase} step does not call {missing}: not the fused path")
        log(f"[path] {phase}: fused Pallas ({', '.join(want)} as "
            f"tpu_custom_call)")

    prompts = make_requests(args.seed, cfg.vocab_size)
    log(f"[serve] {N_REQUESTS} requests, {SYSTEM_LEN}-token shared "
        f"prefix + {USER_LEN[0]}-{USER_LEN[1]} user tokens "
        f"(prompt lengths {[len(p) for p in prompts]}), {NEW_TOKENS} new "
        f"tokens each, {SLOTS} slots, max_len {MAX_LEN}, prefill chunk "
        f"{PREFILL_CHUNK}")
    c0 = clock.seconds
    _, wall = serve(engine, prompts)
    m = engine.metrics
    check(m.prefix_hits > 0, "no request attached the shared prefix")
    sp = obs.sparsity.snapshot()
    tokens = N_REQUESTS * NEW_TOKENS
    log(f"[serve] all {N_REQUESTS} requests done with {NEW_TOKENS} tokens "
        f"each (finite logits at every step: no slot was quarantined); "
        f"{m.prefill_tokens} prefill tokens in {m.prefill_dispatches} "
        f"dispatches, {m.decode_tokens} decode tokens in "
        f"{m.decode_dispatches} dispatches, {m.ticks} ticks; prefix hits "
        f"{m.prefix_hits}/{m.prefix_lookups} "
        f"({m.prefill_tokens_skipped} prompt tokens skipped); rho_eff "
        f"prefill {sp['prefill']['rho_eff']:.3f} / decode "
        f"{sp['decode']['rho_eff']:.3f}")
    log(f"[serve] smoke-size wall rate, not a benchmark: {tokens} "
        f"generated tokens in {wall:.2f} s = {tokens / wall:.1f} tok/s "
        f"(includes {clock.seconds - c0:.1f} s of compiles during "
        f"serving)")
    engine.cache = None     # free the pool before the comparison's own

    xla_cfg = dataclasses.replace(
        base, energon=dataclasses.replace(base.energon, impl="mpmrf_block")
    )
    compare_prefill(model, engine.prefill_fn_t, LMModel(xla_cfg), params,
                    prompts, clock)
    log("[path] comparison: XLA mpmrf_block prefill, same weights")
    log(f"[memory] peak_bytes_in_use {peak_bytes(device)} "
        f"({peak_bytes(device) / 2**30:.2f} GiB)")


def four_chips(args, devices):
    """2 replicas, each tensor-parallel over 2 chips, against the
    one-chip engine on device 0: per-uid token streams must be
    bit-identical (DESIGN.md §9)."""
    import jax

    from repro.configs import phi3_mini_3_8b
    from repro.launch.mesh import make_mesh
    from repro.models import LMModel

    check(len(devices) >= 4, f"--four-chips needs 4 devices, have "
          f"{len(devices)}")
    base = phi3_mini_3_8b.CONFIG
    cfg = dataclasses.replace(
        base, energon=dataclasses.replace(base.energon, impl="pallas")
    )
    model = LMModel(cfg)
    t0 = time.perf_counter()
    # built once on device 0, then held on the host so that each phase
    # places its own copy (two copies do not fit one chip)
    params = jax.device_get(jax.jit(model.init)(jax.random.PRNGKey(
        args.seed)))
    log(f"[init] {cfg.name} weights built in "
        f"{time.perf_counter() - t0:.1f} s (held on the host)")
    prompts = make_requests(args.seed, cfg.vocab_size)

    mesh = make_mesh((2, 2), ("data", "model"))
    rep = build_engine(model, params, seed=args.seed, mesh=mesh,
                       replicated=True)
    groups = [set(e.mesh.devices.flat) for e in rep.engines]
    check(not groups[0] & groups[1], "replica submeshes overlap")
    for e, g in zip(rep.engines, groups):
        placed = {d for leaf in jax.tree.leaves((e.params, e.cache))
                  for d in leaf.devices()}
        check(placed == g, f"replica {e.replica_id} state on "
              f"{sorted(d.id for d in placed)}, submesh "
              f"{sorted(d.id for d in g)}")
        log(f"[mesh] replica {e.replica_id}: devices "
            f"{sorted(d.id for d in g)}, params and page pool placed "
            f"there only")
    streams_4, wall_4 = serve(rep, prompts)
    log(f"[serve] 2x2 mesh: {N_REQUESTS} requests done in {wall_4:.2f} s "
        f"(smoke size, not a benchmark); placement "
        f"{dict(sorted(rep.placement.items()))}")
    del rep
    gc.collect()            # both replicas' weights and pools go

    single = build_engine(model, jax.device_put(params, devices[0]),
                          seed=args.seed)
    streams_1, wall_1 = serve(single, prompts)
    log(f"[serve] one chip (device 0): {N_REQUESTS} requests done in "
        f"{wall_1:.2f} s")
    for d in devices[:4]:
        log(f"[memory] device {d.id} peak_bytes_in_use {peak_bytes(d)} "
            f"({peak_bytes(d) / 2**30:.2f} GiB)")
    diverged = [u for u in sorted(streams_1) if streams_1[u] != streams_4[u]]
    for u in diverged:
        pos = next(i for i, (a, b) in enumerate(zip(streams_1[u],
                                                    streams_4[u])) if a != b)
        log(f"[compare] uid {u} diverges at token {pos}: one chip "
            f"{streams_1[u][pos]}, mesh {streams_4[u][pos]}")
    check(not diverged, f"{len(diverged)} of {N_REQUESTS} streams differ "
          f"between the mesh and one chip")
    log(f"[compare] all {N_REQUESTS} per-uid token streams bit-identical "
        f"between the 2x2 mesh and one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh (2 replicas x 2-way TP) "
                         "against the one-chip engine")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"[device] platform {d0.platform}, kind {d0.device_kind}, "
        f"count {len(devices)}")
    if d0.platform != "tpu":
        log("[device] no TPU: this smoke test has no CPU path")
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"[compile] persistent cache: {cache_dir}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args, devices)
        else:
            one_chip(args, clock, d0)
    except SmokeFailure as e:
        log(f"[FAIL] {e}")
        return 1
    log(f"[compile] {clock.seconds:.1f} s compiling or loading programs "
        f"(persistent cache: {clock.hits} hits, {clock.misses} misses); "
        f"whole run {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
