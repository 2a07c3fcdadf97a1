"""Serving runtime: chunked-prefill → sparse-decode engine with a paged
KV cache and continuous batching.

`make_serve_step` builds the jitted one-token decode step — this is the
function the decode_* dry-run shapes lower. `ServeLoop` is a
continuous-batching engine:

* **Paged cache** (default whenever the model supports it): cache state
  is a shared page pool at exactly the decode filter's block
  granularity (`repro.runtime.paged_cache`), addressed through per-slot
  block tables. Admission is *continuous*: a request is admitted the
  moment enough pages are free for its prompt — no single global
  ``max_len`` pad, short requests stop stranding memory long ones need.
  Decode grows a slot one page at a time; on pool exhaustion the
  **youngest** live slot is preempted (pages freed eagerly, request
  requeued at the front and re-prefilled on re-admission). Completion
  frees pages eagerly. All allocator decisions are host-side and
  deterministic (lowest free page first, admission order decides
  youth), so a given trace preempts identically on every run.
* **Admission** runs the model's chunked-prefill path: in-flight
  prompts prefill together, chunk c of all their prompts per jitted
  call. Ragged final chunks and idle slots reuse the same compiled
  shape via position sentinels. Recurrent families (ssm/hybrid) fall
  back to token-by-token admission (and to the unpaged contiguous
  cache — their state is O(1) per slot). Admission *order* is policy:
  preempted requeues first, then priority classes high→low with
  per-tenant round-robin fairness inside a class
  (:class:`~repro.runtime.pending.PendingQueue`); with the defaults
  (priority 0, tenant "") that is exact FIFO. ``admission_lookahead``
  bounds how many queued candidates past a too-big head may admit
  instead of waiting behind it.
* **Hybrid tick** (``scheduler="hybrid"``, the default): each tick
  dispatches a bounded budget — at most *one* prefill chunk covering
  every mid-prefill slot (each at its own chunk offset) interleaved
  with the decode step over decode-state slots — mirroring the paper's
  stall-free two-stage pipeline (§IV, Fig. 9). Admitting a 2k-token
  prompt costs live streams a few chunk-sized stalls instead of one
  ceil(L/C)-dispatch freeze. ``scheduler="sync"`` restores the old
  whole-wave-per-tick admission; the two schedules produce
  **bit-identical per-uid token streams** (per-slot computation is
  batch-neighbour independent and RNG streams depend only on
  (uid, #samples)), so the hybrid/sync choice is purely a latency
  policy — enforced by the hybrid ≡ sync differential tests.
* **Decode** advances every decode-state slot by one token per tick
  (the paper's l=1 pipeline, §IV-D) with per-slot RNG streams and
  per-slot temperature sampling. RNG streams are deterministic in
  (uid, tokens sampled so far), so a preempted request resumes its
  stream exactly. Committed tokens surface immediately through
  ``Request.on_token`` streaming callbacks — callers need not wait for
  drain.
* **Prefix sharing** (paged default): admission looks the prompt up in
  the allocator's token-chunk prefix trie and attaches the longest
  cached prefix by block-table aliasing — those pages' prefill chunks
  never dispatch. Writes into shared or content-registered pages go
  through copy-on-write clones, completed pages outlive their writer
  in a cached set until the pool needs them back, and the whole
  mechanism is invisible to outputs: shared ≡ unshared ≡ unpaged
  streams are bit-identical, greedy and stochastic (DESIGN.md §4).
* **Metrics** track prefill vs decode throughput *and* per-request
  latency: queue wait, time-to-first-token and inter-token latency with
  p50/p95 in ``summary()`` — scheduler changes are measurable, not just
  tok/s. Paged runs also report preemptions, the page watermark, and
  the prefix cache's hit-rate / pages shared / prefill tokens skipped /
  CoW clones.
* **Request lifecycle & fault tolerance** (DESIGN.md §7): requests move
  pending → prefill → decode → {done, cancelled, expired, failed};
  ``cancel(uid)`` and per-request deadlines evict a request at any
  state (queued, live, preempted-requeued) and free/deregister its
  pages correctly under prefix sharing; ``queue_limit`` bounds the
  admission queue (``QueueFull`` backpressure, optional lowest-priority
  /youngest-first load shedding); ``run_until_drained`` raises a
  diagnostic :class:`EngineStalled` on a zero-progress tick instead of
  spinning to ``max_ticks``. Failures are *contained*: non-finite
  logits quarantine only the faulted slot (pages freed, terminal
  ``failed`` state) while healthy slots stream on, and step dispatches
  retry transient errors under a shared ``RetryPolicy``. A seeded
  :class:`~repro.runtime.fault_tolerance.FaultInjector` threads chaos
  through every fault site deterministically, and the
  **fault-invisibility contract** holds on any injected trace: every
  surviving request's output stream is bit-identical to the fault-free
  run (greedy and stochastic, paged and unpaged).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models import LMModel
from repro.observability import Observability
from repro.observability.metrics import DEFAULT_LATENCY_BOUNDS, MetricsRegistry
from repro.runtime.fault_tolerance import (
    FaultInjector,
    RetryPolicy,
    StragglerMonitor,
    TransientStepError,
    retry_step,
)
from repro.runtime.paged_cache import PageAllocator, PagedLayout
from repro.runtime.pending import PendingQueue


class QueueFull(RuntimeError):
    """The bounded admission queue rejected a submission (backpressure):
    the queue is at ``queue_limit`` and load shedding either is disabled
    or found no lower-priority victim to drop."""


class EngineStalled(RuntimeError):
    """The engine made zero progress with work still queued — no token
    committed, no request admitted or reaching a terminal state — and
    would otherwise spin to ``max_ticks``. ``uids`` names the stuck
    requests (queued and live)."""

    def __init__(self, msg: str, uids: List[int]):
        super().__init__(msg)
        self.uids = list(uids)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    #: admission + load-shedding rank: higher admits first and survives
    #: shedding; ties shed youngest first
    priority: int = 0
    #: fairness domain: within a priority class, tenants take turns at
    #: admission (round-robin) so one flooding tenant cannot starve
    #: another; "" (the default) keeps single-tenant traces exact FIFO
    tenant: str = ""
    #: TTL in seconds from submission; the engine evicts the request at
    #: any state once it expires (None = no deadline)
    deadline_s: Optional[float] = None
    #: streaming hook, called as ``on_token(req, tok)`` the moment each
    #: token commits (first token included) — tokens surface as they
    #: are generated, not at drain. Runs on the engine's tick path, so
    #: it must be cheap and must not raise.
    on_token: Optional[Callable[["Request", int], None]] = None
    tokens_out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: lifecycle: pending → prefill → decode (→ preempted → prefill …)
    #: → {done, cancelled, expired, failed, shed}
    state: str = "new"
    #: diagnostic for terminal failures (e.g. "non-finite logits")
    error: Optional[str] = None
    _next_input: int = 0
    _submit_seq: int = -1
    # latency accounting (perf_counter stamps; managed by the engine).
    # Inter-token gaps keep only a bounded tail of raw samples — the
    # full series streams into the engine's registry histogram at
    # commit time, so per-request memory is O(1) in generation length.
    _t_submit: Optional[float] = None
    _t_admit: Optional[float] = None
    _t_first: Optional[float] = None
    _t_last: Optional[float] = None
    _itl: "deque[float]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=512)
    )
    #: decode-attributed inter-token gaps: the wall gap minus the time
    #: the engine spent in prefill phases between the two commits —
    #: "how slow is decode" with scheduler stalls factored out
    _itl_decode: "deque[float]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=512)
    )
    #: engine prefill-time watermark at this request's last commit
    #: (tick-phase attribution for ``_itl_decode``)
    _pf_mark: float = 0.0


def _pct(vals: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(vals), p)) if vals else 0.0


class _CounterAttr:
    """Integer engine counter with plain attribute semantics (read,
    assign, ``+=`` via get+set) that mirrors every write into the
    engine's optional :class:`MetricsRegistry` — the registry is a live
    view, never a copy that could go stale."""

    def __set_name__(self, owner, name):
        self.name = name

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return obj._counters.get(self.name, 0)

    def __set__(self, obj, value):
        obj._counters[self.name] = int(value)
        if obj.registry is not None:
            obj.registry.counter(obj._ns + self.name).value = int(value)


class _GaugeAttr(_CounterAttr):
    """Like :class:`_CounterAttr` but mirrors into a registry gauge
    (which tracks its own peak)."""

    def __set__(self, obj, value):
        obj._counters[self.name] = int(value)
        if obj.registry is not None:
            obj.registry.gauge(obj._ns + self.name).set(int(value))


class EngineMetrics:
    """Engine accounting: prefill and decode measured separately, plus
    per-request latency records and paged-scheduler counters.

    Counters are descriptor attributes over an optional
    :class:`~repro.observability.MetricsRegistry` (``registry=None`` ⇒
    plain host-side ints, zero overhead). Latency retention is bounded:
    ``request_records`` keeps the last ``max_request_records`` raw
    records (older ones have already been folded into the registry's
    streaming histograms at record time), and each request carries only
    a bounded tail of raw inter-token gaps — a week-long run cannot
    grow host memory without bound.

    ``replica`` namespaces every registry name as
    ``replica{r}/serve_*`` so N engine replicas can share one registry
    (or be merged into one with :meth:`MetricsRegistry.merge`) without
    silently summing incompatible gauges — a mesh run's peak pages is
    the per-replica max, never the sum.
    """

    prefill_tokens = _CounterAttr()
    decode_tokens = _CounterAttr()
    prefill_dispatches = _CounterAttr()
    decode_dispatches = _CounterAttr()
    ticks = _CounterAttr()
    preemptions = _CounterAttr()
    peak_pages_in_use = _GaugeAttr()
    # prefix-sharing counters (paged engines with sharing enabled)
    prefix_lookups = _CounterAttr()
    prefix_hits = _CounterAttr()
    pages_shared = _CounterAttr()
    prefill_tokens_skipped = _CounterAttr()
    cow_clones = _CounterAttr()
    # lifecycle / fault counters (DESIGN.md §7)
    retries = _CounterAttr()
    stragglers = _CounterAttr()
    failed_requests = _CounterAttr()
    cancelled_requests = _CounterAttr()
    expired_requests = _CounterAttr()
    shed_requests = _CounterAttr()

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 max_request_records: int = 1024,
                 replica: Optional[int] = None):
        self.replica = replica
        self._ns = (
            "serve_" if replica is None else f"replica{replica}/serve_"
        )
        self.registry = registry
        self._counters: Dict[str, int] = {}
        self.prefill_time = 0.0
        self.decode_time = 0.0
        #: total requests ever recorded (records themselves are capped)
        self.requests_recorded = 0
        self.request_records: "deque[Dict[str, Any]]" = deque(
            maxlen=max_request_records
        )

    @property
    def prefill_tokens_per_sec(self) -> float:
        return self.prefill_tokens / max(self.prefill_time, 1e-9)

    @property
    def decode_tokens_per_sec(self) -> float:
        return self.decode_tokens / max(self.decode_time, 1e-9)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prefix-cache lookups (one per admission) that
        attached at least one shared page."""
        return self.prefix_hits / max(self.prefix_lookups, 1)

    def _hist(self, name: str):
        return self.registry.histogram(self._ns + name,
                                       DEFAULT_LATENCY_BOUNDS)

    def observe_itl(self, dt: float,
                    decode_dt: Optional[float] = None) -> None:
        """Stream one inter-token gap into the registry histograms (the
        bounded raw tails live on the request). ``dt`` is the wall gap
        the caller experienced; ``decode_dt``, when the engine attributes
        tick phases, is the same gap minus time spent in prefill waves —
        the *truthful* decode latency (an admission stall inflates
        ``itl_seconds`` but not ``itl_decode_seconds``)."""
        if self.registry is not None:
            self._hist("itl_seconds").observe(dt)
            if decode_dt is not None:
                self._hist("itl_decode_seconds").observe(decode_dt)

    def sync_registry(self) -> None:
        """Push the float time accumulators into the registry (integer
        counters mirror on every write and need no sync)."""
        if self.registry is None:
            return
        self.registry.gauge(self._ns + "prefill_time_seconds").set(
            self.prefill_time
        )
        self.registry.gauge(self._ns + "decode_time_seconds").set(
            self.decode_time
        )

    def record_request(self, req: Request) -> None:
        """Fold a completed request's latency stamps into the records
        (bounded) and the registry histograms (streaming)."""
        if req._t_submit is None:
            return
        qw = (
            (req._t_admit - req._t_submit)
            if req._t_admit is not None else 0.0
        )
        ttft = (
            (req._t_first - req._t_submit)
            if req._t_first is not None else 0.0
        )
        rec = {
            "uid": req.uid, "queue_wait": qw, "ttft": ttft,
            "itl": list(req._itl),
            "itl_decode": list(req._itl_decode),
        }
        self.request_records.append(rec)
        self.requests_recorded += 1
        if self.registry is not None:
            self._hist("queue_wait_seconds").observe(qw)
            self._hist("ttft_seconds").observe(ttft)

    def latency_stats(self) -> Dict[str, float]:
        """p50/p95 of queue wait, TTFT and inter-token latency (seconds)
        over the retained request records (zeros when none recorded)."""
        qw = [r["queue_wait"] for r in self.request_records]
        tt = [r["ttft"] for r in self.request_records]
        itl = [x for r in self.request_records for x in r["itl"]]
        itl_d = [
            x for r in self.request_records
            for x in r.get("itl_decode", ())
        ]
        return {
            "requests": float(self.requests_recorded),
            "queue_wait_p50": _pct(qw, 50), "queue_wait_p95": _pct(qw, 95),
            "ttft_p50": _pct(tt, 50), "ttft_p95": _pct(tt, 95),
            "itl_p50": _pct(itl, 50), "itl_p95": _pct(itl, 95),
            "itl_decode_p50": _pct(itl_d, 50),
            "itl_decode_p95": _pct(itl_d, 95),
        }

    def summary(self) -> str:
        s = (
            f"prefill {self.prefill_tokens} tok / "
            f"{self.prefill_dispatches} calls "
            f"({self.prefill_tokens_per_sec:.1f} tok/s) | "
            f"decode {self.decode_tokens} tok / "
            f"{self.decode_dispatches} calls "
            f"({self.decode_tokens_per_sec:.1f} tok/s) | "
            f"{self.ticks} ticks"
        )
        if self.request_records:
            st = self.latency_stats()
            s += (
                f" | queue p50/p95 {st['queue_wait_p50'] * 1e3:.1f}/"
                f"{st['queue_wait_p95'] * 1e3:.1f} ms"
                f" | ttft p50/p95 {st['ttft_p50'] * 1e3:.1f}/"
                f"{st['ttft_p95'] * 1e3:.1f} ms"
                f" | itl p50/p95 {st['itl_p50'] * 1e3:.1f}/"
                f"{st['itl_p95'] * 1e3:.1f} ms"
            )
        if self.peak_pages_in_use:
            s += (
                f" | {self.preemptions} preemptions, "
                f"peak {self.peak_pages_in_use} pages"
            )
        if self.prefix_lookups:
            s += (
                f" | prefix hit-rate {self.prefix_hit_rate:.2f} "
                f"({self.pages_shared} pages shared, "
                f"{self.prefill_tokens_skipped} prefill tok skipped, "
                f"{self.cow_clones} CoW clones)"
            )
        evicted = (self.failed_requests + self.cancelled_requests
                   + self.expired_requests + self.shed_requests)
        if evicted or self.retries or self.stragglers:
            s += (
                f" | lifecycle: {self.retries} retries, "
                f"{self.stragglers} stragglers, "
                f"{self.failed_requests} failed, "
                f"{self.cancelled_requests} cancelled, "
                f"{self.expired_requests} expired, "
                f"{self.shed_requests} shed"
            )
        return s


def make_serve_step(
    model: LMModel,
    mesh: Optional[Mesh] = None,
    max_len: int = 0,
    batch: int = 0,
    num_pages: int = 0,
):
    """Jitted ``(params, cache, inputs, cache_index) -> (logits, cache)``.

    ``num_pages > 0`` builds the sharded step for the *paged* cache
    layout (page-pool pspecs; the block table rides ``inputs`` and stays
    replicated)."""

    def step(params, cache, inputs, cache_index):
        return model.decode_step(params, cache, inputs, cache_index)

    if mesh is None:
        return jax.jit(step, donate_argnums=(1,))

    assert (max_len > 0 and batch > 0) or num_pages > 0, \
        "mesh-sharded serve needs shapes"
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = shd.param_shardings(params_shapes, mesh)
    if num_pages > 0:
        cache_shapes = jax.eval_shape(
            lambda: model.init_paged_cache(
                num_pages, max_len=max_len if max_len > 0 else None
            )
        )
        c_shard = shd.paged_cache_shardings(
            cache_shapes, mesh, model.cfg.energon.decode_key_block
        )
    else:
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(batch=batch, max_len=max_len)
        )
        c_shard = shd.cache_shardings(cache_shapes, mesh)
    return jax.jit(
        step,
        in_shardings=(p_shard, c_shard, None, None),
        out_shardings=(None, c_shard),
        donate_argnums=(1,),
    )


def make_prefill_step(model: LMModel):
    """Jitted chunked-prefill
    ``(params, cache, inputs, cache_index) -> (logits, cache)``, or None
    when the family has no multi-token prefill path."""
    if not getattr(model, "supports_prefill", False):
        return None
    return jax.jit(model.prefill, donate_argnums=(1,))


def sample_tokens(
    logits: jax.Array, temps: jax.Array, keys: jax.Array
) -> jax.Array:
    """Vectorized per-slot sampling.

    logits ``[B, V]``, temps ``[B]`` (≤ 0 ⇒ greedy), keys ``[B, 2]`` —
    each slot draws from its own RNG stream, so one request's sampling is
    independent of its batch neighbours.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    drawn = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0.0, drawn, greedy)


@jax.jit
def _sample_wave(
    logits: jax.Array, temps: jax.Array, keys: jax.Array, mask: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-and-sample with per-slot streams: only ``mask`` slots' RNG
    keys advance, so admitting a request never perturbs a live
    neighbour's stream. ``logits [B, V]``; returns (tokens, new_keys,
    finite) where ``finite[b]`` is False when slot b's logits contain a
    NaN/Inf — the per-slot quarantine signal (DESIGN.md §7). The flag is
    a separate output: healthy slots' token computation is untouched, so
    adding the guard cannot perturb the bit-identical stream contracts."""
    ks = jax.vmap(jax.random.split)(keys)          # [B, 2, 2]
    new_keys = jnp.where(mask[:, None], ks[:, 0], keys)
    finite = jnp.all(jnp.isfinite(logits), axis=-1)
    return sample_tokens(logits, temps, ks[:, 1]), new_keys, finite


def _sample_step(
    logits: jax.Array, temps: jax.Array, keys: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode-tick sampling: `_sample_wave` over the ``active`` slots.
    Only active slots' keys advance — under the hybrid scheduler a
    mid-prefill slot shares the batch with decoding neighbours, and its
    admission-time key must survive those ticks untouched or its first
    token would diverge from the synchronous schedule.
    ``logits [B, 1, V]``; returns (tokens, new_keys, finite)."""
    return _sample_wave(logits[:, -1, :], temps, keys, active)


@jax.jit
def _poison_logits(logits: jax.Array, mask: jax.Array) -> jax.Array:
    """Chaos hook: replace ``mask`` slots' logits with NaN (fault
    injection for the quarantine guard). ``logits [B, ..., V]``,
    ``mask [B]``. Only traced when an injector actually poisons a tick —
    fault-free runs never dispatch it."""
    shape = (-1,) + (1,) * (logits.ndim - 1)
    return jnp.where(mask.reshape(shape), jnp.nan, logits)


@jax.jit
def _advance_key(key: jax.Array, n: jax.Array) -> jax.Array:
    """Advance an RNG key by ``n`` `_sample_wave` splits (key_{i+1} =
    split(key_i)[0]) in one dispatch."""
    return jax.lax.fori_loop(
        0, n, lambda _, k: jax.random.split(k)[0], key
    )


@dataclasses.dataclass
class _PrefillJob:
    """One slot's in-flight chunked prefill under the hybrid scheduler:
    the admission tick allocates pages and creates the job; each
    subsequent tick's single chunk wave advances ``pos`` by one chunk
    until the job covers ``seq`` — then the slot samples its first
    token (fresh jobs), registers its prefix and flips to decode,
    exactly as the synchronous wave would have."""

    req: Request
    #: full token sequence being written (prompt, plus prior
    #: generations for a resumed request)
    seq: List[int]
    resumed: bool
    #: leading tokens restored by prefix-cache attach (never dispatched)
    skip: int
    #: next absolute token offset to prefill (starts at ``skip``)
    pos: int


class ServeLoop:
    """Continuous-batching chunked-prefill / sparse-decode engine over a
    paged (default when supported) or contiguous KV cache."""

    def __init__(
        self,
        model: LMModel,
        params,
        *,
        batch_slots: int = 8,
        max_len: int = 512,
        eos_token: int = 0,
        rng: Optional[jax.Array] = None,
        prefill_chunk: int = 64,
        scheduler: str = "hybrid",
        admission_lookahead: int = 0,
        paged: Optional[bool] = None,
        num_pages: Optional[int] = None,
        prefix_sharing: Optional[bool] = None,
        queue_limit: Optional[int] = None,
        load_shedding: bool = False,
        default_deadline_s: Optional[float] = None,
        fault_injector: Optional[FaultInjector] = None,
        retry_policy: Optional[RetryPolicy] = None,
        audit: bool = False,
        stall_patience: Optional[int] = None,
        observability: Optional[Observability] = None,
        mesh: Optional[Mesh] = None,
        replica_id: Optional[int] = None,
    ):
        self.model = model
        self.params = params
        self.mesh = mesh
        self.replica_id = replica_id
        self.batch_slots = batch_slots
        self.paged = model.supports_paged if paged is None else bool(paged)
        if self.paged and not model.supports_paged:
            raise ValueError(
                "paged serving needs an attention family with "
                "decode_key_block > 0 and a non-dense impl"
            )
        # Prefix sharing rides the paged pool (block-table aliasing is
        # the attach mechanism); default on whenever paged. Sharing is
        # invisible to outputs — shared and unshared engines produce
        # bit-identical streams — so the flag only trades host-side
        # bookkeeping for skipped prefill work.
        if prefix_sharing is None:
            prefix_sharing = self.paged
        if prefix_sharing and not self.paged:
            raise ValueError("prefix_sharing requires the paged cache")
        self.sharing = bool(prefix_sharing)
        # Cache rows are rounded up to whole decode key blocks (the
        # block path must never silently fall back to the row path);
        # the engine's sentinels/limits must use the same rounded value
        # or sentinel positions would land on real cache rows. Paged
        # mode additionally rounds for row-granular impls: pages are
        # decode_key_block wide regardless of the filter granularity.
        rows = model.decode_cache_len(max_len)
        if self.paged:
            bk = model.cfg.energon.decode_key_block
            rows = max(-(-rows // bk), 2) * bk
        self.max_len = rows
        self.eos = eos_token
        self.prefill_chunk = max(1, min(prefill_chunk, max_len))
        if scheduler not in ("hybrid", "sync"):
            raise ValueError(
                f"scheduler must be 'hybrid' or 'sync', got {scheduler!r}"
            )
        #: "hybrid" (default): one prefill chunk wave per tick,
        #: interleaved with decode. "sync": the admission tick runs the
        #: whole prefill wave before decode (the pre-hybrid schedule;
        #: kept for differential tests and latency A/Bs — per-uid token
        #: streams are bit-identical either way).
        self.scheduler = scheduler
        self._hybrid = scheduler == "hybrid"
        #: queued candidates the admission pass may *fail* on before
        #: giving up for the tick: 0 = strict policy order (a too-big
        #: queue head blocks everyone behind it, the old behavior);
        #: k > 0 lets up to k smaller requests behind it admit.
        self.admission_lookahead = max(0, int(admission_lookahead))
        #: slot → in-flight hybrid prefill job
        self._prefill_jobs: Dict[int, _PrefillJob] = {}
        self._base_rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.step_fn = jax.jit(model.decode_step, donate_argnums=(1,))
        self.prefill_fn = make_prefill_step(model)
        # Observability is strictly additive: without it (or with
        # device_telemetry off) the engine dispatches the exact step
        # functions above — the telemetry variants are *separate* jitted
        # functions, so the disabled path's HLO is byte-identical to an
        # engine built before this layer existed.
        self.obs = observability
        self._telemetry = (
            observability is not None and observability.device_telemetry
        )
        self.step_fn_t = None
        self.prefill_fn_t = None
        if self._telemetry:
            self.step_fn_t = jax.jit(
                functools.partial(model.decode_step, telemetry=True),
                donate_argnums=(1,),
            )
            if getattr(model, "supports_prefill", False):
                self.prefill_fn_t = jax.jit(
                    functools.partial(model.prefill, telemetry=True),
                    donate_argnums=(1,),
                )
        if self.paged:
            bk = model.cfg.energon.decode_key_block
            mb = rows // bk
            if num_pages is None:
                # safe default: worst case fits with zero preemptions;
                # callers oversubscribe explicitly (num_pages < B·mb)
                # to realize the HBM saving.
                num_pages = batch_slots * mb
            self.layout = PagedLayout(
                num_pages=num_pages, page_size=bk,
                max_blocks=mb, batch_slots=batch_slots,
            )
            self.allocator = PageAllocator(self.layout)

            def new_pool():
                return model.init_paged_cache(num_pages, max_len=self.max_len)

            if mesh is None:
                self.cache = new_pool()
            else:
                # built in place on the mesh: a pool made on the default
                # device first would have to fit there beside whatever
                # that device already holds
                self.cache = jax.jit(
                    new_pool,
                    out_shardings=shd.paged_cache_shardings(
                        jax.eval_shape(new_pool), mesh, bk
                    ),
                )()
            self._reset_pages_fn = jax.jit(
                model.reset_pages, donate_argnums=(0,)
            )
        else:
            self.layout = None
            self.allocator = None
            self.cache = model.init_cache(batch_slots, max_len)
        if mesh is not None:
            self._install_mesh(mesh)
        self.cache_index = jnp.zeros((batch_slots,), jnp.int32)
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.slot_keys = jax.random.split(self._base_rng, batch_slots)
        self._temps = np.zeros((batch_slots,), np.float32)
        self._lengths = np.zeros((batch_slots,), np.int64)  # host mirror
        self._slot_order: List[Optional[int]] = [None] * batch_slots
        self._admit_seq = itertools.count()
        self.pending = PendingQueue()
        self.completed: List[Request] = []
        self.metrics = EngineMetrics(
            registry=observability.registry if observability else None,
            replica=replica_id,
        )
        # --- lifecycle / fault-tolerance state (DESIGN.md §7) ---------
        #: bounded admission queue: `submit` raises QueueFull (or sheds
        #: a lower-priority victim) past this many *queued* requests.
        #: Preemption requeues bypass the limit — evicting a live slot
        #: must never be able to fail.
        self.queue_limit = queue_limit
        self.load_shedding = bool(load_shedding)
        self.default_deadline_s = default_deadline_s
        self._injector = fault_injector
        self.retry_policy = retry_policy
        #: per-tick allocator self-check (promotes the allocator fuzzer's
        #: invariants into the engine; opt-in — O(pool) host work/tick)
        self.audit = bool(audit)
        #: consecutive zero-progress ticks tolerated before
        #: `run_until_drained` raises EngineStalled. Fault-free, a
        #: zero-progress tick is provably permanent (the deterministic
        #: allocator re-decides identically), so 1 suffices; under
        #: injection a denied allocation is recoverable next tick, so
        #: the default widens.
        self.stall_patience = (
            stall_patience if stall_patience is not None
            else (1 if fault_injector is None else 32)
        )
        self._submit_seq = itertools.count()
        #: requests that reached a non-`done` terminal state
        #: (cancelled / expired / failed / shed) — kept separate from
        #: `completed` so drain semantics are unchanged.
        self.terminated: List[Request] = []
        self.straggler = StragglerMonitor()
        # hook the allocator's eviction site and the injector's fault
        # sites into the event trace
        if observability is not None:
            if self.allocator is not None:
                self.allocator.tracer = observability.trace
            if fault_injector is not None:
                fault_injector.tracer = observability.trace

    def _emit(self, name: str, **kw):
        """Emit a trace event iff observability is attached (the
        disabled path is one attribute check)."""
        if self.obs is not None:
            self.obs.trace.emit(name, **kw)

    def _obs_tick_end(self):
        """Per-tick series + float-gauge sync at every tick exit."""
        if self.obs is None:
            return
        self.metrics.sync_registry()
        self.obs.record_tick_series(
            self.metrics.ticks,
            pool_occupancy=(
                self.allocator.pages_in_use if self.paged else 0
            ),
            queue_depth=len(self.pending),
            live_slots=sum(s is not None for s in self.slots),
        )

    @property
    def ticks(self) -> int:
        return self.metrics.ticks

    # --- API -----------------------------------------------------------
    def submit(self, req: Request):
        # A prompt fits iff the (rounded-up) cache can hold every row it
        # writes: a length-L prompt prefills L rows and its first token
        # is sampled straight off the prefill logits, so L == rows is
        # admissible (the per-request limit in _commit_token then caps
        # generation so decode writes never pass the last row). The old
        # check compared against max_len pre-headroom accounting and
        # rejected prompts the rounded cache could actually hold.
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens does not fit the "
                f"{self.max_len} cache rows"
            )
        if req._t_submit is None:
            req._t_submit = time.perf_counter()
        if req.deadline_s is None:
            req.deadline_s = self.default_deadline_s
        req._submit_seq = next(self._submit_seq)
        req.state = "pending"
        if (
            self.queue_limit is not None
            and len(self.pending) >= self.queue_limit
        ):
            # Backpressure. With shedding on, the victim is the queued
            # request that least deserves its place: lowest priority,
            # ties broken youngest-first. A newcomer that does not
            # outrank the victim *is* the youngest of its class, so it
            # is the one shed — rejected with QueueFull.
            victim = None
            if self.load_shedding and self.pending:
                victim = self.pending.shed_victim()
            if victim is None or victim.priority >= req.priority:
                raise QueueFull(
                    f"admission queue at limit ({self.queue_limit}); "
                    f"request uid={req.uid} rejected"
                )
            self.pending.remove(victim.uid)
            self._finish_terminal(
                victim, "shed",
                f"load-shed for higher-priority uid={req.uid}",
            )
        self.pending.append(req)

    def cancel(self, uid: int) -> bool:
        """Cancel a request at any state — queued, live (prefilling or
        decoding), or preempted-and-requeued. Its pages are freed and
        deregistered under the allocator's normal rules (shared pages
        drop a reference, content-registered pages retire to the cached
        set, the prefix trie stays attachable), so survivors' streams
        are untouched. Returns False when ``uid`` is unknown or already
        terminal."""
        req = self.pending.remove(uid)
        if req is not None:
            self._finish_terminal(req, "cancelled")
            return True
        for i, req in enumerate(self.slots):
            if req is not None and req.uid == uid:
                self._evict_slot(i, "cancelled")
                return True
        return False

    # --- lifecycle internals -------------------------------------------
    def _finish_terminal(
        self, req: Request, state: str, error: Optional[str] = None,
        slot: Optional[int] = None,
    ):
        """Move a request to a non-`done` terminal state. ``done`` stays
        False — it means "completed normally"; ``state`` is the
        authoritative lifecycle field."""
        req.state = state
        if error is not None:
            req.error = error
        self.terminated.append(req)
        counter = {
            "failed": "failed_requests",
            "cancelled": "cancelled_requests",
            "expired": "expired_requests",
            "shed": "shed_requests",
        }[state]
        setattr(self.metrics, counter, getattr(self.metrics, counter) + 1)
        event = {
            "failed": "quarantine",
            "cancelled": "cancel",
            "expired": "expire",
            "shed": "shed",
        }[state]
        self._emit(event, slot=slot, uid=req.uid, error=error or "")

    def _evict_slot(self, i: int, state: str, error: Optional[str] = None):
        """Terminal eviction of a live slot (cancel / expire /
        quarantine): frees its pages eagerly, like completion does."""
        req = self.slots[i]
        self._release_slot(i)
        self._finish_terminal(req, state, error, slot=i)

    def _expire_deadlines(self):
        """Evict every request whose TTL has lapsed — at any state.
        Queued requests (including preempted-requeued ones, whose clock
        never reset) pop off the queue's deadline heap — O(expired),
        not O(queue) per tick; live slots (few) are scanned directly
        and evicted with their pages freed, mid-prefill included."""
        now = time.perf_counter()

        def expired(req: Request) -> bool:
            return (
                req.deadline_s is not None
                and req._t_submit is not None
                and now - req._t_submit > req.deadline_s
            )

        for req in self.pending.pop_expired(now):
            self._finish_terminal(req, "expired", "deadline exceeded")
        for i in range(self.batch_slots):
            if self.slots[i] is not None and expired(self.slots[i]):
                self._evict_slot(i, "expired", "deadline exceeded")

    def _install_mesh(self, mesh: Mesh) -> None:
        """Pin params/cache to ``mesh`` and rebuild the jitted step
        functions with explicit shardings (serve-TP, DESIGN.md §9).

        Params stay **replicated**: sharding weights would reassociate
        the output-projection contraction (a cross-device psum) and
        break the bit-identity contract against the single-device run.
        Only the page-pool leaves shard — KV heads over 'model' when
        divisible, page-aligned pool rows otherwise — and the fused
        kernels engage their shard_map path off the active mesh at
        trace time: each device streams only its head-shard's survivor
        blocks, then all-gathers the exact per-head outputs, so
        everything downstream computes replicated and mesh streams stay
        bit-identical to the single-device paged run.
        """
        if not self.paged:
            raise ValueError("mesh serving requires the paged cache")
        if "data" in mesh.axis_names and mesh.shape["data"] > 1:
            # The data axis is the *replica* axis: one engine = one
            # replica. Letting a lone engine batch-shard its slots over
            # 'data' would change XLA's local shapes (and therefore
            # reduction vectorization) and break the bit-identity
            # contract against the single-device run.
            raise ValueError(
                "ServeLoop takes a TP-only mesh (data axis == 1); use "
                "ReplicatedServeLoop to span the data axis"
            )
        repl = NamedSharding(mesh, P())
        p_spec = jax.tree.map(lambda _: repl, self.params)
        c_spec = shd.paged_cache_shardings(
            self.cache, mesh, self.layout.page_size
        )
        self.params = jax.device_put(self.params, p_spec)
        self.cache = jax.device_put(self.cache, c_spec)
        model = self.model

        # Each wrapper below is a *fresh function object per engine*:
        # replica engines share the model instance, and jitting the
        # bound method directly would share the jaxpr trace cache
        # across replicas — the first replica's trace bakes its
        # submesh into the model's internal shard_maps, and every
        # later replica would lower that alien mesh against its own
        # devices ("incompatible devices for jitted computation").
        def _step(params, cache, inputs, cache_index):
            return model.decode_step(params, cache, inputs, cache_index)

        self.step_fn = jax.jit(
            _step,
            in_shardings=(p_spec, c_spec, None, None),
            out_shardings=(None, c_spec),
            donate_argnums=(1,),
        )
        if self.prefill_fn is not None:
            def _prefill(params, cache, inputs, cache_index):
                return model.prefill(params, cache, inputs, cache_index)

            self.prefill_fn = jax.jit(
                _prefill,
                in_shardings=(p_spec, c_spec, None, None),
                out_shardings=(None, c_spec),
                donate_argnums=(1,),
            )
        if self.step_fn_t is not None:
            def _step_t(params, cache, inputs, cache_index):
                return model.decode_step(
                    params, cache, inputs, cache_index, telemetry=True
                )

            self.step_fn_t = jax.jit(
                _step_t,
                in_shardings=(p_spec, c_spec, None, None),
                out_shardings=(None, c_spec, None),
                donate_argnums=(1,),
            )
        if self.prefill_fn_t is not None:
            def _prefill_t(params, cache, inputs, cache_index):
                return model.prefill(
                    params, cache, inputs, cache_index, telemetry=True
                )

            self.prefill_fn_t = jax.jit(
                _prefill_t,
                in_shardings=(p_spec, c_spec, None, None),
                out_shardings=(None, c_spec, None),
                donate_argnums=(1,),
            )

        def _reset(cache, mask):
            return model.reset_pages(cache, mask)

        self._reset_pages_fn = jax.jit(
            _reset,
            in_shardings=(c_spec, None),
            out_shardings=c_spec,
            donate_argnums=(0,),
        )

    def _dispatch(self, fn, *args):
        """Dispatch with the engine's mesh active (trace-time signal for
        the fused kernels' shard_map path — and for nothing else: the
        mesh is restored before returning so N replica engines on
        disjoint submeshes can interleave ticks on one host)."""
        if self.mesh is None:
            return self._dispatch_impl(fn, *args)
        prev = shd.get_active_mesh()
        shd.set_active_mesh(self.mesh)
        try:
            return self._dispatch_impl(fn, *args)
        finally:
            shd.set_active_mesh(prev)

    def _dispatch_impl(self, fn, *args):
        """One jitted step dispatch under the engine's RetryPolicy.

        The injector's fault site sits *before* the jitted call: an
        injected :class:`TransientStepError` raises while the donated
        cache buffer is still intact, so a retry re-dispatches against
        unchanged state and the fault is invisible to outputs. (A fault
        *after* donation could not be retried this way — the old cache
        is gone.) Fault-free engines with no explicit policy skip the
        wrapper entirely."""
        if self._injector is None and self.retry_policy is None:
            return fn(*args)
        first = [True]

        def attempt():
            fresh, first[0] = first[0], False
            if self._injector is not None and \
                    self._injector.step_fault(fresh):
                raise TransientStepError("injected step fault")
            return fn(*args)

        def note(attempt_no, exc):
            self.metrics.retries += 1
            self._emit("retry", site="step_dispatch", attempt=attempt_no)

        policy = self.retry_policy or RetryPolicy(base_delay=0.0)
        return retry_step(attempt, policy=policy, on_retry=note)

    def _ensure_capacity_inj(self, slot: int, n_tokens: int):
        """``allocator.ensure_capacity`` with the injector's allocation
        fault site. Consulted only when the call would actually allocate
        (denying a no-op would fabricate evictions out of thin air); an
        injected denial surfaces exactly like pool exhaustion — wait at
        admission, preempt at decode — so recovery exercises the real
        paths."""
        if (
            self._injector is not None
            and self.layout.blocks_for(max(n_tokens, 1))
                > int(self.allocator.n_blocks[slot])
            and self._injector.alloc_failure()
        ):
            return None
        return self.allocator.ensure_capacity(slot, n_tokens)

    def _injected_preempt_storm(self):
        """Chaos site: force-preempt the N youngest live slots this
        tick. Recovery is the engine's ordinary preemption machinery —
        requeue at the head, re-prefill, resume the RNG stream — so the
        storm must be invisible to every stream."""
        live = [
            i for i in range(self.batch_slots) if self.slots[i] is not None
        ]
        n = self._injector.preempt_storm(len(live))
        for _ in range(n):
            victim = self._preempt_victim()
            if victim is None:
                break
            self._preempt(victim)

    def _preempt_victim(self) -> Optional[int]:
        """Deterministic preemption policy: lowest priority class
        first, ties broken youngest (latest admission) — with uniform
        priorities this is exactly the old youngest-first rule. Both
        decode-growth exhaustion and injected storms use it, and a
        mid-prefill slot is as evictable as a decoding one (its job is
        dropped and it re-admits fresh)."""
        return max(
            (j for j in range(self.batch_slots)
             if self.slots[j] is not None),
            key=lambda j: (-self.slots[j].priority, self._slot_order[j]),
            default=None,
        )

    def _replayed_key(self, uid: int, n_sampled: int) -> jax.Array:
        """Per-request RNG stream, deterministic in (uid, #samples):
        `_sample_wave` advances a slot's key once per sample, so
        re-admitting a preempted request replays the same number of
        splits and its stochastic continuation is unchanged. The replay
        is one jitted fori_loop dispatch, not n tiny splits."""
        return _advance_key(
            jax.random.fold_in(self._base_rng, uid), jnp.int32(n_sampled)
        )

    def _device_block_table(self) -> jnp.ndarray:
        return self.allocator.table_device()

    def _reset_pages(self, pages: List[int]):
        """Zero freshly allocated pages before first use (a reused page
        must not leak its previous occupant's rows or absmax)."""
        return self._reset_pages_fn(
            self.cache, self.allocator.page_reset_mask(pages)
        )

    def _plan_prefix_attach(self, seq_tokens: List[int], resumed: bool):
        """Longest-cached-prefix plan for one admission.

        Returns ``(skip, attach_pages, clone_src)``: the number of
        leading tokens whose prefill is skipped entirely, the full
        shared pages to attach by block-table aliasing, and — when the
        skip boundary lands mid-page — the shared page the slot must
        clone (copy-on-write) because the ragged tail chunk will write
        into it.

        Skip geometry: recomputed chunks must stay on the global
        ``prefill_chunk`` grid — MP-MRF prefill selection pools scores
        per query block, so a shifted chunk would change the pooled
        planes and break the shared ≡ unshared / preempted ≡ ample
        bit-exactness contracts. A *fresh* request additionally caps
        the skip at L−1: its last prompt token's logits seed sampling.
        A *resumed* request needs no logits (its pending token is
        already sampled), so when the match covers everything it wrote
        it skips prefill outright, grid notwithstanding — a pure table
        aliasing restore recomputes nothing.
        """
        matched = self.allocator.match_prefix(seq_tokens)
        if not matched:
            return 0, [], None
        bk = self.layout.page_size
        if resumed:
            skip = min(len(matched) * bk, len(seq_tokens))
            if skip < len(seq_tokens):
                # some re-prefill remains: its chunks must sit on the
                # same grid the original admission used (only a fully
                # covered restore — pure table aliasing, no recompute —
                # may end off-grid).
                skip = (skip // self.prefill_chunk) * self.prefill_chunk
        else:
            skip = min(len(matched) * bk, len(seq_tokens) - 1)
            skip = (skip // self.prefill_chunk) * self.prefill_chunk
        if skip <= 0:
            return 0, [], None
        n_attach = skip // bk
        clone_src = matched[n_attach] if skip % bk else None
        return skip, matched[:n_attach], clone_src

    def _admit(self):
        """Fill free slots from the queue in admission-policy order.

        Candidate selection is the queue's policy (preempted requeues,
        then priority classes with tenant round-robin); the pass
        examines at most ``free_slots + admission_lookahead`` queued
        candidates and tolerates ``admission_lookahead`` allocation
        failures before giving up for the tick — lookahead 0 (default)
        reproduces the old strict order, where a head too big for the
        free pool blocks everything behind it.

        Under the synchronous scheduler the whole chunked prefill wave
        runs here; under the hybrid scheduler this only *allocates*
        (pages, slot, RNG key) and enqueues a :class:`_PrefillJob` —
        `_prefill_tick` then advances every job one chunk per tick.
        """
        chunked, sequential = [], []
        admitted_slots: List[int] = []
        new_pages: List[int] = []
        now = time.perf_counter()
        free = [
            i for i in range(self.batch_slots) if self.slots[i] is None
        ]
        candidates = (
            self.pending.admission_order(
                len(free) + self.admission_lookahead
            )
            if free and self.pending else []
        )
        misses = 0
        cand_iter = iter(candidates)
        for i in free:
            req = next(cand_iter, None)
            admitted = False
            while req is not None:
                if self._try_admit(i, req, now, new_pages,
                                   admitted_slots, chunked, sequential):
                    admitted = True
                    break
                misses += 1
                if misses > self.admission_lookahead:
                    break
                req = next(cand_iter, None)
            if not admitted:
                break
        if self.paged:
            # paged slot hygiene happens per *page*, at allocation:
            # fresh pages are zeroed, attached pages carry live shared
            # data (never zeroed), CoW destinations were overwritten
            # whole by their (already applied) clones — zeroing them
            # would destroy the copy, so they are never in new_pages.
            if new_pages:
                self.cache = self._reset_pages(new_pages)
            # sync the watermark here too: a request whose prompt fills
            # its whole allowance can complete straight off the prefill
            # wave, never reaching tick()'s decode-branch sync.
            self.metrics.peak_pages_in_use = \
                self.allocator.peak_pages_in_use
        elif admitted_slots:
            # recurrent families: admitted slots must not inherit their
            # previous occupants' accumulated state (no-op for
            # positional KV caches); one combined-mask pass per wave.
            reset_mask = np.zeros((self.batch_slots,), bool)
            reset_mask[admitted_slots] = True
            self.cache = self.model.reset_decode_slots(
                self.cache, jnp.asarray(reset_mask)
            )
        if sequential:
            # recurrent-family admission stays synchronous under both
            # schedulers: token-by-token restore has no chunk structure
            # to interleave
            self._sequential_prefill_wave(sequential)
        if chunked:
            if self._hybrid:
                self._enqueue_prefill_jobs(chunked)
            else:
                self._prefill_slots(chunked)

    def _try_admit(self, i: int, req: Request, now: float,
                   new_pages: List[int], admitted_slots: List[int],
                   chunked: List, sequential: List) -> bool:
        """Attempt to admit ``req`` into free slot ``i``: prefix attach
        then page allocation, with rollback. On pool exhaustion every
        acquired reference is released, the request stays queued, and
        the caller's lookahead budget decides whether another candidate
        gets a try. Returns True iff ``req`` now owns the slot."""
        resumed = bool(req.tokens_out)
        # a resumed (preempted) request re-prefills everything it
        # had written: prompt + generated tokens minus the pending
        # one (tokens_out[-1] is its _next_input, not yet written).
        seq_tokens = (
            req.prompt + req.tokens_out[:-1] if resumed else req.prompt
        )
        skip = 0
        if self.paged:
            attach, clone_src = [], None
            use_chunked = resumed or (
                self.prefill_fn is not None and len(req.prompt) > 1
            )
            if self.sharing and use_chunked and len(seq_tokens) > 1:
                skip, attach, clone_src = self._plan_prefix_attach(
                    seq_tokens, resumed
                )
            # attach-then-alloc with rollback: shared pages are
            # refcounted *before* fresh allocation so an eviction
            # can never reclaim a page this admission depends on;
            # on pool exhaustion every acquired reference is
            # released and the request stays queued.
            pair = None
            for p in attach:
                self.allocator.share(i, p)
            if clone_src is not None:
                self.allocator.share(i, clone_src)
                pair = self.allocator.cow(i, len(attach))
                if pair is not None:
                    # copy *now*: the cow just dropped the source
                    # to refcount 0 (cached), so a later allocation
                    # in this very pass may evict it into new_pages
                    # — and the end-of-admission zeroing must never
                    # beat the clone to its source.
                    self.cache = self.model.clone_pages(
                        self.cache, [pair[0]], [pair[1]]
                    )
            pages = None
            if clone_src is None or pair is not None:
                pages = self._ensure_capacity_inj(
                    i, max(len(seq_tokens), 1)
                )
            if pages is None:
                # not enough free pages for this candidate
                self.allocator.free_slot(i)
                return False
            new_pages += pages
            if self.sharing and use_chunked and len(seq_tokens) > 1:
                self.metrics.prefix_lookups += 1
            if pair is not None:
                self.metrics.cow_clones += 1
                self._emit("cow_clone", slot=i, uid=req.uid,
                           src=pair[0], dst=pair[1], site="admit")
            if skip > 0:
                self.metrics.prefix_hits += 1
                self.metrics.pages_shared += len(attach) + (
                    clone_src is not None
                )
                self.metrics.prefill_tokens_skipped += skip
        self.pending.remove(req.uid)
        self.pending.note_admitted(req)
        self.slots[i] = req
        req.state = "prefill"
        self._slot_order[i] = next(self._admit_seq)
        self._emit("admit", slot=i, uid=req.uid, resumed=resumed,
                   prompt_len=len(seq_tokens), skip=skip)
        if req._t_admit is None:
            req._t_admit = now
        # per-request RNG stream: deterministic in uid (and, for
        # resumed requests, in how many tokens were sampled), not in
        # what else happens to share the batch.
        self.slot_keys = self.slot_keys.at[i].set(
            self._replayed_key(req.uid, len(req.tokens_out))
        )
        self._temps[i] = req.temperature
        self.cache_index = self.cache_index.at[i].set(0)
        self._lengths[i] = 0
        admitted_slots.append(i)
        if resumed:
            if seq_tokens:
                chunked.append((i, req, seq_tokens, True, skip))
            else:
                # nothing was ever written; _next_input resumes it and
                # there is no prefill phase to run
                req.state = "decode"
        elif self.prefill_fn is not None and len(req.prompt) > 1:
            chunked.append((i, req, seq_tokens, False, skip))
        else:
            sequential.append((i, req))
        return True

    def _enqueue_prefill_jobs(self, admitted):
        """Hybrid admission tail: turn this tick's admissions into
        per-slot :class:`_PrefillJob` state instead of running the wave
        inline. A fully-covered resumed slot (prefix attach restored
        everything) has no chunks to run and completes immediately —
        pure block-table aliasing, exactly like the synchronous path's
        zero-chunk wave."""
        for i, req, seq, resumed, skip in admitted:
            if skip >= len(seq):
                self.cache_index = self.cache_index.at[i].set(len(seq))
                self._lengths[i] = len(seq)
                if self.paged and self.sharing:
                    self.allocator.register_prefix(i, seq)
                req.state = "decode"
            else:
                self._prefill_jobs[i] = _PrefillJob(
                    req=req, seq=seq, resumed=resumed, skip=skip,
                    pos=skip,
                )

    def _prefill_slots(self, admitted):
        """Batched chunked prefill for every slot admitted this tick:
        chunk c of all admitted prompts rides one jitted call, so a
        full admission wave costs ceil(max_L/C) dispatches — not
        sum(ceil(L_i/C)). A *fresh* slot's first generated token is
        sampled straight off its final prefill chunk; a *resumed*
        (preempted) slot only restores its cache rows — its pending
        token is already in ``tokens_out`` and must not be re-sampled.

        A slot admitted with a shared-prefix attach starts its chunks
        at ``skip`` — the aliased pages already hold those rows, so the
        skipped chunks never dispatch. A fully-covered resumed slot
        contributes nothing and restores purely by table aliasing.
        """
        C = self.prefill_chunk
        t0 = time.perf_counter()
        n_chunks = max(
            -(-(len(seq) - skip) // C) for _, _, seq, _, skip in admitted
        )
        bt = self._device_block_table() if self.paged else None
        last_logits = {}
        logits = None
        use_t = self._telemetry and self.prefill_fn_t is not None
        stats_chunks = []
        for c in range(n_chunks):
            toks = np.zeros((self.batch_slots, C), np.int32)
            # position sentinel max_len ⇒ no cache write, output ignored
            # (idle slots, already-finished prompts and ragged tails all
            # share one compiled shape).
            pos = np.full((self.batch_slots, C), self.max_len, np.int32)
            for i, req, seq, _, skip in admitted:
                lo = skip + c * C
                part = seq[lo:lo + C]
                if part:
                    toks[i, :len(part)] = part
                    pos[i, :len(part)] = lo + np.arange(len(part))
            inputs = {
                "tokens": jnp.asarray(toks), "positions": jnp.asarray(pos),
            }
            if bt is not None:
                inputs["block_table"] = bt
            if use_t:
                logits, self.cache, st = self._dispatch(
                    self.prefill_fn_t,
                    self.params, self.cache, inputs, self.cache_index,
                )
                stats_chunks.append(st)
            else:
                logits, self.cache = self._dispatch(
                    self.prefill_fn,
                    self.params, self.cache, inputs, self.cache_index,
                )
            self.metrics.prefill_dispatches += 1
            self._emit("prefill_chunk", site="prefill",
                       chunk=c, slots=len(admitted))
            for i, req, seq, resumed, skip in admitted:
                lo = skip + c * C
                if not resumed and lo < len(seq) <= lo + C:
                    last_logits[i] = logits[i, len(seq) - 1 - lo]
        # jax dispatch is async: sync before stopping the clock so the
        # prefill/decode throughput split reflects device time, not
        # dispatch time.
        if last_logits or logits is not None:
            jax.block_until_ready(
                list(last_logits.values()) if last_logits else logits
            )
        for i, req, seq, _, skip in admitted:
            self.cache_index = self.cache_index.at[i].set(len(seq))
            self._lengths[i] = len(seq)
            self.metrics.prefill_tokens += len(seq) - skip
        self.metrics.prefill_time += time.perf_counter() - t0
        self._emit("prefill_wave", site="prefill",
                   dur=time.perf_counter() - t0,
                   chunks=n_chunks, slots=len(admitted))
        if stats_chunks:
            # one host sync for the whole wave; stats are tiny [L, B, 4]
            for st in jax.device_get(stats_chunks):
                self.obs.record_prefill_stats(np.asarray(st))
        self._complete_prefill(admitted, last_logits)

    def _complete_prefill(self, entries, last_logits):
        """Shared tail of a synchronous wave and of hybrid chunk
        completion, in the exact order the contracts rely on: sample
        every *fresh* finishing slot's first token in one `_sample_wave`
        call (the ``poison_prefill`` chaos site sits just before it),
        quarantine non-finite slots **before** prefix registration (a
        faulted slot's pages must never enter the trie), register every
        surviving slot's prefix, then commit first tokens and flip to
        decode. ``entries`` is a list of ``(i, req, seq, resumed, skip)``
        whose prefill finished; ``last_logits`` maps fresh slots to the
        logits of their final prompt token."""
        toks = None
        if last_logits:
            zero_row = jnp.zeros_like(next(iter(last_logits.values())))
            logits_mat = jnp.stack([
                last_logits.get(i, zero_row)
                for i in range(self.batch_slots)
            ])
            mask = np.zeros((self.batch_slots,), bool)
            for i in last_logits:
                mask[i] = True
            if self._injector is not None:
                doomed = self._injector.poison_prefill([
                    req.uid for _, req, _, resumed, _ in entries
                    if not resumed
                ])
                if doomed:
                    pmask = np.zeros((self.batch_slots,), bool)
                    for i, req, _, resumed, _ in entries:
                        if not resumed and req.uid in doomed:
                            pmask[i] = True
                    logits_mat = _poison_logits(
                        logits_mat, jnp.asarray(pmask)
                    )
            toks, self.slot_keys, finite = _sample_wave(
                logits_mat, jnp.asarray(self._temps), self.slot_keys,
                jnp.asarray(mask),
            )
            toks, finite = jax.device_get((toks, finite))
            # quarantine *before* prefix registration: a faulted slot's
            # pages must never enter the trie for other requests to
            # attach. Idle rows are zero (finite) so only real fresh
            # slots can trip the guard.
            for i, req, _, resumed, _ in entries:
                if not resumed and not bool(finite[i]):
                    self._evict_slot(i, "failed", "non-finite logits")
        if self.paged and self.sharing:
            # content-address every page the wave filled. Registration
            # happens only now — mid-wave, a sharer could have read a
            # page its writer had not finished.
            for i, req, seq, _, _ in entries:
                if self.slots[i] is req:
                    self.allocator.register_prefix(i, seq)
        for i, req, _, resumed, _ in entries:
            if not resumed and self.slots[i] is req:
                self._commit_token(i, req, int(toks[i]))
            if self.slots[i] is req:
                req.state = "decode"

    def _prefill_tick(self):
        """Hybrid scheduler: advance every in-flight prefill job by
        exactly **one** chunk — all jobs share a single jitted dispatch,
        each at its own chunk offset (position sentinels idle the other
        slots, the same compiled shape as the synchronous wave). Jobs
        whose sequence is now fully written run the shared completion
        tail (`_complete_prefill`): first-token sampling, chaos poison,
        quarantine, prefix registration, commit, state → decode.

        Per-slot attention is independent of batch neighbours and the
        slot's RNG key only advances when *it* samples, so splitting the
        wave across ticks — with decode steps in between — produces the
        same per-uid streams as the synchronous schedule, bit for bit.
        """
        jobs = sorted(self._prefill_jobs.items())
        if not jobs:
            return
        C = self.prefill_chunk
        t0 = time.perf_counter()
        bt = self._device_block_table() if self.paged else None
        toks = np.zeros((self.batch_slots, C), np.int32)
        # position sentinel max_len ⇒ no cache write, output ignored
        # (idle/decoding slots and ragged tails share one compiled
        # shape).
        pos = np.full((self.batch_slots, C), self.max_len, np.int32)
        consumed: Dict[int, int] = {}
        for i, job in jobs:
            part = job.seq[job.pos:job.pos + C]
            toks[i, :len(part)] = part
            pos[i, :len(part)] = job.pos + np.arange(len(part))
            consumed[i] = len(part)
        inputs = {
            "tokens": jnp.asarray(toks), "positions": jnp.asarray(pos),
        }
        if bt is not None:
            inputs["block_table"] = bt
        use_t = self._telemetry and self.prefill_fn_t is not None
        stats = None
        if use_t:
            logits, self.cache, stats = self._dispatch(
                self.prefill_fn_t,
                self.params, self.cache, inputs, self.cache_index,
            )
        else:
            logits, self.cache = self._dispatch(
                self.prefill_fn,
                self.params, self.cache, inputs, self.cache_index,
            )
        self.metrics.prefill_dispatches += 1
        self._emit("prefill_chunk", site="prefill",
                   chunk=min((j.pos - j.skip) // C for _, j in jobs),
                   slots=len(jobs))
        finished = []
        last_logits = {}
        for i, job in jobs:
            lo, job.pos = job.pos, job.pos + consumed[i]
            # per-chunk accounting (the sync wave counts per slot at
            # wave end — same totals) keeps the stall detector's
            # progress marker advancing on prefill-only ticks
            self.metrics.prefill_tokens += consumed[i]
            if job.pos >= len(job.seq):
                finished.append(
                    (i, job.req, job.seq, job.resumed, job.skip)
                )
                if not job.resumed:
                    last_logits[i] = logits[i, len(job.seq) - 1 - lo]
        # sync before stopping the clock: prefill_time must reflect
        # device time for the ITL tick-phase attribution to be truthful
        jax.block_until_ready(
            list(last_logits.values()) if last_logits else logits
        )
        self.metrics.prefill_time += time.perf_counter() - t0
        self._emit("prefill_tick", site="prefill",
                   dur=time.perf_counter() - t0, slots=len(jobs),
                   finished=len(finished))
        if use_t and stats is not None:
            self.obs.record_prefill_stats(
                np.asarray(jax.device_get(stats))
            )
        for i, *_ in finished:
            job = self._prefill_jobs.pop(i)
            self.cache_index = self.cache_index.at[i].set(len(job.seq))
            self._lengths[i] = len(job.seq)
        if finished:
            self._complete_prefill(finished, last_logits)

    def _sequential_prefill_wave(self, admitted):
        """Token-by-token admission for models without a chunked-prefill
        path (recurrent families) and ≤1-token prompts. All admitted
        slots march together: token t of every prompt rides one
        whole-batch decode step, so a wave costs max(L_i)-1 dispatches,
        not sum(L_i)-k. The `active` mask gates recurrent-state updates
        to exactly the slots that consumed a token, so live decode
        neighbours are never advanced on garbage inputs."""
        t0 = time.perf_counter()
        n_steps = max(len(req.prompt) - 1 for _, req in admitted)
        logits = None
        for t in range(max(n_steps, 0)):
            tokens = np.full((self.batch_slots, 1), self.eos, np.int32)
            active = np.zeros((self.batch_slots,), bool)
            for i, req in admitted:
                if t < len(req.prompt) - 1:
                    tokens[i, 0] = req.prompt[t]
                    active[i] = True
            inputs = {
                "tokens": jnp.asarray(tokens),
                "active": jnp.asarray(active),
            }
            if self.paged:
                inputs["block_table"] = self._device_block_table()
            logits, self.cache = self._dispatch(
                self.step_fn,
                self.params, self.cache, inputs, self.cache_index,
            )
            self.cache_index = self.cache_index + jnp.asarray(
                active, jnp.int32
            )
            self._lengths += active
            self.metrics.prefill_dispatches += 1
            self.metrics.prefill_tokens += int(active.sum())
        if logits is not None:
            jax.block_until_ready(logits)
        self.metrics.prefill_time += time.perf_counter() - t0
        for i, req in admitted:
            req._next_input = req.prompt[-1] if req.prompt else self.eos
            req.state = "decode"

    def _release_slot(self, i: int):
        """Clear slot state; in paged mode its pages free *eagerly*."""
        self._prefill_jobs.pop(i, None)
        self.slots[i] = None
        self._temps[i] = 0.0
        self.cache_index = self.cache_index.at[i].set(0)
        self._lengths[i] = 0
        self._slot_order[i] = None
        if self.paged:
            self.allocator.free_slot(i)

    def _preempt(self, victim: int):
        """Evict a live slot: free its pages, requeue it at the front.
        On re-admission it re-prefills prompt + generated tokens and
        continues — stream and RNG state are preserved exactly."""
        req = self.slots[victim]
        self._release_slot(victim)
        req.state = "preempted"
        # requeue bypasses the queue limit: evicting a live slot must
        # never be able to fail.
        self.pending.requeue_front(req)
        self.metrics.preemptions += 1
        # a fresh slot preempted mid-prefill has no sampled token yet:
        # it re-admits as fresh and nothing it wrote survives
        self._emit("preempt", slot=victim, uid=req.uid,
                   written=max(
                       len(req.prompt) + len(req.tokens_out) - 1, 0
                   ))

    def _ensure_decode_capacity(self, live: List[int]) -> List[int]:
        """Every live slot must own the page its next token's KV row
        lands in — *exclusively*: a slot about to append into a shared
        or content-registered page first swaps in a copy-on-write clone
        (the engine's admission geometry makes this rare, but the guard
        makes "no slot ever writes a page another reader maps" an
        invariant rather than a schedule accident). On pool exhaustion,
        preempt the *youngest* live slot (latest admission) and retry —
        deterministic for a given trace. Returns the slots still live
        afterwards."""
        fresh: List[int] = []
        for i in live:
            while self.slots[i] is not None:
                got = self._ensure_capacity_inj(
                    i, int(self._lengths[i]) + 1
                )
                if got is not None:
                    fresh += got
                if got is not None and self.sharing:
                    blk = int(self._lengths[i]) // self.layout.page_size
                    if not self.allocator.writable(i, blk):
                        pair = self.allocator.cow(i, blk)
                        if pair is None:
                            # the clone needs a page we don't have:
                            # preempt below and retry (the grown pages
                            # stay — ensure_capacity is then a no-op).
                            got = None
                        else:
                            # applied immediately: a later preemption in
                            # this same pass may free + recycle the
                            # clone's page, and the final fresh-page
                            # zeroing must win over the copy.
                            self.cache = self.model.clone_pages(
                                self.cache, [pair[0]], [pair[1]]
                            )
                            self.metrics.cow_clones += 1
                            self._emit(
                                "cow_clone", slot=i,
                                uid=self.slots[i].uid,
                                src=pair[0], dst=pair[1], site="decode",
                            )
                if got is not None:
                    break
                self._preempt(self._preempt_victim())
        if fresh:
            self.cache = self._reset_pages(fresh)
        return [i for i in live if self.slots[i] is not None]

    def _commit_token(self, i: int, req: Request, tok: int):
        now = time.perf_counter()
        if not req.tokens_out:
            req._t_first = now
        elif req._t_last is not None:
            dt = now - req._t_last
            # tick-phase attribution: subtract the engine prefill time
            # that elapsed between this request's commits — admission
            # waves (sync) and chunk waves (hybrid) stall the stream
            # but are *scheduler* latency, not decode latency. The raw
            # wall gap stays in `itl`; `itl_decode` is the truthful
            # decode histogram the SLO bench reads.
            stall = max(self.metrics.prefill_time - req._pf_mark, 0.0)
            decode_dt = max(dt - stall, 0.0)
            req._itl.append(dt)
            req._itl_decode.append(decode_dt)
            self.metrics.observe_itl(dt, decode_dt)
        req._t_last = now
        req._pf_mark = self.metrics.prefill_time
        req.tokens_out.append(tok)
        req._next_input = tok
        if req.on_token is not None:
            # streaming: the token surfaces now, not at drain
            req.on_token(req, tok)
        # a request generating m tokens writes prompt + m - 1 rows (the
        # final token is sampled but never appended to the cache), so
        # m ≤ rows - len(prompt) + 1 always fits.
        limit = min(
            req.max_new_tokens,
            self.max_len - len(req.prompt) + 1,
        )
        if tok == self.eos or len(req.tokens_out) >= limit:
            req.done = True
            req.state = "done"
            self.completed.append(req)
            self._release_slot(i)
            self.metrics.record_request(req)
            self._emit("finish", slot=i, uid=req.uid,
                       tokens=len(req.tokens_out))

    def _audit_tick(self):
        """Optional per-tick allocator self-check: the PR 4 fuzzer's
        invariants (refcounts == live table refs, single-writer,
        live + free + cached == pool) promoted into the engine. Raises
        :class:`~repro.runtime.paged_cache.AllocatorInvariantError` at
        the tick that corrupts state, not at the test that trips over
        it later."""
        if self.audit and self.paged:
            self.allocator.check_invariants()

    def _end_tick(self):
        """Uniform tick epilogue: every `tick()` call counts exactly
        once (prefill-only and idle ticks included — the observability
        series append once per tick, so `len(series) == ticks` holds on
        every path), then audit + per-tick series."""
        self.metrics.ticks += 1
        self._audit_tick()
        self._obs_tick_end()

    def tick(self):
        """One engine iteration, budget-bounded: expire deadlines,
        admit (allocation only under the hybrid scheduler), advance
        in-flight prefills by at most one chunk wave, then decode one
        token for every decode-state slot (quarantining any slot whose
        logits go non-finite). Under ``scheduler="sync"`` admission
        runs its entire prefill wave inline instead and every live slot
        is in decode state by the time the decode step dispatches."""
        if self.obs is not None:
            self.obs.trace.tick = self.metrics.ticks
        self._expire_deadlines()
        if self._injector is not None:
            self._injected_preempt_storm()
        self._admit()
        if self._hybrid and self._prefill_jobs:
            self._prefill_tick()
        live = [
            i for i, r in enumerate(self.slots)
            if r is not None
            and (not self._hybrid or r.state == "decode")
        ]
        if not live:
            self._end_tick()
            return
        if self.paged:
            live = self._ensure_decode_capacity(live)
            self.metrics.peak_pages_in_use = \
                self.allocator.peak_pages_in_use
            if not live:
                self._end_tick()
                return
        t0 = time.perf_counter()
        tokens = np.full((self.batch_slots, 1), self.eos, np.int32)
        active = np.zeros((self.batch_slots,), bool)
        for i in live:
            tokens[i, 0] = self.slots[i]._next_input
            active[i] = True
        inputs = {
            "tokens": jnp.asarray(tokens), "active": jnp.asarray(active),
        }
        if self.paged:
            inputs["block_table"] = self._device_block_table()
        # Unpaged decode writes K/V *positionally* at cache_index with no
        # active gating ("self-healing": an idle slot's garbage row is
        # overwritten by the next prefill before it can be read). Under
        # the hybrid scheduler an inactive slot can be *mid-prefill* —
        # rows already written by earlier chunks must not be clobbered —
        # so inactive slots get the max_len sentinel, whose one-hot
        # write row is all zeros (no write). The paged path already
        # drops idle writes via its write_mask.
        step_index = self.cache_index
        if not self.paged:
            step_index = jnp.where(
                jnp.asarray(active), self.cache_index, self.max_len
            )
        step_stats = None
        if self._telemetry and self.step_fn_t is not None:
            logits, self.cache, step_stats = self._dispatch(
                self.step_fn_t,
                self.params, self.cache, inputs, step_index,
            )
        else:
            logits, self.cache = self._dispatch(
                self.step_fn,
                self.params, self.cache, inputs, step_index,
            )
        self.cache_index = self.cache_index + jnp.asarray(active, jnp.int32)
        self._lengths += active
        if self.paged and self.sharing:
            # a decode append that just *filled* a page freezes it:
            # register its content (prompt + written generations) so a
            # preempted-and-resumed twin — or an identical re-request —
            # can attach instead of re-prefilling. Registered pages are
            # immutable; the slot's next append starts a new page. The
            # registration re-walks the slot's chain from the root —
            # O(len) host work per page fill, bounded by the engine's
            # rows ≤ max_len invariant (≤ max_len²/bk per request, dict
            # lookups on small tuples) — noise next to a decode
            # dispatch.
            bk = self.layout.page_size
            for i in live:
                n = int(self._lengths[i])
                if n and n % bk == 0:
                    req = self.slots[i]
                    self.allocator.register_prefix(
                        i, req.prompt + req.tokens_out
                    )
        if self._injector is not None:
            doomed = self._injector.poison_decode(
                [self.slots[i].uid for i in live]
            )
            if doomed:
                pmask = np.zeros((self.batch_slots,), bool)
                for i in live:
                    if self.slots[i].uid in doomed:
                        pmask[i] = True
                logits = _poison_logits(logits, jnp.asarray(pmask))
        next_tokens, self.slot_keys, finite = _sample_step(
            logits, jnp.asarray(self._temps), self.slot_keys,
            inputs["active"],
        )
        if step_stats is not None:
            # stats ride the device_get the engine already pays for the
            # sampled tokens — no extra host sync on the telemetry path.
            next_tokens, finite, stats_host = jax.device_get(
                (next_tokens, finite, step_stats)
            )
            self.obs.record_decode_stats(np.asarray(stats_host), slots=live)
        else:
            next_tokens, finite = jax.device_get((next_tokens, finite))
        if self._injector is not None:
            # injected straggler: the sleep lands inside decode_time so
            # the StragglerMonitor sees it like a real slow step.
            delay = self._injector.step_delay()
            if delay:
                time.sleep(delay)
        self.metrics.decode_dispatches += 1
        elapsed = time.perf_counter() - t0
        self.metrics.decode_time += elapsed
        self._emit("decode_tick", site="decode", dur=elapsed, live=len(live))
        if self.straggler.record(elapsed):
            self.metrics.stragglers += 1
        for i in live:
            req = self.slots[i]
            if not bool(finite[i]):
                # quarantine: only the faulted slot dies — its pages
                # free under the allocator's normal rules, its
                # neighbours' committed tokens are untouched.
                self._evict_slot(i, "failed", "non-finite logits")
                continue
            self.metrics.decode_tokens += 1
            self._commit_token(i, req, int(next_tokens[i]))
        self._end_tick()

    # --- draining ------------------------------------------------------
    def _has_work(self) -> bool:
        return bool(self.pending) or any(
            s is not None for s in self.slots
        )

    def _progress_marker(self) -> Tuple[int, ...]:
        """Monotone progress fingerprint for stall detection: any token
        computed, any request reaching a terminal state, and any change
        in queue depth (an admission or an eviction) all count."""
        return (
            self.metrics.prefill_tokens,
            self.metrics.decode_tokens,
            self.metrics.preemptions,
            len(self.completed),
            len(self.terminated),
            len(self.pending),
        )

    def _stuck_uids(self) -> List[int]:
        return sorted(
            [r.uid for r in self.pending]
            + [r.uid for r in self.slots if r is not None]
        )

    def run_until_drained(
        self, max_ticks: int = 10_000, *, raise_on_stall: bool = True
    ):
        """Tick until every request reaches a terminal state.

        A tick that makes zero progress (no token, no admission, no
        terminal transition) with work still queued is diagnosed instead
        of spun on: fault-free, the engine's decisions are deterministic
        in its state, so a zero-progress tick would repeat forever —
        e.g. a prompt needing more free pages than the pool can ever
        offer while nothing is live. After ``stall_patience``
        consecutive zero-progress ticks (injected faults can make a
        single one recoverable), or when ``max_ticks`` is exhausted with
        work remaining, :class:`EngineStalled` names the stuck uids —
        no more silently returned partial results. ``raise_on_stall=
        False`` restores the old return-partial behavior for callers
        that inspect state themselves."""
        stagnant = 0
        for _ in range(max_ticks):
            if not self._has_work():
                return self.completed
            before = self._progress_marker()
            self.tick()
            if self._progress_marker() == before:
                stagnant += 1
                if stagnant > self.stall_patience:
                    if raise_on_stall:
                        raise EngineStalled(
                            f"zero progress over {stagnant} consecutive "
                            f"ticks with work queued; stuck uids: "
                            f"{self._stuck_uids()}",
                            self._stuck_uids(),
                        )
                    return self.completed
            else:
                stagnant = 0
        if self._has_work() and raise_on_stall:
            raise EngineStalled(
                f"max_ticks={max_ticks} exhausted with work queued; "
                f"stuck uids: {self._stuck_uids()}",
                self._stuck_uids(),
            )
        return self.completed
