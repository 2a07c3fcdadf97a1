"""Plain float32 reference of a served configuration under MP-MRF.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: no
kernels, no pages, no cache, no batching of unrelated requests. It
imports nothing of the system under test and takes nothing it made; it
reads the configuration file's dict and rebuilds each layer's weights
from the seed (``bench.weights``).

The architecture is the published decoder (pre-norm RMSNorm, RoPE with
rotate-half, optional per-head q/k RMSNorm, SwiGLU MLP, untied head).
Departures, each forced by what the configuration serves:

* attention in layers ``>= min_prune_layer`` is Energon's MP-MRF
  selection as the configuration states it (ρ, round bits, α, block
  sizes), applied the way a serving engine meets the sequence: the
  prompt in chunks of ``prefill_chunk`` rows on a grid from position 0
  (query blocks × key tiles, pooled block maxima, Eq. 3 rounds,
  sink and diagonal pins, top-B), each later token as one decode query
  (pages of ``decode_key_block`` rows, G query heads pooled, exact
  budget with tiers and a live budget). Keys are scored from int16
  codes with one absmax scale per page over the rows written at that
  moment, and their top 2 and 4 bits;
* the embedding table is read multiplied by sqrt(hidden_size), as the
  served program scales it (the published model with that table);
* norm gains are 1, as the benchmark draws them.

``low=True`` computes the same thing with every matmul operand rounded
to float8 e4m3 under a per-tensor (weights) or per-row (activations)
absmax scale: the control, one precision step below bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

NEG = -1e30
HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def _f8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, low, a_axis=-1, b_axis=None):
    if low:
        a = _f8(a, a_axis)
        b = _f8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x [..., n, H, hd], pos [..., n] — rotate-half RoPE."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _softmax(s, mask):
    """Attention weights of scores s [..., q, k] over the ``mask``ed keys
    (a row with none gets zeros)."""
    s = jnp.where(mask, s, NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def _eq3(s, alpha, valid):
    """Eq. 3 threshold over the last axis among ``valid`` entries."""
    cnt = jnp.maximum(jnp.sum(valid, axis=-1, keepdims=True), 1)
    mean = jnp.sum(jnp.where(valid, s, 0.0), -1, keepdims=True) / cnt
    if alpha >= 0:
        mx = jnp.max(jnp.where(valid, s, NEG), -1, keepdims=True)
        return alpha * mx + (1 - alpha) * mean
    mn = jnp.min(jnp.where(valid, s, -NEG), -1, keepdims=True)
    return -alpha * mn + (1 + alpha) * mean


def _int16(x, scale):
    return jnp.clip(jnp.round(x / scale), -32767, 32767)


def _plane(codes, bits):
    """Top ``bits`` bits of an int16 code (arithmetic shift = floor)."""
    return jnp.floor(codes / 2.0 ** (16 - bits))


def _scale(absmax):
    return jnp.maximum(absmax, 1e-8) / 32767.0


# --------------------------------------------------------------------------
# MP-MRF selection and attention
# --------------------------------------------------------------------------

class Geometry:
    """Static sizes of one cell (hashable, so it can key a jit)."""

    def __init__(self, cfg: Dict[str, Any], max_len: int, chunk: int,
                 shared: int, rows: int, served: int):
        e = cfg["energon"]
        self.kv = cfg["num_key_value_heads"]
        self.g = cfg["num_attention_heads"] // self.kv
        self.hd = cfg["head_dim"]
        self.n_k = max_len
        self.chunk = chunk
        self.shared = shared          # rows of the shared prefix
        self.rows = rows              # suffix rows per request
        self.served = served          # logit rows compared per request
        self.ps = e["decode_key_block"]
        self.qb = e["query_block"]
        self.kb = e["key_block"]
        self.rho = float(e["pruning_ratio"])
        self.bits = tuple(e["round_bits"])
        self.alphas = tuple(float(a) for a in e["alphas"])
        self.keep_first = bool(e["keep_first"])
        self.keep_diag = bool(e["keep_diagonal"])
        self.dense_layers = int(e["min_prune_layer"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.qk_norm = bool(cfg["qk_norm"])
        if len(self.bits) != 2 or not e["reuse_partial"]:
            raise ValueError("the reference scores two rounds with reuse")
        if max_len % self.kb or self.kb % self.ps:
            raise ValueError("max_len must be whole key tiles of pages")
        if (self.g * chunk) % self.qb:
            raise ValueError("folded chunk rows must be whole query blocks")
        if shared % chunk or rows % chunk or shared + rows != max_len:
            raise ValueError("shared prefix and suffix must fill max_len "
                             "in whole chunks")

    def _key(self):
        return tuple(sorted(vars(self).items()))

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Geometry) and self._key() == other._key()


def _qplane(q, geo):
    """Per-row int16 codes of q [..., hd] → (4-bit plane, row scale)."""
    qs = _scale(jnp.max(jnp.abs(q), axis=-1, keepdims=True))
    return _plane(_int16(q, qs), geo.bits[1]), qs


def _round_scores(qp, qs, kc, ks, geo):
    """Two rounds' real-unit scores from planes: qp [KV, R, hd], qs
    [KV, R, 1], kc key codes [KV, N, hd], ks key scales [KV, N]."""
    lo, hi = geo.bits
    acc0 = jnp.einsum("hrd,hnd->hrn", qp, _plane(kc, lo), precision=HI)
    acc1 = jnp.einsum("hrd,hnd->hrn", qp, _plane(kc, hi), precision=HI)
    base = qs * 2.0 ** (16 - hi) * ks[:, None, :]
    return acc0 * base * 2.0 ** (16 - lo), acc1 * base * 2.0 ** (16 - hi)


def _page_codes(k, extent, geo):
    """int16 codes and per-row scales of keys k [KV, n_k, hd] as the
    pool holds them once rows [0, extent) are written: one absmax scale
    per page over its written rows."""
    rows = jnp.arange(geo.n_k)
    km = jnp.where((rows < extent)[None, :, None], k, 0.0)
    pages = km.reshape(geo.kv, geo.n_k // geo.ps, geo.ps * geo.hd)
    ks = jnp.repeat(_scale(jnp.max(jnp.abs(pages), axis=-1)), geo.ps, -1)
    return _int16(km, ks[..., None]), ks


def _prefill_chunk(q, qpos, k, v, extent, geo):
    """One prompt chunk against keys [0, extent). q [KV, G, C, hd],
    qpos [C] (rows >= extent are padding). Returns [KV, G, C, hd]."""
    n_k, R = geo.n_k, geo.g * geo.chunk
    kc, ks = _page_codes(k, extent, geo)
    qf = q.reshape(geo.kv, R, geo.hd)
    real = qpos < extent
    qpf = jnp.tile(jnp.where(real, qpos, n_k), geo.g)          # [R]
    qp, qs = _qplane(qf, geo)
    s0, s1 = _round_scores(qp, qs, kc, ks, geo)
    kpos = jnp.arange(n_k)
    ok = (kpos[None, :] <= qpf[:, None]) & (qpf[:, None] < n_k)
    nqb, nkb = R // geo.qb, n_k // geo.kb

    def pool(s):
        s = jnp.where(ok[None], s, NEG)
        return jnp.max(s.reshape(geo.kv, nqb, geo.qb, nkb, geo.kb), (2, 4))

    b0, b1 = pool(s0), pool(s1)
    valid = b0 > NEG / 2
    keep = valid
    for alpha, b in zip(geo.alphas, (b0, b1)):
        keep = keep & (b >= _eq3(b, alpha, keep))
    if geo.keep_first:
        keep = keep.at[..., 0].set(valid[..., 0])
    if geo.keep_diag:
        eff = jnp.where(qpf < n_k, qpf, -1).reshape(nqb, geo.qb).max(-1)
        diag = jnp.clip(eff, 0, n_k - 1) // geo.kb
        keep = keep | (jax.nn.one_hot(diag, nkb, dtype=bool)[None] & valid)
    budget = min(max(1, math.ceil(nkb / geo.rho)), nkb)
    vals, idx = jax.lax.top_k(jnp.where(keep, b1, NEG), budget)
    chosen = jnp.any(
        jax.nn.one_hot(idx, nkb, dtype=bool) & (vals > NEG / 2)[..., None],
        axis=-2,
    )                                                          # [KV,nqb,nkb]
    tile_ok = jnp.repeat(jnp.repeat(chosen, geo.qb, 1), geo.kb, 2)
    mask = ok[None] & tile_ok
    s = jnp.einsum("hrd,hnd->hrn", qf, k, precision=HI) / math.sqrt(geo.hd)
    p = _softmax(s, mask)
    out = jnp.einsum("hrn,hnd->hrd", p, v, precision=HI)
    return out.reshape(geo.kv, geo.g, geo.chunk, geo.hd)


def _decode_rows(q, tpos, k, v, kc_full, ks_full, geo):
    """Decode queries at positions tpos [D] (each with cache length
    t+1). q [KV, G, D, hd]. Returns [KV, G, D, hd]."""
    n_k, ps, D = geo.n_k, geo.ps, tpos.shape[0]
    mb = n_k // ps
    page = tpos // ps                                          # [D]
    qp, qs = _qplane(q, geo)                                   # [KV,G,D,*]
    R = geo.g * D
    qpf, qsf = qp.reshape(geo.kv, R, geo.hd), qs.reshape(geo.kv, R, 1)
    # pages below each query's own page are full: their codes are final
    s0, s1 = _round_scores(qpf, qsf, kc_full, ks_full, geo)
    s0 = s0.reshape(geo.kv, geo.g, D, mb, ps)
    s1 = s1.reshape(geo.kv, geo.g, D, mb, ps)
    below = jnp.arange(mb)[None, :] < page[:, None]            # [D, mb]
    b0 = jnp.max(jnp.where(below[None, None, :, :, None], s0, NEG), (1, 4))
    b1 = jnp.max(jnp.where(below[None, None, :, :, None], s1, NEG), (1, 4))
    # the query's own page holds rows [page*ps, t], scaled over those
    rows = page[:, None] * ps + jnp.arange(ps)[None, :]        # [D, ps]
    live = rows <= tpos[:, None]
    kown = jnp.where(live[None, :, :, None], k[:, rows], 0.0)  # [KV,D,ps,hd]
    sc = _scale(jnp.max(jnp.abs(kown), axis=(2, 3)))           # [KV, D]
    cown = _int16(kown, sc[..., None, None])
    lo, hi = geo.bits
    base = qs[..., 0] * 2.0 ** (16 - hi) * sc[:, None, :]      # [KV,G,D]
    a0 = jnp.einsum("hgtd,htpd->hgtp", qp, _plane(cown, lo), precision=HI)
    a1 = jnp.einsum("hgtd,htpd->hgtp", qp, _plane(cown, hi), precision=HI)
    o0 = jnp.where(live[None, None], a0 * (base * 2.0 ** (16 - lo))[..., None],
                   NEG).max((1, 3))
    o1 = jnp.where(live[None, None], a1 * (base * 2.0 ** (16 - hi))[..., None],
                   NEG).max((1, 3))
    own = jax.nn.one_hot(page, mb, dtype=bool)[None]           # [1, D, mb]
    b0 = jnp.where(own, o0[..., None], b0)                     # [KV, D, mb]
    b1 = jnp.where(own, o1[..., None], b1)
    valid = b0 > NEG / 2
    keep = valid
    for alpha, b in zip(geo.alphas, (b0, b1)):
        keep = keep & (b >= _eq3(b, alpha, keep))
    order = jnp.argsort(-jnp.where(valid, b1, NEG), axis=-1)
    rank = jnp.argsort(order, axis=-1)
    tier = jnp.where(keep, 2, valid.astype(jnp.int32))
    ids = jnp.arange(mb)
    pinned = jnp.zeros(valid.shape, bool)
    if geo.keep_first:
        pinned = pinned | ((ids == 0)[None, None] & valid)
    if geo.keep_diag:
        pinned = pinned | (own & valid)
    tier = jnp.where(pinned, 3, tier)
    key = tier * mb + (mb - 1 - rank)
    place = jnp.argsort(jnp.argsort(-key, axis=-1), axis=-1)   # 0 = first
    budget = min(max(1, math.ceil(mb / geo.rho)), mb)
    live_blocks = (tpos + 1 + ps - 1) // ps
    lb = jnp.maximum(jnp.ceil(live_blocks / geo.rho), 1).astype(jnp.int32)
    chosen = (tier >= 1) & (place < budget) & (
        (place < lb[None, :, None]) | (tier == 3))             # [KV, D, mb]
    key_ok = jnp.arange(n_k)[None, :] <= tpos[:, None]         # [D, n_k]
    mask = key_ok[None] & jnp.repeat(chosen, ps, axis=-1)      # [KV, D, n_k]
    s = jnp.einsum("hgtd,hnd->hgtn", q, k, precision=HI) / math.sqrt(geo.hd)
    p = _softmax(s, mask[:, None])
    return jnp.einsum("hgtn,hnd->hgtd", p, v, precision=HI)


def _dense_rows(q, qpos, k, v, geo):
    """Causal dense attention of q [KV, G, n, hd] at positions qpos,
    ``chunk`` rows at a time."""
    C, n = geo.chunk, qpos.shape[0]

    def one(args):
        qi, pi = args
        s = jnp.einsum("hgtd,hnd->hgtn", qi, k,
                       precision=HI) / math.sqrt(geo.hd)
        mask = jnp.arange(geo.n_k)[None, :] <= pi[:, None]
        p = _softmax(s, mask[None, None])
        return jnp.einsum("hgtn,hnd->hgtd", p, v, precision=HI)

    qc = q.reshape(geo.kv, geo.g, n // C, C, geo.hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(one, (qc, qpos.reshape(n // C, C)))
    return out.transpose(1, 2, 0, 3, 4).reshape(q.shape)


# --------------------------------------------------------------------------
# one layer, shared prefix and suffixes together
# --------------------------------------------------------------------------

def _qkv(x, pos, w, geo, low):
    """x [n, d] → q [KV, G, n, hd], k/v [KV, n, hd]."""
    n = x.shape[0]
    q = _mm("nd,dhk->nhk", x, w["wq"], low, -1, (0, 1, 2))
    k = _mm("nd,dhk->nhk", x, w["wk"], low, -1, (0, 1, 2))
    v = _mm("nd,dhk->nhk", x, w["wv"], low, -1, (0, 1, 2))
    if geo.qk_norm:
        q, k = _rmsnorm(q, geo.eps), _rmsnorm(k, geo.eps)
    q, k = _rope(q, pos, geo.theta), _rope(k, pos, geo.theta)
    if low:     # the control keeps its cache in float8 too
        k, v = _f8(k, -1), _f8(v, -1)
    q = q.reshape(n, geo.kv, geo.g, geo.hd).transpose(1, 2, 0, 3)
    return q, k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def _mlp_out(x, att, w, geo, low):
    """Residual update of x [n, d] by attention output att [n, H, hd]."""
    x = x + _mm("nhk,hkd->nd", att, w["wo"], low, (1, 2), (0, 1, 2))
    h = _rmsnorm(x, geo.eps)
    up = _mm("nd,df->nf", h, w["w_up"], low, -1, (0, 1))
    gate = _mm("nd,df->nf", h, w["w_gate"], low, -1, (0, 1))
    return x + _mm("nf,fd->nd", jax.nn.silu(gate) * up, w["w_down"], low,
                   -1, (0, 1))


def _unfold(o, geo):
    """[KV, G, n, hd] → [n, H, hd]."""
    n = o.shape[2]
    return o.transpose(2, 0, 1, 3).reshape(n, geo.kv * geo.g, geo.hd)


def _prefill_rows(q, pos, first, k, v, extent_end, geo):
    """Prompt rows q [KV, G, n, hd] at positions pos (chunks of the grid
    starting at ``first``), each chunk seeing keys up to its end (capped
    at ``extent_end``)."""
    C, n = geo.chunk, pos.shape[0]
    qc = q.reshape(geo.kv, geo.g, n // C, C, geo.hd).transpose(2, 0, 1, 3, 4)
    pc = pos.reshape(n // C, C)

    def one(args):
        qi, pi = args
        end = jnp.minimum(pi[0] + C, extent_end)
        return _prefill_chunk(qi, pi, k, v, end, geo)

    out = jax.lax.map(one, (qc, pc))                  # [nc, KV, G, C, hd]
    return out.transpose(1, 2, 0, 3, 4).reshape(geo.kv, geo.g, n, geo.hd)


@functools.partial(jax.jit, static_argnames=("cfg_items", "geo", "low",
                                             "dense"))
def _layer(x_doc, x_suf, prompt_len, total_len, key, index, *, cfg_items,
           geo, low, dense):
    """One decoder layer over the shared prefix rows x_doc [S, d] and
    each request's suffix rows x_suf [R, P, d]."""
    cfg = dict(cfg_items)
    w = {n: a.astype(jnp.float32)
         for n, a in W.layer_weights(cfg, key, index).items()}
    S, P = geo.shared, geo.rows
    h_doc = _rmsnorm(x_doc, geo.eps)
    qd, kd, vd = _qkv(h_doc, jnp.arange(S), w, geo, low)
    pad = ((0, 0), (0, geo.n_k - S), (0, 0))
    kdp, vdp = jnp.pad(kd, pad), jnp.pad(vd, pad)
    if S:
        dpos = jnp.arange(S)
        if dense:
            od = _dense_rows(qd, dpos, kdp, vdp, geo)
        else:
            od = _prefill_rows(qd, dpos, 0, kdp, vdp, S, geo)
        x_doc = _mlp_out(x_doc, _unfold(od, geo), w, geo, low)

    def request(xs, L, T):
        spos = S + jnp.arange(P)
        qs_, ks_, vs_ = _qkv(_rmsnorm(xs, geo.eps), spos, w, geo, low)
        k = jnp.concatenate([kd, ks_], axis=1)
        v = jnp.concatenate([vd, vs_], axis=1)
        written = jnp.arange(geo.n_k) < T
        k = jnp.where(written[None, :, None], k, 0.0)
        v = jnp.where(written[None, :, None], v, 0.0)
        if dense:
            o = _dense_rows(qs_, spos, k, v, geo)
        else:
            op = _prefill_rows(qs_, spos, S, k, v, L, geo)
            kc, ks = _page_codes(k, geo.n_k, geo)
            blocks = P // geo.ps
            qb = qs_.reshape(geo.kv, geo.g, blocks, geo.ps, geo.hd)

            def dec(args):
                qi, pi = args
                return _decode_rows(qi, pi, k, v, kc, ks, geo)

            od = jax.lax.map(dec, (qb.transpose(2, 0, 1, 3, 4),
                                   spos.reshape(blocks, geo.ps)))
            od = od.transpose(1, 2, 0, 3, 4).reshape(op.shape)
            o = jnp.where((spos < L)[None, None, :, None], op, od)
        return _mlp_out(xs, _unfold(o, geo), w, geo, low)

    x_suf = jax.lax.map(lambda a: request(*a), (x_suf, prompt_len,
                                                total_len))
    return x_doc, x_suf


@functools.partial(jax.jit, static_argnames=("geo", "low"))
def _embed(tokens, table, *, geo, low):
    del geo
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    return _f8(x, -1) if low else x


@functools.partial(jax.jit, static_argnames=("geo", "low"))
def _head(x_suf, prompt_len, eval_tokens, head, *, geo, low):
    """Logits of the compared rows: for request r, positions
    L_r - 1 + j, j < served. Returns (max logit, argmax, logits of
    eval_tokens [R, served, M])."""
    j = jnp.arange(geo.served)
    rows = jnp.clip(prompt_len[:, None] - 1 - geo.shared + j[None, :], 0,
                    geo.rows - 1)
    x = jnp.take_along_axis(x_suf, rows[..., None], axis=1)
    x = _rmsnorm(x, geo.eps)
    logits = _mm("rjd,dv->rjv", x, head.astype(jnp.float32), low, -1,
                 (0, 1))
    picked = jnp.take_along_axis(logits, eval_tokens, axis=-1)
    return logits.max(-1), logits.argmax(-1).astype(jnp.int32), picked


def reference_logits(cfg: Dict[str, Any], seed: int, *, shared: Sequence[int],
                     requests: Sequence[Tuple[Sequence[int], int]],
                     eval_tokens: np.ndarray, max_len: int, chunk: int,
                     served: int, low: bool = False):
    """Teacher-forced logits of each request's served rows.

    ``shared`` is the shared prefix (possibly empty), ``requests`` a
    list of ``(rows, prompt_len)``: every token written to the cache
    after the prefix (prompt suffix, then served tokens but the last)
    and the prompt's length from position 0. ``eval_tokens`` int32
    ``[R, served, M]`` names the tokens whose logits to return per
    compared row. Returns numpy ``(max, argmax, picked)``.
    """
    S = len(shared)
    P = max_len - S
    geo = Geometry(cfg, max_len, chunk, S, P, served)
    R = len(requests)
    toks = np.zeros((R, P), np.int32)
    plen = np.zeros((R,), np.int32)
    tlen = np.zeros((R,), np.int32)
    for r, (rows, L) in enumerate(requests):
        if len(rows) > P or L <= S:
            raise ValueError("request does not fit the geometry")
        toks[r, :len(rows)] = rows
        plen[r], tlen[r] = L, S + len(rows)
    key = W.seed_key(seed)
    items = tuple((k, cfg[k]) for k in _LAYER_KEYS)
    scale = math.sqrt(cfg["hidden_size"])
    with jax.default_matmul_precision("highest"):
        table = W.embedding_table(cfg, key).astype(jnp.float32) * scale
        x_doc = _embed(jnp.asarray(np.asarray(shared, np.int32)), table,
                       geo=geo, low=low)
        x_suf = _embed(jnp.asarray(toks), table, geo=geo, low=low)
        del table
        pl, tl = jnp.asarray(plen), jnp.asarray(tlen)
        for i in range(cfg["num_hidden_layers"]):
            x_doc, x_suf = _layer(
                x_doc, x_suf, pl, tl, key, jnp.int32(i), cfg_items=items,
                geo=geo, low=low, dense=i < geo.dense_layers,
            )
        if cfg["tie_word_embeddings"]:
            head = W.embedding_table(cfg, key).T
        else:
            head = W.head_matrix(cfg, key)
        out = _head(x_suf, pl, jnp.asarray(eval_tokens, jnp.int32), head,
                    geo=geo, low=low)
        return tuple(np.asarray(a) for a in out)


#: the configuration keys a layer reads (the jit key)
_LAYER_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "initializer_range",
               "torch_dtype")

