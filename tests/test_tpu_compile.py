"""Ahead-of-time compiles of the fused MP-MRF kernels for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a described,
unattached chip, so these tests catch what interpret mode cannot — tile
misalignment, unsupported vector ops, scratch-memory overruns — at no
chip time. Shapes are the serving engine's at real widths:

* phi3-mini-3.8b: 32 KV heads (MHA, G = 1), head_dim 96;
* qwen3-14b: 8 KV heads, G = 5, head_dim 128;

both with 4 slots, max_len 1024 and 64-row pages (``decode_key_block``),
128-token prefill chunks and 128-row key tiles (two pages).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers import every test file.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mpmrf_decode as dk
from repro.kernels import mpmrf_prefill as pk
from repro.kernels import ops

SLOTS, MAX_LEN, PAGE, CHUNK, KEY_TILE, RHO = 4, 1024, 64, 128, 128, 4.0
MB = MAX_LEN // PAGE
#: (name, kv_heads, group, head_dim)
SHAPES = [("phi3-mini", 32, 1, 96), ("qwen3-14b-gqa", 8, 5, 128)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


def _dims(kv, g):
    bh = SLOTS * kv
    return bh, kv * SLOTS * MB          # rows of the folded table, pages


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_decode_filter_kernel(one_chip, name, kv, g, d):
    pages = SLOTS * MB
    text = _compile(
        one_chip,
        functools.partial(dk.mpmrf_paged_filter_scores, key_block=PAGE),
        ((SLOTS, kv * g, d), jnp.int8), ((SLOTS, kv * g, 1), jnp.float32),
        ((1, kv, pages * PAGE, d), jnp.int16), ((1, kv, pages), jnp.float32),
        ((SLOTS, MB), jnp.int32), ((SLOTS,), jnp.int32),
    )
    assert "mpmrf_decode_filter" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_decode_gather_kernel(one_chip, name, kv, g, d):
    bh, pages = _dims(kv, g)
    budget = math.ceil(MB / RHO)
    text = _compile(
        one_chip,
        functools.partial(dk.paged_decode_gather_attention, key_block=PAGE),
        ((bh, g, d), jnp.bfloat16),
        ((pages, PAGE, d), jnp.bfloat16), ((pages, PAGE, d), jnp.bfloat16),
        ((bh, budget), jnp.int32), ((bh, budget), jnp.int32),
        ((bh, MB), jnp.int32), ((bh,), jnp.int32),
    )
    assert "mpmrf_decode_gather" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_prefill_filter_kernel(one_chip, name, kv, g, d):
    bh, pages = _dims(kv, g)
    n_q = CHUNK * g
    text = _compile(
        one_chip,
        functools.partial(pk.mpmrf_paged_prefill_filter_scores,
                          query_block=CHUNK, key_block=PAGE),
        ((bh, n_q, d), jnp.int8), ((bh, n_q, 1), jnp.float32),
        ((bh, n_q), jnp.int32),
        ((pages, PAGE, d), jnp.int16), ((pages, 1), jnp.float32),
        ((bh, MB), jnp.int32),
    )
    assert "mpmrf_prefill_filter" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_prefill_gather_kernel(one_chip, name, kv, g, d):
    bh, pages = _dims(kv, g)
    n_q = CHUNK * g
    # survivors of 128-row key tiles, expanded to their two pages
    slots = math.ceil(MAX_LEN // KEY_TILE / RHO) * (KEY_TILE // PAGE)
    text = _compile(
        one_chip,
        functools.partial(pk.paged_prefill_gather_attention,
                          query_block=CHUNK, key_block=PAGE),
        ((bh, n_q, d), jnp.bfloat16), ((bh, n_q), jnp.int32),
        ((pages, PAGE, d), jnp.bfloat16), ((pages, PAGE, d), jnp.bfloat16),
        ((bh, n_q // CHUNK, slots), jnp.int32),
        ((bh, n_q // CHUNK, slots), jnp.int32),
        ((bh, MB), jnp.int32),
    )
    assert "mpmrf_prefill_gather" in text


def _pool(kv, d):
    rows = SLOTS * MB * PAGE
    return [((kv, rows, d), jnp.bfloat16), ((kv, rows, d), jnp.bfloat16),
            ((kv, rows, d), jnp.int16), ((kv, SLOTS * MB), jnp.float32),
            ((SLOTS, MB), jnp.int32)]


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_fused_paged_decode_pipeline(one_chip, name, kv, g, d):
    """Both kernels plus the XLA selection glue between them, as one
    decode-attention call of the serving engine."""
    text = _compile(
        one_chip,
        functools.partial(
            ops.fused_paged_decode_attention, key_block=PAGE,
            block_budget=math.ceil(MB / RHO), interpret=False,
        ),
        ((SLOTS, kv, g, d), jnp.bfloat16), *_pool(kv, d),
        ((SLOTS,), jnp.int32),
    )
    assert "mpmrf_decode_filter" in text and "mpmrf_decode_gather" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_fused_paged_prefill_pipeline(one_chip, name, kv, g, d):
    """Chunk prefill with 128-row key tiles over 64-row pages: page
    maxima pooled two at a time, survivors expanded to page pairs."""
    n_kb = MAX_LEN // KEY_TILE
    text = _compile(
        one_chip,
        functools.partial(
            ops.fused_paged_prefill_attention, query_block=CHUNK,
            key_block=KEY_TILE, block_budget=math.ceil(n_kb / RHO),
            interpret=False,
        ),
        ((SLOTS, kv, CHUNK * g, d), jnp.bfloat16), *_pool(kv, d),
        ((SLOTS, CHUNK * g), jnp.int32),
    )
    assert "mpmrf_prefill_filter" in text and "mpmrf_prefill_gather" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_prefill_dense_kernel(one_chip, name, kv, g, d):
    """The dense layers' streamed prefill: one chunk's folded rows of
    every slot over the shared pool, through the block table."""
    rows = SLOTS * MB * PAGE
    text = _compile(
        one_chip,
        functools.partial(pk.paged_prefill_dense_attention, key_block=PAGE),
        ((SLOTS, kv, CHUNK * g, d), jnp.bfloat16),
        ((SLOTS, CHUNK * g), jnp.int32),
        ((1, kv, rows, d), jnp.bfloat16), ((1, kv, rows, d), jnp.bfloat16),
        ((SLOTS, MB), jnp.int32),
    )
    assert "paged_prefill_dense" in text


@pytest.mark.parametrize("name,kv,g,d", SHAPES)
def test_dense_paged_prefill_dispatch(one_chip, name, kv, g, d):
    """The dense prefill as the serving engine's dense layers call it,
    on the pool leaves a paged cache holds."""
    k, v, _, _, table = _pool(kv, d)
    text = _compile(
        one_chip,
        functools.partial(ops.dense_paged_prefill_attention, page=PAGE,
                          interpret=False),
        ((SLOTS, kv, CHUNK * g, d), jnp.bfloat16), k, v, table,
        ((SLOTS, CHUNK * g), jnp.int32),
    )
    assert "paged_prefill_dense" in text
