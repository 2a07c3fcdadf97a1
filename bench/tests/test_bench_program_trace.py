"""The program-trace reduction (``bench/program_trace.py``) and the
per-layer metrics that read it.

On the older recorded slice (``docqa_slice.xplane.pb``, a program with
no scopes and no ``serve.*`` spans) every scope metric reads None and
the whole step is unscoped; on a slice recorded from the scoped program
(``docqa_scoped.xplane.pb``: 1.34 s of ``phi3.docqa-shared`` on a TPU
v5 lite, 9 decode steps and one prefill chunk, taken by the harness
with its slice moved onto a tick that admits a new turn) the readers
give the numbers recorded here, and the scope buckets add up to the
decode step's op time.
"""

import os
import shutil
from collections import defaultdict

import pytest

from bench import harness, program_trace, trace_reduce

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
UNSCOPED_SLICE = os.path.join(FIXTURES, "docqa_slice.xplane.pb")
SCOPE_METRICS = ("attn.decode_ms", "attn.select_ms", "attn.page_write_ms",
                 "mlp.decode_ms")
SPAN_METRICS = ("sched.tick_host_ms",)


def _read_all(monkeypatch, tmp_path, fixture):
    """Every new reader on one fixture, found the way a run finds it."""
    shutil.copy(fixture, tmp_path)
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    run = harness.RunData({}, 3.0, {}, trace_reduce.reduce_trace(fixture),
                          [], {})
    return {m: harness.load_reader(m)(run)
            for m in SCOPE_METRICS + SPAN_METRICS + ("step.unscoped_ms",)}


def test_unscoped_program_reads_none(monkeypatch, tmp_path):
    read = _read_all(monkeypatch, tmp_path, UNSCOPED_SLICE)
    for m in SCOPE_METRICS + SPAN_METRICS:
        assert read[m] is None, m
    trace = program_trace.reduce_program_trace(UNSCOPED_SLICE)
    assert trace.steps == 7 and trace.ambiguous == []
    assert set(trace.scope_ns) == {program_trace.UNSCOPED}
    assert read["step.unscoped_ms"] == pytest.approx(
        trace.step_op_ns / 7 * 1e-6)
    # ops fill the decode module's executions: their sum is its time
    steps = trace_reduce.reduce_trace(UNSCOPED_SLICE).module_times(
        "jit_decode_step")
    assert trace.step_op_ns == pytest.approx(sum(steps), rel=1e-3)


def test_tf_ops_are_read_from_the_device_plane():
    ops = program_trace.read_tf_ops(UNSCOPED_SLICE)
    names = {}
    for (_, op), paths in ops.items():
        names.setdefault(op, set()).update(paths)
    assert len(names) == 394
    assert all(len(p) == 1 for p in names.values())    # none ambiguous
    filt = [p for op, p in names.items()
            if op.startswith("%mpmrf_decode_filter")]
    assert filt and next(iter(filt[0])).endswith(
        "/mpmrf_decode_filter/pallas_call")


def test_readers_without_a_trace_read_none(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "none"))
    untraced = harness.RunData({}, 3.0, {}, None, [], {})
    traced_elsewhere = harness.RunData({}, 3.0, {}, object(), [], {})
    for m in SCOPE_METRICS + SPAN_METRICS + ("step.unscoped_ms",):
        assert harness.load_reader(m)(untraced) is None, m
        assert harness.load_reader(m)(traced_elsewhere) is None, m


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(decode_step)/while/body/closed_call/attention/select/"
     "jit(mpmrf_paged_filter_scores)/mpmrf_decode_filter/pallas_call",
     "attention/select"),
    ("jit(decode_step)/while/body/closed_call/attention/page_write/"
     "scatter", "attention/page_write"),
    ("jit(decode_step)/while/body/closed_call/attention/gather/"
     "mpmrf_decode_gather/pallas_call", "attention/gather"),
    # an op named gather directly in the attention scope is no scope
    ("jit(decode_step)/while/body/closed_call/attention/jit(_take)/gather",
     "attention"),
    ("jit(decode_step)/while/body/closed_call/attention/gather",
     "attention"),
    ("jit(decode_step)/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(decode_step)/head/dot_general", "head"),
    ("jit(decode_step)/while/body/closed_call/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_matches_whole_components(tf_op, scope):
    assert program_trace.scope_of(tf_op) == scope


def test_idle_goes_to_the_innermost_span():
    spans = [(0, 100, "bench.tick"), (10, 90, "serve.tick"),
             (20, 50, "serve.decode.sync"), (95, 100, "bench.x")]
    segments = program_trace._segments(spans, 0, 120)
    idle = defaultdict(float)
    program_trace._add_idle(idle, [(5, 30), (40, 60), (99, 110)], segments)
    assert dict(idle) == {"bench.tick": 5.0, "serve.tick": 20.0,
                          "serve.decode.sync": 20.0, "bench.x": 1.0,
                          "outside spans": 10.0}


# --------------------------------------------------------------------------
# a slice recorded from the scoped program
# --------------------------------------------------------------------------

SCOPED_SLICE = os.path.join(FIXTURES, "docqa_scoped.xplane.pb")
#: what the readers gave when the slice was recorded
RECORDED = {
    "attn.decode_ms": 87.91149833333333,
    "attn.select_ms": 51.78730644444445,
    "attn.page_write_ms": 9.722544,
    "mlp.decode_ms": 6.527559111111111,
    "step.unscoped_ms": 26.815984666666665,
    "sched.tick_host_ms": 11.078204444444443,
}


@pytest.fixture(scope="module")
def scoped():
    return program_trace.reduce_program_trace(SCOPED_SLICE)


def test_scoped_recorded_numbers(monkeypatch, tmp_path):
    read = _read_all(monkeypatch, tmp_path, SCOPED_SLICE)
    for m, want in RECORDED.items():
        assert read[m] == (None if want is None else pytest.approx(want)), m


def test_scoped_slice_holds_one_prefill_chunk(scoped):
    chunks = scoped.span_ns("serve.prefill")
    assert chunks == [pytest.approx(185.406406e6)]
    (sync,) = scoped.span_ns("serve.prefill.sync")
    assert 0 < sync < chunks[0]


def test_scope_buckets_add_up_to_the_decode_step(scoped):
    assert scoped.ambiguous == []
    tops = [scoped.scope_ms(s) for s in
            ("attention", "mlp", "head", program_trace.UNSCOPED)]
    steps = trace_reduce.reduce_trace(SCOPED_SLICE).module_times(
        "jit_decode_step")
    step_ms = sum(steps) / len(steps) * 1e-6
    assert len(steps) == scoped.steps
    assert sum(tops) == pytest.approx(scoped.step_op_ns / scoped.steps
                                      * 1e-6, rel=1e-9)
    assert sum(tops) == pytest.approx(step_ms, rel=5e-3)
    assert scoped.scope_ms(program_trace.UNSCOPED) < 0.25 * step_ms
    nested = sum(scoped.scope_ms(s) for s in (
        "attention/select", "attention/gather", "attention/page_write"))
    assert nested < scoped.scope_ms("attention")


def test_idle_in_ticks_lies_under_serve_spans(scoped):
    in_tick = sum(ns for name, ns in scoped.idle_ns.items()
                  if name == "bench.tick" or name.startswith("serve."))
    under_child = sum(ns for name, ns in scoped.idle_ns.items()
                      if name.startswith("serve.") and name != "serve.tick")
    assert under_child >= 0.75 * in_tick > 0
    ticks = scoped.span_ns("serve.tick")
    assert len(ticks) == len(scoped.span_ns("serve.decode.sync")) > 0


# --------------------------------------------------------------------------
# the prefill chunk, from the window's counters
# --------------------------------------------------------------------------

@pytest.mark.parametrize("counters,want", [
    ({"prefill_time": 3.3, "prefill_dispatches": 22}, 150.0),
    ({"prefill_time": 0.1854, "prefill_dispatches": 1}, 185.4),
    ({"prefill_time": 0.0, "prefill_dispatches": 0}, None),
    ({}, None),
])
def test_prefill_chunk_reads_the_window_counters(counters, want):
    read = harness.load_reader("sched.prefill_chunk_ms")
    # the window's counters are enough: a slice without a prefill span,
    # or no trace at all, reads the same
    for trace in (None, object()):
        run = harness.RunData({}, 51.0, counters, trace, [], {})
        got = read(run)
        assert got == (None if want is None else pytest.approx(want))
