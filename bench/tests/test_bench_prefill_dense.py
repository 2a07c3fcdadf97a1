"""The dense layers' prefill metrics of ``qwen3-14b-8l.agent-shared``:
the cost of a prefill wave counted by hand, the roofline reader on a
host trace whose ``serve.prefill.chunk`` spans carry their args, and
the cell resolving with both new metrics.

The host trace is recorded here on the CPU: it has the spans and no
device plane, so the device side of a run is a stand-in summary
giving the kernel's time and the prefill module's executions."""

import os
import shutil
import time

import jax
import pytest

from bench import harness, peaks
from bench.costs import paged_prefill_dense

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
CELL = "qwen3-14b-8l.agent-shared"
NEW = ("attn.prefill_dense_ms", "paged_prefill_dense_roofline")
CFG = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "energon": {"min_prune_layer": 2}}
V5E = peaks.peaks("TPU v5 lite")


def test_wave_cost_by_hand():
    # slot a: rows 100..102 (3 rows, extent 103); slot b: rows 0..1
    flops, nbytes = paged_prefill_dense.cost(CFG, [(100, 3), (0, 2)])
    pairs = (101 + 102 + 103) + (1 + 2)
    assert flops == 4 * 4 * 16 * pairs
    kv_rows = 103 + 2
    assert nbytes == kv_rows * 2 * 16 * 2 * 2 + 5 * 4 * 16 * 2 * 2


def test_empty_wave_costs_nothing():
    assert paged_prefill_dense.cost(CFG, []) == (0.0, 0)


class _Summary:
    """The device side of a traced run: the kernel's summed ns and the
    prefill module's executions."""

    def __init__(self, kernel_ns, prefill_runs):
        self._kernel_ns, self._runs = kernel_ns, prefill_runs

    def kernel_ns(self, kernel):
        return self._kernel_ns if kernel == "paged_prefill_dense" else None

    def module_times(self, prefix):
        return [1e6] * self._runs if prefix == "jit_prefill" else []


@pytest.fixture(scope="module")
def host_trace(tmp_path_factory):
    """Three chunk waves: two inside ``bench.window`` (two live slots,
    then one, whose single value the profiler keeps as an int) and one
    after it, which the reader leaves out."""
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("serve.prefill.chunk",
                                          starts="16384 0", rows="128 37"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("serve.prefill.chunk",
                                          starts="16512", rows="64"):
            time.sleep(0.002)
    with jax.profiler.TraceAnnotation("serve.prefill.chunk",
                                      starts="9", rows="9"):
        time.sleep(0.002)
    jax.profiler.stop_trace()
    return str(out)


def _read(monkeypatch, trace_dir, summary, metric):
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    run = harness.RunData(CFG, 3.0, {}, summary, [], V5E)
    return harness.load_reader(metric)(run)


def test_roofline_reads_the_waves_in_the_window(monkeypatch, host_trace):
    kernel_ns = 2e6
    got = _read(monkeypatch, host_trace, _Summary(kernel_ns, 2),
                "paged_prefill_dense_roofline")
    least = 0.0
    for wave in ([(16384, 128), (0, 37)], [(16512, 64)]):
        flops, nbytes = paged_prefill_dense.cost(CFG, wave)
        least += 2 * max(flops / V5E["bf16_flops"],
                         nbytes / V5E["hbm_bytes_per_s"])
    assert got == pytest.approx(100.0 * least / (kernel_ns * 1e-9))


def test_dense_ms_is_kernel_time_per_prefill_run(monkeypatch, host_trace):
    got = _read(monkeypatch, host_trace, _Summary(3e6, 4),
                "attn.prefill_dense_ms")
    assert got == pytest.approx(0.75)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_kernel_reads_none(monkeypatch, tmp_path,
                                                 metric):
    """The parent's program (a recorded docqa slice: one prefill chunk,
    no dense kernel, no chunk spans), an untraced run, and a slice
    without prefill all read None, and nothing raises."""
    from bench import trace_reduce

    fixture = os.path.join(FIXTURES, "docqa_scoped.xplane.pb")
    shutil.copy(fixture, tmp_path)
    summary = trace_reduce.reduce_trace(fixture)
    assert summary.module_times("jit_prefill")
    assert _read(monkeypatch, str(tmp_path), summary, metric) is None
    assert _read(monkeypatch, str(tmp_path), None, metric) is None
    assert _read(monkeypatch, str(tmp_path), _Summary(None, 0),
                 metric) is None


def test_roofline_without_chunk_spans_reads_none(monkeypatch, tmp_path):
    shutil.copy(os.path.join(FIXTURES, "docqa_scoped.xplane.pb"), tmp_path)
    assert _read(monkeypatch, str(tmp_path), _Summary(2e6, 1),
                 "paged_prefill_dense_roofline") is None


def test_cell_resolves_with_both_new_metrics():
    spec = harness.load_cell(CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) <= names and len(names) == 16
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tok_s", "ttft_p90_ms", "setup_s"}
    assert spec["cfg"]["name"] == "qwen3-14b-8l"
    assert spec["traffic"]["engine"]["batch_slots"] == 8
    assert spec["chips"] == 1
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "ttft_p90_ms"
