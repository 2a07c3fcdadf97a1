"""The trace reduction on a small trace captured on the chip: a 0.89-s
slice of ``phi3.docqa-shared`` (7 decode steps, TPU v5 lite), reduced
once when it was recorded; these are the numbers it gave."""

import os

import numpy as np
import pytest

from bench import harness, trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "docqa_slice.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(FIXTURE)


def test_recorded_numbers(summary):
    assert summary.devices == 1
    assert summary.window_ns == pytest.approx(890169829.0)
    assert summary.busy_ns == pytest.approx(865274839.0)
    assert summary.kernel_ns("mpmrf_decode_filter") == \
        pytest.approx(351645960.0)
    assert summary.kernel_calls("mpmrf_decode_filter") == 210   # 7 x 30
    assert summary.kernel_ns("mpmrf_decode_gather") == \
        pytest.approx(149994341.0)
    assert summary.kernel_calls("mpmrf_decode_gather") == 210
    steps = summary.module_times("jit_decode_step")
    assert len(steps) == 7 and sum(steps) == pytest.approx(851091991.0)
    assert summary.kernel_ns("no_such_kernel") is None


def test_breakdown_names_the_kernels(summary):
    b = trace_reduce.breakdown(summary)
    names = [n for n, _ in b["device_ops"]]
    assert names[:2] == ["mpmrf_decode_filter.11", "mpmrf_decode_gather.11"]
    assert not any(n.startswith("while") for n in names)
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0][0] == "bench.tick"
    assert b["idle_gaps"][0][1] == pytest.approx(0.004360932)


def test_readers_on_the_recorded_trace(summary):
    cfg = harness._json(os.path.join(harness.ROOT, "bench", "configs",
                                     "phi3-mini-3.8b.json"))
    peaks = {"bf16_flops": 197e12, "int8_ops": 393e12,
             "hbm_bytes_per_s": 819e9}
    # 7 steps of 4 decoding slots near 3.2k rows, as the slice ran
    calls = [(np.array([3150, 3180, 3200, 3230]) + i, np.ones(4, bool))
             for i in range(7)]
    data = harness.RunData(cfg, 3.0, {}, summary, calls, peaks)
    read = {m: harness.load_reader(m)(data) for m in (
        "device.idle_share", "step.decode_ms", "step.decode_mfu",
        "mpmrf_decode_filter_roofline", "mpmrf_decode_gather_roofline")}
    assert read["device.idle_share"] == pytest.approx(
        100 * (1 - 865274839.0 / 890169829.0))
    assert read["step.decode_ms"] == pytest.approx(851091991.0 / 7 / 1e6)
    for m in ("step.decode_mfu", "mpmrf_decode_filter_roofline",
              "mpmrf_decode_gather_roofline"):
        assert 0 < read[m] < 100, m
    empty = harness.RunData(cfg, 3.0, {}, None, [], peaks)
    assert harness.load_reader("mpmrf_decode_filter_roofline")(empty) is None
