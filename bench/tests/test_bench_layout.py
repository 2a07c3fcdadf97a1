"""BENCHMARK.json resolves to files by name, and a cell is added with
files and an entry only."""

import json
import os
import shutil

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves():
    bench = _bench()
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"])
        assert spec["cfg"]["name"] == w["config"]
        assert spec["traffic"]["engine"]["batch_slots"] > 0
        assert spec["limits"].get("logit_gap_mean") is not None, w["name"]
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_every_per_layer_metric_has_a_reader():
    for m in _bench()["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_config_files_keep_their_sources():
    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(p, "rb").read() for p in
              (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = _bench()
    mix = dict(harness._json(os.path.join(ROOT, "bench", "traffic",
                                          "chat-short.json")))
    mix["rate_per_s"] = 0.25
    (tmp_path / "bench" / "traffic" / "chat-slow.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "limits" / "phi3.chat-slow.json").write_text(
        json.dumps({"logit_gap_mean": 1.0}))
    bench["workloads"].append({
        "name": "phi3.chat-slow", "config": "phi3-mini-3.8b",
        "traffic": "chat-slow", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("sched.prefill_share", "device.idle_share"):
            m["workloads"].append("phi3.chat-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_cell("phi3.chat-slow", root=str(tmp_path))
    assert spec["traffic"]["rate_per_s"] == 0.25
    assert spec["limits"]["logit_gap_mean"] == 1.0
    assert {m["name"] for m in spec["end_to_end"]} == {
        "ttft_p90_ms", "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {
        "sched.prefill_share", "device.idle_share"}
    after = {p: open(p, "rb").read() for p in before}
    assert after == before            # no existing file was edited
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root=str(tmp_path))
