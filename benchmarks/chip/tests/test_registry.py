"""Discovery by name: every cell's pieces are found from BENCHMARK.json,
and a new cell or metric is new files and entries, with no edit."""

import json
import os
import shutil

import tiny  # noqa: F401
import registry

CHIP = registry.HERE


def test_every_cell_is_found():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        spec = registry.cell(w["name"])
        assert spec["chips"] == w["chips"]
        assert spec["config"]["name"] == w["config"]
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert spec["per_layer"], w["name"]
        for name, reader in spec["readers"].items():
            assert callable(reader.read), name
        assert set(spec["limits"]["limits"]) == {
            "loss_gap", "grad1_gap", "change3_gap"}
        assert spec["flops"].flops_per_token(
            spec["config"], spec["traffic"]["seq"])["total"] > 0


def test_files_and_entries_agree():
    """Every entry has its file; every cell has its limits.  (Files of
    the four-chip cell that a later PR adds, its config, traffic and
    collective readers, are there before their entries.)"""
    bench = registry.benchmark()
    assert {c["name"] for c in bench["configs"]} <= set(
        registry.names("configs"))
    assert {w["traffic"] for w in bench["workloads"]} <= set(
        registry.names("traffic"))
    assert {w["name"] for w in bench["workloads"]} == set(
        registry.names("limits"))
    assert {m["name"] for m in bench["per_layer"]} <= set(
        registry.names("metrics"))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(registry.ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_new_cell_and_metric_need_no_edit(tmp_path):
    """Copy the harness, then add a cell and a metric by new files and new
    entries only: the registry finds both, and no file that was there
    changed."""
    here = tmp_path / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".*"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    bench = registry.benchmark()
    (here / "metrics" / "new.metric.py").write_text(
        "def read(r, facts):\n    return 1.0\n")
    (here / "limits" / "stablelm-1.6b-l4.other.json").write_text(
        json.dumps({"limits": {"loss_gap": 1, "grad1_gap": 1,
                               "change3_gap": 1}}))
    (here / "traffic" / "other.json").write_text(
        (here / "traffic" / "seq2k.l2m1.json").read_text())
    bench["workloads"].append({"name": "stablelm-1.6b-l4.other",
                               "config": "stablelm-1.6b-l4",
                               "traffic": "other", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "tokens_per_s",
                               "workloads": ["stablelm-1.6b-l4.other"]})
    spec = registry.cell("stablelm-1.6b-l4.other", bench, str(here))
    assert "new.metric" in spec["readers"]
    assert spec["readers"]["new.metric"].read(None, {}) == 1.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_device_kind_fails():
    import pytest
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        registry.peaks("TPU v99")
