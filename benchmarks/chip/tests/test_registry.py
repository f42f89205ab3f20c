"""Discovery by name: every cell's pieces are found from BENCHMARK.json,
and a new cell or metric is new files and entries, with no edit."""

import json
import os
import shutil

import pytest

import tiny  # noqa: F401
import registry
import run

CHIP = registry.HERE


def _numbers():
    """The names of the numbers ``run.readings_gaps`` gives."""
    same = {"losses": [2.0, 1.9, 1.8], "grad1_norms": [1.0, 2.0],
            "change_norms": [0.1, 0.2]}
    return {k for k, v in run.readings_gaps(same, same).items()
            if isinstance(v, float)}


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
        assert spec["limits"]["limits"]
        assert set(spec["limits"]["limits"]) <= _numbers(), w["name"]
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


X_CONFIG = {
    "name": "tiny-x", "arch": "qwen3-moe-30b-a3b", "family": "x",
    "hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "moe_intermediate_size": 48, "num_experts": 4, "num_experts_per_tok": 2,
    "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 32, "d_ff": 48, "vocab_size": 256,
                "n_experts": 4, "experts_per_token": 2, "norm_eps": 1e-6},
    "reference": {"rows_per_block": 1},
}
X_FLOPS = """
def flops_per_token(cfg, seq):
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    qkvo = d * cfg["head_dim"] * (2 * cfg["num_attention_heads"]
                                  + 2 * cfg["num_key_value_heads"])
    out = {"attention": 6.0 * layers * qkvo,
           "experts": 6.0 * layers * cfg["num_experts_per_tok"] * 3 * d
           * cfg["moe_intermediate_size"],
           "head": 6.0 * d * cfg["vocab_size"]}
    out["total"] = sum(out.values())
    return out


def bytes_per_token(cfg, seq):
    return {"experts": 1000.0}
"""
X_REFERENCE = """
def program_sizes(cfg):
    return {"n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "d_ff": cfg["moe_intermediate_size"],
            "n_experts": cfg["num_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"]}
"""
SCOPE_READER = """
import program_trace

SCOPE = "{scope}"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
"""
ROOFLINE_READER = """
import program_trace

SCOPE = "experts"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    ms = t.scope_ms(SCOPE) if t else None
    if not ms:
        return None
    least_s = max(facts["flops_parts_per_step"]["experts"]
                  / facts["peak_flops_per_s"],
                  facts["bytes_parts_per_step"]["experts"]
                  / facts["peak_hbm_bytes_per_s"]) / t.chips
    return 100.0 * least_s / (ms / 1e3)
"""


def test_new_family_scope_and_roofline_need_no_edit(tmp_path, monkeypatch):
    """Copy the harness, then add a third family (a configuration, its
    FLOPs and bytes, and its reference's program sizes), a reader of a new
    named scope and a kernel-roofline reader, by new files and new entries
    only: the registry finds them, the program's configuration is checked
    against the family's sizes, the readers read their scopes and the
    work by part, and no file that was there changed."""
    import program_trace as P
    here = tmp_path / "chip"
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".*"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "tiny-x.json").write_text(json.dumps(X_CONFIG))
    (here / "flops" / "x.py").write_text(X_FLOPS)
    (here / "reference" / "x.py").write_text(X_REFERENCE)
    (here / "metrics" / "router.ms.py").write_text(
        SCOPE_READER.format(scope="router"))
    (here / "metrics" / "experts_roofline.py").write_text(ROOFLINE_READER)
    (here / "limits" / "tiny-x.seq2k.json").write_text(
        json.dumps({"limits": {"loss_gap": 1}}))
    bench = registry.benchmark()
    bench["configs"].append({"name": "tiny-x", "source": "x",
                             "file": "benchmarks/chip/configs/tiny-x.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-x.seq2k", "config": "tiny-x",
                               "traffic": "seq2k.l2m1", "chips": 1,
                               "why": "x"})
    for m in bench["per_layer"]:
        if m["name"] in ("mlp.ms", "unscoped.ms"):
            m["workloads"].append("tiny-x.seq2k")
    for name in ("router.ms", "experts_roofline"):
        bench["per_layer"].append({"name": name, "unit": "%",
                                   "better": "higher",
                                   "source": "device_trace",
                                   "layer": "model", "moves": "tokens_per_s",
                                   "workloads": ["tiny-x.seq2k"]})

    spec = registry.cell("tiny-x.seq2k", bench, str(here))
    assert set(spec["readers"]) == {"mlp.ms", "unscoped.ms", "router.ms",
                                    "experts_roofline"}
    assert registry.scopes(spec["readers"]) == ("experts", "mlp", "router")
    old = registry.cell("stablelm-1.6b-l4.seq2k", bench, str(here))
    assert registry.scopes(old["readers"]) == ("adam", "attention", "ce",
                                               "mlp")

    arch = run.program_config(spec["config"], spec["reference"])
    assert (arch.n_experts, arch.experts_per_token, arch.n_kv_heads,
            arch.head_dim) == (4, 2, 2, 32)
    for key, value in [("num_key_value_heads", 4), ("head_dim", 16),
                       ("num_experts", 8), ("hidden_size", 128)]:
        bad = dict(spec["config"], **{key: value})
        with pytest.raises(ValueError):
            run.program_config(bad, spec["reference"])

    facts = run.reader_facts(spec, registry.peaks("TPU v5 lite", str(here)))
    tokens = 2 * 2048
    per_token = spec["flops"].flops_per_token(spec["config"], 2048)
    assert facts["flops_parts_per_step"] == {
        k: v * tokens for k, v in per_token.items() if k != "total"}
    assert facts["flops_per_step"] == per_token["total"] * tokens
    assert facts["bytes_parts_per_step"] == {"experts": 1000.0 * tokens}
    assert facts["peak_hbm_bytes_per_s"] == 819e9
    assert facts["scopes"] == ("experts", "mlp", "router")

    ops = [("jit(step)/mlp/router/dot_general", 0, 10),
           ("jit(step)/transpose(jvp(mlp))/experts/dot_general", 10, 40),
           ("jit(step)/mlp/mul", 40, 50), ("jit(step)/add", 50, 60)]
    ev = P.Events([ops], [[(s, e) for _, s, e in ops]],
                  [("engine.step", 0, 60), ("sync", 60, 61)])
    monkeypatch.setattr(P, "load",
                        lambda path=None, scopes=(): P.reduce(ev, scopes))
    got = {n: r.read(None, facts) for n, r in spec["readers"].items()}
    assert got["router.ms"] == pytest.approx(10e-6)
    assert got["mlp.ms"] == pytest.approx(10e-6)
    assert got["unscoped.ms"] == pytest.approx(10e-6)
    least = max(facts["flops_parts_per_step"]["experts"] / 197e12,
                1000.0 * tokens / 819e9)
    assert got["experts_roofline"] == pytest.approx(100 * least / 30e-9)
    # in a cell that lists neither new reader, their ops stay in mlp
    old_facts = run.reader_facts(old, registry.peaks("TPU v5 lite"))
    assert old["readers"]["mlp.ms"].read(None, old_facts) == \
        pytest.approx(50e-6)

    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_device_kind_fails():
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        registry.peaks("TPU v99")
