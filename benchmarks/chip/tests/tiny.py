"""Tiny cells for the CPU tests: the same files and code paths as the
chip cells, at sizes a test run can hold."""

from __future__ import annotations

import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import registry  # noqa: E402

DENSE = {
    "name": "tiny-dense", "arch": "stablelm-1.6b", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000, "tie_word_embeddings": False, "rms_norm_eps": 1e-5,
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
                "head_dim": 16, "d_ff": 128, "vocab_size": 256},
    "reference": {"rows_per_block": 2},
}
SSM = {
    "name": "tiny-ssm", "arch": "mamba2-370m", "family": "ssm",
    "hidden_size": 64, "num_hidden_layers": 2, "state_size": 16,
    "expand": 2, "head_dim": 16, "n_groups": 1, "conv_kernel": 4,
    "chunk_size": 16, "vocab_size": 256, "tie_embeddings": False,
    "rms_norm_eps": 1e-5,
    "program": {"n_layers": 2, "d_model": 64, "ssm_state": 16,
                "ssm_head_dim": 16, "ssm_chunk": 16, "vocab_size": 256},
    "reference": {"rows_per_block": 1},
}
LIMITS = {"limits": {"loss_gap": 0.02, "grad1_gap": 0.05,
                     "change3_gap": 0.05}}


def mix(seq=64, ranks=((2, 1, 1.0),)):
    return {"seq": seq,
            "ranks": [{"ell": e, "m": m, "state_ratio": r}
                      for e, m, r in ranks],
            "pool_blocks": 6,
            "generator": {"kind": "bigram", "successors": 8, "noise": 0.1},
            "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}}


def spec(cfg, traffic=None, chips=1, metrics=()):
    """A cell's spec as ``registry.cell`` returns it, for a tiny config."""
    bench = registry.benchmark()
    per_layer = [m for m in bench["per_layer"] if m["name"] in metrics]
    fam = cfg["family"]
    return {
        "name": cfg["name"] + ".test", "chips": chips,
        "config": copy.deepcopy(cfg),
        "traffic": traffic or mix(),
        "limits": copy.deepcopy(LIMITS),
        "end_to_end": bench["end_to_end"],
        "per_layer": per_layer,
        "readers": {m["name"]: registry.load_module(
            os.path.join(CHIP, "metrics", m["name"] + ".py"))
            for m in per_layer},
        "flops": registry.load_module(
            os.path.join(CHIP, "flops", fam + ".py")),
        "reference": registry.load_module(
            os.path.join(CHIP, "reference", fam + ".py")),
    }
