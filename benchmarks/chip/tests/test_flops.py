"""FLOP counts against figures worked out by hand from the configs."""

import json
import os

import pytest

import tiny  # noqa: F401  (puts the harness on the path)
import registry

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def _flops(family):
    return registry.load_module(os.path.join(CHIP, "flops", family + ".py"))


def test_dense_l4_by_hand():
    # 4 x (4*2048^2 + 3*2048*5632) + 2048*100352 = 205,520,896 + 205,520,896
    f = _flops("dense").flops_per_token(_cfg("stablelm-1.6b-l4"), 2048)
    assert _flops("dense").matmul_params(_cfg("stablelm-1.6b-l4")) \
        == 411_041_792
    assert f["matmul"] == 6 * 411_041_792
    assert f["attention"] == 6 * 2048 * 2048 * 4          # 100.7 M
    assert f["total"] == pytest.approx(2.5669e9, rel=1e-4)


def test_dense_full_by_hand():
    f = _flops("dense").flops_per_token(_cfg("stablelm-1.6b"), 2048)
    n = 24 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2048 * 100352
    assert f["matmul"] == 6 * n
    assert f["total"] == pytest.approx(9.238e9, rel=1e-3)


def test_ssm_by_hand():
    cfg = _cfg("mamba2-370m")
    # in_proj 1024 x (4096 + 256 + 32), out_proj 2048 x 1024, head
    per_layer = 1024 * 4384 + 2048 * 1024
    n = 48 * per_layer + 1024 * 50280
    assert _flops("ssm").matmul_params(cfg) == n == 367_632_384
    f = _flops("ssm").flops_per_token(cfg, 4096)
    assert f["conv"] == 6 * 48 * 4 * (2048 + 256)
    # 2 Q N + 2 Q di + 4 di N per layer forward, x3 with the backward
    assert f["ssd"] == 3 * 48 * (2 * 256 * 128 + 2 * 256 * 2048
                                 + 4 * 2048 * 128)
    assert f["total"] == pytest.approx(2.5178e9, rel=1e-3)
