"""The ``stablelm`` family (the published StableLM 2) and its two
four-chip cells, found and checked as new files alone: the cells, the
configuration against the registered architecture, the reference's tree,
its FLOPs, and a rehearsal of the uneven cell on four host devices."""

import copy
import os
import subprocess
import sys

import pytest

import tiny
import registry
import run

CELLS = ("stablelm-2-1.6b.even4", "stablelm-2-1.6b.uneven4")

#: the published configuration at a tiny size (the file's keys, tiny.DENSE's
#: sizes)
TINY = {**{k: v for k, v in tiny.DENSE.items() if k != "rms_norm_eps"},
        "name": "tiny-stablelm2", "arch": "stablelm-2-1.6b",
        "family": "stablelm", "partial_rotary_factor": 0.25,
        "use_qkv_bias": True, "qk_layernorm": False,
        "use_parallel_residual": False, "hidden_act": "silu",
        "layer_norm_eps": 1e-5}
#: the limits' names of the cells; at this size every gap is larger
TINY_LIMITS = {"limits": {"loss_gap": 0.02, "grad1_gap": 0.05,
                          "change3_median_gap": 0.05}}


@pytest.mark.parametrize("name", CELLS)
def test_cells_are_found(name):
    c = registry.cell(name)
    assert c["chips"] == 4
    assert c["config"]["family"] == "stablelm"
    assert c["config"]["reduced"] == {}
    assert sum(r["ell"] * r["m"] for r in c["traffic"]["ranks"]) == 8
    assert c["traffic"]["seq"] == 2048
    assert sum(r["state_ratio"] for r in c["traffic"]["ranks"]) == \
        pytest.approx(1.0)
    assert set(c["readers"]) == {"collective.ms", "collective.exposed_ms",
                                 "gather.ms", "scatter.ms"}
    assert registry.scopes(c["readers"]) == ("unit_gather", "unit_scatter")
    assert set(c["limits"]["limits"]) <= {
        "loss_gap", "grad1_gap", "grad1_median_gap", "change3_gap",
        "change3_median_gap"}


def test_program_config_takes_the_published_model_only():
    c = registry.cell(CELLS[0])
    arch = run.program_config(c["config"], c["reference"])
    assert (arch.name, arch.norm_kind, arch.rope_fraction, arch.qkv_bias,
            arch.n_layers) == ("stablelm-2-1.6b", "layernorm", 0.25, True,
                               24)
    variant = dict(c["config"], arch="stablelm-1.6b")
    with pytest.raises(ValueError):
        run.program_config(variant, c["reference"])


def test_reference_tree_is_the_programs():
    c = registry.cell(CELLS[1])
    run.check_tree(c, run.program_config(c["config"], c["reference"]))


def test_flops_are_the_dense_count():
    """6 N + 6 S d L with N = L (4 d^2 + 3 d ff) + d V: 9.24 GFLOP a
    token at seq 2048."""
    c = registry.cell(CELLS[0])
    d, ff, L, V, S = 2048, 5632, 24, 100352, 2048
    n = L * (4 * d * d + 3 * d * ff) + d * V
    got = c["flops"].flops_per_token(c["config"], S)
    assert got["total"] == 6 * n + 6 * S * d * L
    assert round(got["total"] / 1e9, 2) == 9.24


FOUR = """
import sys, time, copy
sys.path.insert(0, {tests!r})
import jax, tiny, run, faults as F
import test_stablelm_family as T
assert len(jax.devices()) == 4
sp = tiny.spec(T.TINY, traffic=tiny.mix(ranks={uneven!r}), chips=4)
sp["limits"] = copy.deepcopy(T.TINY_LIMITS)
peaks = {{"bf16_flops_per_s": 1e12}}
ok = run.run(sp, 2 ** 31 + 5, 0.3, False, jax.devices(), time.monotonic(),
             peaks)
with F.planted("no_exchange"):
    bad = run.run(sp, 2 ** 31 + 5, 0.3, False, jax.devices(),
                  time.monotonic(), peaks)
print("CHECKS", ok["checks"], bad["checks"])
print("RESULT", ok["correct"], bad["correct"])
"""


def test_uneven_four_devices():
    """The uneven plan over four host devices at a tiny size: correct, and
    not correct with the ReduceScatter left out."""
    from test_rehearsal import UNEVEN
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c",
                          FOUR.format(tests=tiny.HERE, uneven=UNEVEN)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "RESULT True False" in out.stdout, (out.stdout[-2000:],
                                               out.stderr[-3000:])


def test_sound_run_is_correct_on_one_device():
    import time
    import jax
    sp = tiny.spec(copy.deepcopy(TINY))
    sp["limits"] = copy.deepcopy(TINY_LIMITS)
    res = run.run(sp, 2 ** 31 + 12345, 0.3, False, jax.devices(),
                  time.monotonic(), {"bf16_flops_per_s": 1e12})
    assert res["correct"], res["checks"]


def test_exchange_scopes_split_the_ops():
    """The cells' scope set takes the unit exchange's ops, the forward's
    and the recomputed ones, and leaves the lax gathers of the loss (op
    names that end ``/gather``) unscoped."""
    import program_trace as P
    from test_program_trace import _ev
    names = [
        "jit(step)/shard_map/jvp()/while/body/closed_call/unit_gather/"
        "all_gather",
        "jit(step)/shard_map/transpose(jvp(jvp()))/checkpoint/unit_scatter/"
        "reduce_scatter",
        "jit(step)/shard_map/jvp(vmap(ce))/closed_call/while/body/"
        "closed_call/checkpoint/jit(take_along_axis)/gather",
        "jit(step)/shard_map/transpose(jvp(jvp()))/checkpoint/"
        "rematted_computation/unit_gather/concatenate"]
    scopes = registry.scopes(registry.cell(CELLS[1])["readers"])
    t = P.reduce(_ev(names, devices=4), scopes)
    assert t.scope_ms("unit_gather") == pytest.approx(
        2 * t.scope_ms("unit_scatter"))
    assert t.unscoped_ms() == pytest.approx(t.scope_ms("unit_scatter"))
