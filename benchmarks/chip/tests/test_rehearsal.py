"""CPU rehearsals of a whole run at tiny sizes, through ``run.run`` with
the look for a chip skipped: sound runs come out ``correct``, and every
fault of ``faults.py`` that a cell can have, planted underneath the timed
path, comes out not correct."""

import os
import subprocess
import sys
import time

import pytest

import tiny
import faults as F
import run

PEAKS = {"bf16_flops_per_s": 1e12}
UNEVEN = ((2, 1, 0.126953125), (0, 0, 0.2060546875),
          (3, 1, 0.3330078125), (3, 1, 0.333984375))


def _run(cfg, seed=11, **kw):
    import jax
    res = run.run(tiny.spec(cfg, **kw), seed, 0.3, False, jax.devices(),
                  time.monotonic(), PEAKS)
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    return res


@pytest.mark.parametrize("cfg", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_sound_run_is_correct(cfg):
    res = _run(cfg, seed=2 ** 31 + 12345)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "mfu", "hbm_gib",
                                   "setup_s"}
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "row_altered"])
@pytest.mark.parametrize("cfg", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_fault_is_not_correct(cfg, fault):
    with F.planted(fault):
        res = _run(cfg)
    assert not res["correct"], res["checks"]


def test_control_fails_where_the_program_passes():
    """The fp8 control in the program's place reads several times the
    program's gaps on the same weights and blocks."""
    import jax
    import calibrate
    sp = tiny.spec(tiny.DENSE)
    _, _, engine = run.build(sp, jax.devices())
    gaps, pool, ref = calibrate.program_gaps(sp, engine, 5, jax.devices())
    ctl = run.readings_gaps(run.reference_readings(sp, 5, pool, "fp8"), ref)
    assert ctl["loss_gap"] > 3 * gaps["loss_gap"]
    assert ctl["grad1_gap"] > 3 * gaps["grad1_gap"]


FOUR = """
import sys, time
sys.path.insert(0, {tests!r})
import tiny, run, faults as F, jax
assert len(jax.devices()) == 4
sp = tiny.spec(tiny.DENSE, traffic=tiny.mix(ranks={uneven!r}), chips=4)
ok = run.run(sp, 3, 0.3, False, jax.devices(), time.monotonic(), {{}})
with F.planted("no_exchange"):
    bad = run.run(sp, 3, 0.3, False, jax.devices(), time.monotonic(), {{}})
print("RESULT", ok["correct"], bad["correct"])
"""


def test_uneven_four_devices():
    """The uneven plan over four host devices: correct, and not correct
    with the ReduceScatter left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(tests=tiny.HERE, uneven=UNEVEN).replace(
        "{}", "{'bf16_flops_per_s': 1e12}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "RESULT True False" in out.stdout, out.stderr[-3000:]


@pytest.mark.parametrize("cfg", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_reference_matches_program_in_float32(cfg):
    """With the program computing in float32 too, the reference's loss is
    the program's own model loss to float32 rounding."""
    import dataclasses
    import jax
    from reference import common as C
    from repro.models import model as M
    sp = tiny.spec(cfg)
    arch = dataclasses.replace(run.program_config(sp["config"], sp["reference"]),
                               dtype="float32")
    params = run.weights(sp, 9)
    import traffic
    block = traffic.make_pool(sp["traffic"], cfg["vocab_size"], 9)[0]
    batch = C.batch_from_block(block)
    with jax.default_matmul_precision("highest"):
        want = float(M.loss_fn(arch, params, batch)[0])
    loss = sp["reference"].make_loss(sp["config"], C.Precision("float32"))
    got = float(jax.jit(loss)(params, batch["tokens"], batch["labels"],
                              batch["weights"]))
    assert abs(got - want) < 1e-5 * abs(want), (got, want)
