"""The published StableLM 2 (``stablelm-2-1.6b``) on the normal training
path against the benchmark's plain reference (``reference/stablelm.py``).

At a tiny size with the reference's seeded random weights (biases drawn
non-zero), one step of ``build_train_step(substrate="shard_map")`` and
``SpmdEngine.step`` gives the reference's loss, first gradient and
parameter change after one Adam step: on one device, and on four forced
host devices under the uneven four-chip plan (ell 2/0/3/3, a rank that
holds state and computes nothing).  The repository's variant (rotary over
the whole head, no q/k/v bias) fails the same comparison.

The program computes in float32 here (the chip runs bfloat16), so the
tolerances are float32 rounding: what separates the variant is orders of
magnitude larger.
"""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.join(os.path.dirname(HERE), "benchmarks", "chip")

CFG = {
    "name": "tiny-stablelm2", "arch": "stablelm-2-1.6b", "family": "stablelm",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000, "partial_rotary_factor": 0.25,
    "use_qkv_bias": True, "qk_layernorm": False,
    "use_parallel_residual": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "layer_norm_eps": 1e-5,
}
UNEVEN4 = ((2, 1, 0.126953125), (0, 0, 0.2060546875),
           (3, 1, 0.3330078125), (3, 1, 0.333984375))
OPT = {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
SEQ = 32

#: float32 rounding between two orders of the same sums
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4
#: Adam's first step is lr x g / (|g| + eps): a change agrees to a small
#: share of lr where the gradient is well above eps (near eps a rounding
#: of the gradient moves the step by a large share of lr)
CHANGE_ATOL = 1e-2 * OPT["lr"]
CHANGE_MIN_GRAD = 100 * OPT["eps"]


def reference():
    """``reference/stablelm.py`` and ``reference/common.py``, by path."""
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    spec = importlib.util.spec_from_file_location(
        "stablelm_reference", os.path.join(CHIP, "reference", "stablelm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from reference import common
    return mod, common


def program_arch(variant=False):
    from repro.configs.base import get_arch
    arch = dataclasses.replace(
        get_arch("stablelm-2-1.6b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
        dtype="float32")
    if variant:
        arch = dataclasses.replace(arch, rope_fraction=1.0, qkv_bias=False)
    return arch


def block(seed, rows):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG["vocab_size"], (rows, SEQ + 1), np.int32)


def program_step(arch, params, big, ranks):
    """Loss, first gradient and change of one ``SpmdEngine.step``."""
    from repro.core.engine import build_train_step
    from repro.core.partition import Plan, RankPlan
    from repro.launch.mesh import make_mesh
    from repro.optim.adam import AdamConfig
    plan = Plan(model=arch.name, cluster="test",
                global_batch=sum(e * m for e, m, _ in ranks),
                ranks=[RankPlan(i, f"d{i}", m=m, ell=e, state_ratio=r)
                       for i, (e, m, r) in enumerate(ranks)])
    mesh = make_mesh((plan.n,), ("data",), devices=jax.devices()[:plan.n])
    engine = build_train_step(arch, plan, substrate="shard_map",
                              seq_len=SEQ, mesh=mesh, adam=AdamConfig(**OPT))
    state = engine.import_state({"p": params})
    state, loss = engine.step(state, big)
    prog = engine.program
    grad = jax.tree.map(lambda m: m / (1 - OPT["b1"]),
                        prog.gather_part(state, "m"))
    change = jax.tree.map(jnp.subtract, prog.gather_part(state, "p"), params)
    return loss, grad, change


def reference_step(params, big):
    ref, C = reference()
    loss_fn = ref.make_loss(CFG, C.Precision("float32"))
    batch = C.batch_from_block(big)
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.value_and_grad(loss_fn)(
            params, batch["tokens"], batch["labels"], batch["weights"])
        zeros = jax.tree.map(jnp.zeros_like, params)
        p1, _, _ = C.adam(OPT, params, grad, zeros, zeros, 1)
    return float(loss), grad, jax.tree.map(jnp.subtract, p1, params)


def gaps(ranks, variant=False, seed=3):
    """The largest relative gaps of loss and gradient, and the largest
    absolute gap of the change, program against reference.  Each gap is
    inf where the trees differ."""
    ref, C = reference()
    params = ref.init_params(CFG, C.seed_key(seed))
    big = block(seed, sum(e * m for e, m, _ in ranks))
    want_loss, want_grad, want_change = reference_step(params, big)
    if variant:
        for b in ("bq", "bk", "bv"):
            del params["stages"][0]["attn"][b]
    loss, grad, change = program_step(program_arch(variant), params, big,
                                      ranks)
    out = {"loss": abs(loss - want_loss) / abs(want_loss)}
    if jax.tree.structure(grad) != jax.tree.structure(want_grad):
        return {**out, "grad": np.inf, "change": np.inf}
    out["grad"] = max(
        float(jnp.abs(a - b).max() / jnp.abs(b).max())
        for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(want_grad)))
    out["change"] = max(
        float(jnp.where(jnp.abs(g) >= CHANGE_MIN_GRAD, jnp.abs(a - b),
                        0.0).max())
        for a, b, g in zip(jax.tree.leaves(change),
                           jax.tree.leaves(want_change),
                           jax.tree.leaves(want_grad)))
    # the key bias of the dims that rotary leaves alone adds q.b to every
    # logit of a query's row, which the softmax cancels: its gradient is
    # nought but rounding (and so below CHANGE_MIN_GRAD)
    rd = int(CFG["partial_rotary_factor"]
             * CFG["hidden_size"] // CFG["num_attention_heads"])
    bk = want_grad["stages"][0]["attn"]["bk"]
    out["bk_pass_grad"] = float(jnp.abs(bk[..., rd:]).max()
                                / jnp.abs(bk[..., :rd]).max())
    return out


def assert_matches(g):
    assert g["loss"] < LOSS_RTOL, g
    assert g["grad"] < GRAD_RTOL, g
    assert g["change"] < CHANGE_ATOL, g
    assert g["bk_pass_grad"] < 1e-4, g


def test_one_device_matches_reference():
    assert_matches(gaps(((2, 1, 1.0),)))


def test_variant_fails_the_reference():
    """Rotary over the whole head and no q/k/v bias: the loss alone is off
    by far more than its tolerance."""
    g = gaps(((2, 1, 1.0),), variant=True)
    assert g["loss"] > 100 * LOSS_RTOL, g


FOUR = """
import sys
sys.path.insert(0, {here!r})
import jax
import test_stablelm2_reference as T
assert len(jax.devices()) == 4
g = T.gaps(T.UNEVEN4)
print("GAPS", g)
T.assert_matches(g)
print("FOUR-OK")
"""


@pytest.mark.integration
def test_uneven_four_devices_match_reference(subproc):
    out = subproc(FOUR.format(here=HERE), n_devices=4)
    assert "FOUR-OK" in out, out
