"""Multi-device integration tests (subprocess with 8 fake host devices).

These are the system-level correctness gates:

* the Cephalo SPMD train step (layered GA, uneven state) is bit-compatible
  with single-device training (Eq. 1 + ZeRO-3 + layered schedule);
* layered GA moves ~ℓ× fewer AllGather bytes than per-microbatch FSDP-GA
  (paper Fig. 4/8, measured on real HLO);
* GSPMD serving shardings produce the same logits as unsharded decode.
"""

import pytest


@pytest.mark.integration
@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mamba2-370m",
                                  "stablelm-2-1.6b"],
                         ids=["dense", "ssm", "stablelm2"])
def test_spmd_step_matches_reference(subproc, arch):
    out = subproc(f"ARCH = {arch!r}\n" + """
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_arch
from repro.core.layered_ga import CephaloProgram
from repro.models import model as M
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.data.pipeline import SyntheticStream, DataConfig, make_homogeneous_batch
from repro.launch.mesh import make_mesh

cfg = get_arch(ARCH).reduced()
mesh = make_mesh((2, 4), ("data", "model"))
N, ell, m, seq = 8, 2, 2, 32
B = N * ell * m
stream = SyntheticStream(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq, seed=0))
hb = make_homogeneous_batch(stream, 0, B)
batch = {k: jnp.asarray(hb[k].reshape(N, ell, m, seq)) for k in ("tokens", "labels", "weights")}

def reference(prog, state):
    params0 = prog.gather_params(state)
    full = {k: jnp.asarray(hb[k]) for k in ("tokens", "labels", "weights")}
    ref_loss, _ = M.loss_fn(cfg, params0, full)
    g = jax.grad(lambda p: M.loss_fn(cfg, p, full)[0])(params0)
    m0, v0 = adam_init(params0)
    p1, _, _ = adam_update(AdamConfig(lr=1e-3), params0, g, m0, v0, jnp.int32(1))
    return float(ref_loss), p1

for mode, ratios in (("layered", None), ("per_microbatch", None),
                     ("layered", [0.3, 0.2, 0.15, 0.1, 0.1, 0.05, 0.05, 0.05])):
    prog = CephaloProgram(cfg, mesh, ratios=ratios, ell=ell, m=m, seq=seq,
                          ga_mode=mode, adam=AdamConfig(lr=1e-3))
    state = prog.init_state(jax.random.PRNGKey(0))
    ref_loss, ref_p1 = reference(prog, state)
    new_state, loss = prog.jit_step()(state, batch)
    assert abs(float(loss) - ref_loss) < 1e-3, (mode, float(loss), ref_loss)
    p1 = prog.gather_params(new_state)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, ref_p1)))
    assert err < 3e-4, (mode, ratios, err)
    print(f"{mode} ratios={'uneven' if ratios else 'even'}: OK err={err:.2e}")
print("ALL-OK")
""")
    assert "ALL-OK" in out


@pytest.mark.integration
def test_layered_ga_reduces_collective_traffic(subproc):
    """Fig. 4/8: per-microbatch FSDP-GA pays ~ell× the per-unit collective
    traffic of layered GA.  Measured on the compiled HLO of the real train
    step (8 devices, unrolled loops).

    Measured detail worth knowing: when the microbatch loop is unrolled,
    XLA's CSE merges the *AllGathers* of identical param shards across
    microbatches (at the cost of keeping gathered params live — exactly
    the memory layered GA avoids by construction); the *ReduceScatters*
    carry distinct gradients and cannot be merged, so they expose the raw
    ℓ× collective structure of FSDP-GA.
    """
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import get_arch
from repro.launch.mesh import make_mesh
from repro.core.layered_ga import CephaloProgram
from repro.roofline.analysis import parse_collectives

cfg = get_arch("stablelm-1.6b").reduced()
mesh = make_mesh((2, 4), ("data", "model"))
ell = 4

def coll(mode):
    prog = CephaloProgram(cfg, mesh, ell=ell, m=1, seq=32, ga_mode=mode,
                          unroll=True)
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.state_shapes().items()}
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.batch_shapes().items()}
    hlo = jax.jit(prog.build()).lower(state, batch).compile().as_text()
    return parse_collectives(hlo)

cl = coll("layered")
cp = coll("per_microbatch")
rs_ratio = cp.counts.get("reduce-scatter", 0) / \
    max(cl.counts.get("reduce-scatter", 1), 1)
print("layered:", cl.counts)
print("per-microbatch:", cp.counts)
print("reduce-scatter count ratio:", rs_ratio)
assert rs_ratio >= ell * 0.8, f"expected ~{ell}x RS, got {rs_ratio:.2f}"
# AllGathers must NOT grow for layered GA (and CSE may shrink the
# baseline's — see docstring)
assert cl.counts.get("all-gather", 0) <= cp.counts.get("all-gather", 0) + 1
print("ALL-OK")
""", timeout=1200)
    assert "ALL-OK" in out


@pytest.mark.integration
def test_sharded_decode_matches_unsharded(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import get_arch, InputShape
from repro.launch import serving
from repro.launch.mesh import make_mesh
from repro.models import model as M

cfg = get_arch("stablelm-1.6b").reduced()
mesh = make_mesh((2, 4), ("data", "model"))
B, S = 4, 64
params = M.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

# unsharded reference
logits_ref, caches = M.prefill(cfg, params, toks, max_len=S + 4)
nt = jnp.argmax(logits_ref[:, -1], -1).astype(jnp.int32)[:, None]
pos = jnp.full((B,), S, jnp.int32)
dec_ref, _ = M.decode_step(cfg, params, caches, nt, pos)

# sharded: place under serving shardings and run the jitted fns
shape = InputShape("t", S + 4, B, "decode")
p_sh = serving.param_shardings(cfg, mesh)
params_s = jax.device_put(params, p_sh)
c_sh = serving.cache_shardings(cfg, mesh, B, S + 4)
tok_sh, pos_sh = serving.batch_sharding(mesh, B)

prefill = jax.jit(lambda p, t: M.prefill(cfg, p, t, max_len=S + 4),
                  in_shardings=(p_sh, tok_sh))
logits_s, caches_s = prefill(params_s, jax.device_put(toks, tok_sh))
caches_s = jax.device_put(caches_s, c_sh)
decode = jax.jit(lambda p, c, t, q: M.decode_step(cfg, p, c, t, q),
                 in_shardings=(p_sh, c_sh, tok_sh, pos_sh))
dec_s, _ = decode(params_s, caches_s, jax.device_put(nt, tok_sh),
                  jax.device_put(pos, pos_sh))
err_p = float(jnp.abs(logits_ref - logits_s).max())
err_d = float(jnp.abs(dec_ref - dec_s).max())
print("prefill err", err_p, "decode err", err_d)
assert err_p < 2e-3 and err_d < 2e-3
print("ALL-OK")
""")
    assert "ALL-OK" in out


@pytest.mark.integration
def test_hetero_mpmd_equivalence():
    """MPMD loopback trainer (single device, no subprocess needed)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_arch
    from repro.core import device_specs as D
    from repro.core.cost_model import analytic_cluster_model
    from repro.core.hetero_trainer import HeteroTrainer
    from repro.core.model_stats import build_model_stats
    from repro.core.planner import solve
    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.models import model as M
    from repro.optim.adam import AdamConfig, adam_init, adam_update

    cfg = get_arch("tiny-llama").reduced()
    seq = 32
    cluster = D.Cluster([D.L4, D.A6000, D.P40, D.P100], 50, "mini")
    cm = analytic_cluster_model(cluster, build_model_stats(cfg, seq))
    plan = solve(cm, 16)
    assert plan.feasible
    tr = HeteroTrainer(cfg, plan, AdamConfig(lr=1e-3), seq_len=seq)
    shards = tr.init_shards(jax.random.PRNGKey(0))
    stream = SyntheticStream(DataConfig(cfg.vocab_size, seq, seed=1))
    big = stream.sample(0, 16)

    params0 = tr.software_allgather(shards)
    batch = {"tokens": jnp.asarray(big[:, :-1]),
             "labels": jnp.asarray(big[:, 1:]),
             "weights": jnp.full((16, seq), 1.0 / (16 * seq))}
    ref_loss, _ = M.loss_fn(cfg, params0, batch)
    g = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params0)
    m0, v0 = adam_init(params0)
    ref_p1, _, _ = adam_update(AdamConfig(lr=1e-3), params0, g, m0, v0,
                               jnp.int32(1))

    shards1, loss = tr.step(shards, big)
    assert abs(loss - float(ref_loss)) < 1e-3
    p1 = tr.software_allgather(shards1)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, ref_p1)))
    assert err < 3e-4

    # memory really is ∝ r_i (ragged shards)
    for r in range(plan.n):
        nbytes = sum(v.nbytes for gname in (g2.name for g2 in tr.groups)
                     for v in shards[r][gname].values())
        expected = plan.ranks[r].state_ratio
        total = sum(
            sum(v.nbytes for v in shards[q][gname].values())
            for q in range(plan.n)
            for gname in (g2.name for g2 in tr.groups))
        assert abs(nbytes / total - expected) < 0.05


@pytest.mark.integration
@pytest.mark.slow
def test_dryrun_one_production_mesh(subproc):
    """The real dry-run entry point on the 256-chip mesh (smallest arch)."""
    out = subproc("""
from repro.launch.dryrun import dryrun_one
import tempfile
with tempfile.TemporaryDirectory() as d:
    rec = dryrun_one("mamba2-370m", "train_4k", multi_pod=False, out_dir=d)
    assert rec["status"] == "ok", rec.get("error")
    rec2 = dryrun_one("mamba2-370m", "decode_32k", multi_pod=False, out_dir=d)
    assert rec2["status"] == "ok", rec2.get("error")
print("ALL-OK")
""", n_devices=512, timeout=2400)
    assert "ALL-OK" in out


@pytest.mark.integration
def test_hsdp_state_axes_matches_reference(subproc):
    """Beyond-paper HSDP: state sharded over 'model' only, replicated over
    'data' (grad all-reduce across replicas) must train identically."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import get_arch
from repro.launch.mesh import make_mesh
from repro.core.layered_ga import CephaloProgram
from repro.models import model as M
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.data.pipeline import SyntheticStream, DataConfig, make_homogeneous_batch

cfg = get_arch("stablelm-1.6b").reduced()
mesh = make_mesh((2, 4), ("data", "model"))
N, ell, m, seq = 8, 1, 2, 32
B = N * ell * m
stream = SyntheticStream(DataConfig(cfg.vocab_size, seq, seed=0))
hb = make_homogeneous_batch(stream, 0, B)
batch = {k: jnp.asarray(hb[k].reshape(N, ell, m, seq))
         for k in ("tokens", "labels", "weights")}
full = {k: jnp.asarray(hb[k]) for k in ("tokens", "labels", "weights")}
prog = CephaloProgram(cfg, mesh, ell=ell, m=m, seq=seq,
                      adam=AdamConfig(lr=1e-3), state_axes=("model",))
assert prog.n_state == 4 and prog.replica_axes == ("data",)
state = prog.init_state(jax.random.PRNGKey(0))
params0 = prog.gather_params(state)
ref_loss, _ = M.loss_fn(cfg, params0, full)
g = jax.grad(lambda p: M.loss_fn(cfg, p, full)[0])(params0)
m0, v0 = adam_init(params0)
ref_p1, _, _ = adam_update(AdamConfig(lr=1e-3), params0, g, m0, v0,
                           jnp.int32(1))
ns, loss = prog.jit_step()(state, batch)
p1 = prog.gather_params(ns)
err = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.abs(a - b).max()), p1, ref_p1)))
assert abs(float(loss) - float(ref_loss)) < 1e-3 and err < 3e-4, (
    float(loss), float(ref_loss), err)
print("ALL-OK")
""")
    assert "ALL-OK" in out
