"""End-to-end behaviour tests: plan → train → loss ↓, on the MPMD hetero
runtime (the paper's full pipeline: profile → optimize → train)."""

import jax

from repro.configs.base import get_arch
from repro.core import device_specs as D
from repro.core.cost_model import analytic_cluster_model
from repro.core.hetero_trainer import HeteroTrainer
from repro.core.model_stats import build_model_stats
from repro.core.planner import solve
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.optim.adam import AdamConfig


def test_end_to_end_hetero_training_loss_decreases():
    cfg = get_arch("stablelm-1.6b").reduced()
    seq, batch = 32, 16
    cluster = D.Cluster([D.L4, D.A6000, D.P40, D.P100], 50, "mini")
    cm = analytic_cluster_model(cluster, build_model_stats(cfg, seq))
    plan = solve(cm, batch)
    assert plan.feasible, plan.infeasible_reason

    trainer = HeteroTrainer(cfg, plan, AdamConfig(lr=2e-3), seq_len=seq)
    shards = trainer.init_shards(jax.random.PRNGKey(0))
    stream = SyntheticStream(DataConfig(cfg.vocab_size, seq, seed=3))

    losses = []
    for step in range(8):
        shards, loss = trainer.step(shards, stream.sample(step, batch))
        losses.append(loss)
    assert losses[-1] < losses[0] - 0.1, losses
    sim = trainer.simulated_iteration_seconds()
    assert sim["iteration_s"] > 0 and sim["throughput_samples_s"] > 0


def test_serving_sharding_rules_cover_all_archs():
    """Every assigned arch gets valid (rank-consistent) serving specs."""
    from repro.configs.base import ASSIGNED
    from repro.launch import serving
    from repro.models import model as M

    for arch in ASSIGNED:
        cfg = get_arch(arch)
        shapes = jax.eval_shape(
            lambda c=cfg: M.init_params(c, jax.random.PRNGKey(0)))

        def check(path, leaf):
            spec = serving._leaf_spec(
                serving_mesh, serving._path_names(path), leaf.shape)
            assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is not None:
                    n = serving._axes_size(serving_mesh, ax)
                    assert dim % n == 0, (path, dim, ax)

        # emulate the production mesh geometry without devices
        class ServingMesh:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        serving_mesh = ServingMesh()
        jax.tree_util.tree_map_with_path(check, shapes)
