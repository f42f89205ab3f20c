"""``build_train_step`` — one entry point over both Cephalo runtimes.

The engine exposes a uniform training surface for a ``(cfg, plan)`` pair::

    engine = build_train_step(cfg, plan, schedule="layered",
                              substrate="loopback")
    state = engine.init_state(jax.random.PRNGKey(0))
    state, loss = engine.step(state, big)      # big: (B, seq+1) tokens
    params = engine.gather_params(state)

Both substrates consume the same plan, the same data block, the same
UnitPlanner layouts, and any registered Schedule; the gradient math is
identical (Eq. 1), which `tests/test_engine.py` asserts numerically.

* ``substrate="shard_map"`` — the SPMD runtime: one ``shard_map`` program
  over ``plan.n`` devices, padded ``(ell_pad, m_pad)`` grids with Eq. 1
  zero-weight padding.  Requires ``jax.device_count() >= plan.n`` (or an
  explicit ``mesh``).
* ``substrate="loopback"`` — the MPMD runtime: per-rank programs with
  unpadded ``(ell_i, m_i)`` shapes and software loopback collectives;
  runs on a single device.
* ``substrate="multiproc"`` — the MPMD runtime across real OS process
  boundaries: one worker process per rank, AllGatherv / ReduceScatterv
  through the coordinator (``topology="hub"``) or peer-to-peer over
  worker↔worker ring channels (``topology="ring"``,
  :mod:`repro.core.engine.multiproc`; add ``overlap_rounds=True`` to
  prefetch each round's gathers under the previous round's compute),
  bitwise-matching loopback step for step every way.  Engines on this
  substrate own worker fleets — call :meth:`TrainEngine.close` (or use
  the engine as a context manager) when done.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, Optional, Tuple, Union

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core.engine.schedules import Schedule, get_schedule
from repro.core.partition import Plan, RankPlan
from repro.launch.mesh import make_mesh
from repro.optim.adam import AdamConfig

SUBSTRATES = ("shard_map", "loopback", "multiproc")


def homogeneous_plan(n: int, ell: int, m: int,
                     device: str = "dev") -> Plan:
    """Even plan for n identical ranks (the SPMD launcher's geometry)."""
    ranks = [RankPlan(i, device, m=m, ell=ell, state_ratio=1.0 / n)
             for i in range(n)]
    return Plan(model="homogeneous", cluster=f"{n}x{device}",
                global_batch=n * ell * m, ranks=ranks)


class TrainEngine(abc.ABC):
    """Uniform train-step surface over a (cfg, plan, schedule, substrate)."""

    cfg: ArchConfig
    plan: Plan
    schedule: Schedule

    @abc.abstractmethod
    def init_state(self, key: jax.Array) -> Any:
        """Materialize sharded training state from a PRNG key."""

    @abc.abstractmethod
    def step(self, state: Any, big: np.ndarray) -> Tuple[Any, float]:
        """One optimizer step over a (B, seq+1) token block."""

    @abc.abstractmethod
    def gather_params(self, state: Any) -> Dict[str, Any]:
        """Host-side: reassemble the full model param pytree."""

    @abc.abstractmethod
    def export_state(self, state: Any) -> Dict[str, Any]:
        """Substrate-independent full training state:
        ``{"step": int, "p"/"m"/"v": model-shaped pytrees}``.

        One AllGather per part through the engine's CollectiveSubstrate —
        the export half of elastic state migration
        (:mod:`repro.core.engine.elastic`)."""

    @abc.abstractmethod
    def import_state(self, exported: Dict[str, Any]) -> Any:
        """Lay an :meth:`export_state` payload out on THIS engine's plan:
        params and Adam moments land on the new shard layouts, the step
        counter carries over.  The import half of elastic migration."""

    def close(self) -> None:
        """Release engine-held resources (worker processes, shared
        memory).  No-op for in-process substrates; the multiproc
        substrate shuts its rank fleet down here.  Idempotent."""

    def __enter__(self) -> "TrainEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class SpmdEngine(TrainEngine):
    """shard_map substrate: the plan's padded grid on plan.n devices."""

    def __init__(self, cfg: ArchConfig, plan: Plan, schedule: Schedule,
                 adam: AdamConfig, seq_len: int, mesh=None, **knobs):
        from repro.core.engine.units import normalized_ratios
        from repro.core.layered_ga import CephaloProgram
        assert plan.feasible, plan.infeasible_reason
        self.cfg, self.plan, self.schedule = cfg, plan, schedule
        self.seq = seq_len
        if mesh is None:
            if jax.device_count() < plan.n:
                raise ValueError(
                    f"shard_map substrate needs >= {plan.n} devices, "
                    f"have {jax.device_count()} (set "
                    f"--xla_force_host_platform_device_count or pass mesh)")
            mesh = make_mesh((plan.n,), ("data",),
                             devices=jax.devices()[: plan.n])
        self.mesh = mesh
        ratios = normalized_ratios(plan.state_ratios())
        self.program = CephaloProgram(
            cfg, mesh, ratios=list(ratios), ell=max(plan.ell_pad, 1),
            m=max(plan.m_pad, 1), seq=seq_len, schedule=schedule,
            adam=adam, **knobs)
        self._jitted = None
        self._steps = 0

    def init_state(self, key: jax.Array) -> Dict[str, jax.Array]:
        return self.program.init_state(key)

    def step(self, state, big: np.ndarray):
        """One step, under profiler spans on the trace's own clock:
        ``spmd.step`` around the call, and inside it ``spmd.grid`` (the
        padded grid, with its real and padding rows), ``spmd.put`` (the
        transfer, with its bytes), ``spmd.dispatch`` (the enqueue of the
        jitted step) and ``spmd.loss_wait`` (the host's wait for the
        loss)."""
        from repro.data.pipeline import plan_grid_from_block
        import jax.numpy as jnp
        plan = self.plan
        rows = plan.n * max(plan.ell_pad, 1) * max(plan.m_pad, 1)
        with StepTraceAnnotation("spmd.step", step_num=self._steps):
            self._steps += 1
            if self._jitted is None:
                self._jitted = self.program.jit_step()
            with TraceAnnotation("spmd.grid", rows_real=plan.global_batch,
                                 rows_padded=rows - plan.global_batch):
                grid = plan_grid_from_block(plan, np.asarray(big))
            with TraceAnnotation("spmd.put", bytes=sum(
                    v.nbytes for v in grid.values())):
                batch = {k: jnp.asarray(v) for k, v in grid.items()}
            with TraceAnnotation("spmd.dispatch"):
                new_state, loss = self._jitted(state, batch)
            with TraceAnnotation("spmd.loss_wait"):
                loss = float(loss)
        return new_state, loss

    def gather_params(self, state) -> Dict[str, Any]:
        return self.program.gather_params(state)

    def export_state(self, state) -> Dict[str, Any]:
        return {"step": int(np.asarray(state["step"])),
                "p": self.program.gather_part(state, "p"),
                "m": self.program.gather_part(state, "m"),
                "v": self.program.gather_part(state, "v")}

    def import_state(self, exported: Dict[str, Any]):
        return self.program.state_from_trees(
            exported["p"], exported.get("m"), exported.get("v"),
            step=int(exported.get("step", 0)))


class MpmdEngine(TrainEngine):
    """Loopback substrate: per-rank unpadded programs on one process."""

    def __init__(self, cfg: ArchConfig, plan: Plan, schedule: Schedule,
                 adam: AdamConfig, seq_len: int, **knobs):
        from repro.core.hetero_trainer import HeteroTrainer
        self.cfg, self.plan, self.schedule = cfg, plan, schedule
        self.seq = seq_len
        self.trainer = HeteroTrainer(cfg, plan, adam=adam,
                                     seq_len=seq_len, schedule=schedule)

    def init_state(self, key: jax.Array):
        return self.trainer.init_shards(key)

    def step(self, state, big: np.ndarray):
        return self.trainer.step(state, np.asarray(big))

    def gather_params(self, state) -> Dict[str, Any]:
        return self.trainer.software_allgather(state)

    def export_state(self, state) -> Dict[str, Any]:
        sub = self.trainer.substrate
        return {"step": int(state[0]["step"]) if state else 0,
                "p": sub.allgather_params(state, "p"),
                "m": sub.allgather_params(state, "m"),
                "v": sub.allgather_params(state, "v")}

    def import_state(self, exported: Dict[str, Any]):
        shards = self.trainer.substrate.shard_state(
            exported["p"], exported.get("m"), exported.get("v"))
        for s in shards:
            s["step"] = int(exported.get("step", 0))
        return shards

    # MPMD extras surfaced for the launcher
    def memory_report(self, state) -> str:
        return self.trainer.memory_report(state)

    def simulated_iteration_seconds(self) -> Dict[str, float]:
        return self.trainer.simulated_iteration_seconds()


def build_train_step(cfg: ArchConfig, plan: Plan, *,
                     schedule: Union[str, Schedule] = "layered",
                     substrate: str = "auto",
                     adam: AdamConfig = AdamConfig(),
                     seq_len: int = 512,
                     mesh=None,
                     elastic=None,
                     cost_model=None,
                     oracle=None,
                     **knobs) -> TrainEngine:
    """Build a train engine for ``(cfg, plan)`` on the chosen substrate.

    ``schedule`` — any name in :func:`repro.core.engine.list_schedules`
    (or a :class:`Schedule` instance).  ``substrate`` — ``"shard_map"``,
    ``"loopback"``, ``"multiproc"``, or ``"auto"`` (shard_map iff enough
    devices exist for the plan).  Extra ``knobs`` (``gather_dtype``,
    ``remat``, ``unroll``, ``state_axes``, ...) are forwarded to the
    SPMD program; the multiproc substrate takes ``transport=``,
    ``topology=`` (``"hub"``/``"ring"``), ``overlap_rounds=`` (ring
    only: pipeline the collective rounds so round *k+1*'s AllGatherv
    prefetches under round *k*'s compute — same bits, less exposed
    wire time; default ``$CEPHALO_MP_OVERLAP``), ``ring_timeout=``,
    ``reply_timeout=``, ``jax_coordinator=``, and ``sanitize=`` (arm the
    runtime comm sanitizer on every ring worker — live conformance
    against the statically verified protocol model of
    :mod:`repro.core.engine.verify`; default
    ``$CEPHALO_COMM_SANITIZE``).  With ``elastic=`` the
    knobs are captured and re-applied on every replan rebuild, so e.g.
    a ring fleet replans into a ring fleet and an overlapped fleet
    stays overlapped.

    ``elastic`` — an :class:`repro.core.engine.elastic.ElasticConfig`
    (or ``True`` for defaults) returns an
    :class:`~repro.core.engine.elastic.ElasticEngine` that replans and
    live-migrates state when runtime telemetry drifts from the plan;
    requires ``cost_model`` (the :class:`ClusterCostModel` the plan came
    from).  ``oracle`` optionally overrides the latency-measurement
    source (see ``elastic.CostModelOracle``).
    """
    if elastic is not None and elastic is not False:
        from repro.core.engine.elastic import ElasticConfig, ElasticEngine
        if cost_model is None:
            raise ValueError("elastic replanning needs cost_model= (the "
                             "ClusterCostModel the plan was solved from)")
        ecfg = ElasticConfig() if elastic is True else elastic
        return ElasticEngine(cfg, cost_model, plan=plan,
                             schedule=schedule, substrate=substrate,
                             adam=adam, seq_len=seq_len, mesh=mesh,
                             elastic=ecfg, oracle=oracle, **knobs)
    if cost_model is not None or oracle is not None:
        raise ValueError("cost_model=/oracle= only apply with elastic=")
    sched = get_schedule(schedule)
    if substrate == "auto":
        substrate = "shard_map" if (mesh is not None or
                                    jax.device_count() >= plan.n > 1) \
            else "loopback"
    if substrate == "shard_map":
        return SpmdEngine(cfg, plan, sched, adam, seq_len, mesh=mesh,
                          **knobs)
    if substrate == "loopback":
        if knobs:
            raise ValueError(
                f"loopback substrate takes no extra knobs, got {knobs}")
        return MpmdEngine(cfg, plan, sched, adam, seq_len)
    if substrate == "multiproc":
        from repro.core.engine.multiproc import ProcessEngine
        return ProcessEngine(cfg, plan, sched, adam, seq_len, **knobs)
    raise ValueError(f"unknown substrate {substrate!r}; "
                     f"choose from {SUBSTRATES}")
