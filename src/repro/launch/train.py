"""Training launcher — both runtimes go through the execution engine.

Two substrates (``repro.core.engine.build_train_step``):

* ``--runtime spmd`` — the Cephalo SPMD step on a jax mesh (homogeneous
  pods; the production path).  Device count comes from the environment;
  the launcher synthesizes an even plan for it.
* ``--runtime mpmd`` — the heterogeneous MPMD runtime: profiles / builds
  the cost model for ``--cluster``, runs the Cephalo planner, then
  trains with truly uneven per-rank batches and state shards.
  ``--substrate loopback`` (default) simulates the fleet in-process;
  ``--substrate multiproc --nprocs N`` runs one OS process per rank
  (``repro.core.engine.multiproc``) with real AllGatherv /
  ReduceScatterv and *wall-clock* telemetry — ``--elastic`` then refits
  from real measurements, and ``--straggler`` makes the chosen worker
  process actually slower instead of scaling an oracle.
  ``--topology hub`` (default) routes collective payloads through the
  coordinator; ``--topology ring`` moves them over peer-to-peer
  worker↔worker ring channels and keeps the coordinator control-plane
  only (also selectable via ``CEPHALO_MP_TOPOLOGY``).  ``--overlap``
  (ring only, also ``CEPHALO_MP_OVERLAP=1``) pipelines the collective
  rounds: each worker prefetches round *k+1*'s parameter AllGatherv on
  a dedicated comm thread while round *k* computes, hiding ring
  latency without changing a single bit of the result.

``--ga-mode`` selects any registered gradient-accumulation schedule
(layered / per_microbatch / interleaved / ...) on either substrate.

``--elastic`` wraps the MPMD runtime in the elastic replanning engine
(``repro.core.engine.elastic``): step-time telemetry refits the cost
model, the planner re-solves when observed imbalance crosses the
threshold, and training state (params + Adam moments) live-migrates to
the new plan.  ``--straggler RANK:FACTOR@STEP`` injects a simulated
slowdown mid-run to exercise the loop (e.g. ``1:3.0@5`` makes rank 1 3x
slower from step 5).

Example (CPU, small model)::

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --reduced --steps 20 --batch 16 --seq 64 --runtime mpmd \
        --cluster cluster-a --elastic --straggler 0:2.5@8

Real processes + real wall-clock (the ROADMAP telemetry item)::

    PYTHONPATH=src python -m repro.launch.train --arch tiny-llama \
        --reduced --steps 10 --batch 8 --seq 16 --runtime mpmd \
        --substrate multiproc --nprocs 2 --elastic --straggler 0:4.0@3
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import get_arch
from repro.core import device_specs as D
from repro.core.cost_model import analytic_cluster_model
from repro.core.engine import (build_train_step, homogeneous_plan,
                               list_schedules)
from repro.core.engine.transport import TOPOLOGIES, resolve_topology
from repro.core.model_stats import build_model_stats
from repro.core.planner import auto_solve
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adam import AdamConfig

CLUSTERS = {
    "cluster-a": D.cluster_a,
    "cluster-b": D.cluster_b,
    "mini": lambda: D.Cluster([D.L4, D.A6000, D.P40, D.P100],
                              link_gbps=50, name="mini"),
}


def _train_loop(engine, args, plan, state=None, on_step=None) -> object:
    stream = SyntheticStream(DataConfig(engine.cfg.vocab_size, args.seq,
                                        seed=args.seed))
    if state is None:
        state = engine.init_state(jax.random.PRNGKey(args.seed))
    # perf_counter, not time.time(): step wall time feeds the elastic
    # planner's wall-clock oracle, and an NTP adjustment mid-run must
    # not corrupt it (monotonic clocks can't step backwards)
    t0 = time.perf_counter()
    for step in range(args.steps):
        if on_step is not None:
            on_step(step)
        big = stream.sample(step, plan.global_batch)
        state, loss = engine.step(state, big)
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:>5} loss {float(loss):.4f} "
                  f"({time.perf_counter() - t0:.1f}s wall)")
    return state


def _parse_straggler(spec: str):
    """'RANK:FACTOR@STEP' → (rank, factor, step)."""
    head, step = spec.split("@")
    rank, factor = head.split(":")
    return int(rank), float(factor), int(step)


def run_mpmd(args) -> None:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cluster = CLUSTERS[args.cluster]()
    if args.nprocs:
        # size the fleet explicitly: cycle the named cluster's device
        # specs out to --nprocs ranks (one worker process per rank),
        # keeping its link efficiency / topology fields intact
        import dataclasses
        devices = [cluster.devices[i % len(cluster.devices)]
                   for i in range(args.nprocs)]
        cluster = dataclasses.replace(
            cluster, devices=devices,
            name=f"{cluster.name}x{args.nprocs}")
    if args.substrate == "multiproc":
        # bootstrap the planner in *wall-clock* units: the rank fleet is
        # N local processes, so host-measured single-layer latency is
        # the observed truth and the elastic loop starts calibrated
        from repro.core.profiler import wallclock_cluster_model
        print("profiling host wall-clock latency models ...")
        cm = wallclock_cluster_model(cluster, cfg, args.seq)
    else:
        cm = analytic_cluster_model(cluster,
                                    build_model_stats(cfg, args.seq))
    plan = auto_solve(cm, args.batch)
    print(plan.summary())
    if not plan.feasible:
        raise SystemExit(f"infeasible: {plan.infeasible_reason}")
    on_step = None
    elastic_kw = {}
    if args.elastic:
        from repro.core.engine.elastic import (CostModelOracle,
                                               ElasticConfig)
        from repro.core.engine.multiproc import WallClockOracle
        oracle = WallClockOracle() if args.substrate == "multiproc" \
            else CostModelOracle(cm)
        elastic_kw = dict(elastic=ElasticConfig(), cost_model=cm,
                          oracle=oracle)
        if args.straggler:
            rank, factor, at_step = _parse_straggler(args.straggler)
            if not 0 <= rank < cluster.n:
                raise SystemExit(
                    f"--straggler rank {rank} out of range for "
                    f"{cluster.name} (n={cluster.n})")

            def on_step(step, _r=rank, _f=factor, _s=at_step):
                if step == _s:
                    print(f"-- injecting straggler: rank {_r} x{_f} --")
                    oracle.degrade(_r, _f)
    elif args.straggler:
        raise SystemExit("--straggler needs --elastic")
    substrate_kw = {}
    if args.substrate == "multiproc":
        # explicit flag > $CEPHALO_MP_TOPOLOGY > hub
        substrate_kw["topology"] = resolve_topology(args.topology)
        if args.overlap:
            if substrate_kw["topology"] != "ring":
                raise SystemExit(
                    "--overlap needs --topology ring (the hub data "
                    "plane has no prefetch lane)")
            substrate_kw["overlap_rounds"] = True
    engine = build_train_step(cfg, plan, schedule=args.ga_mode,
                              substrate=args.substrate,
                              adam=AdamConfig(lr=args.lr),
                              seq_len=args.seq, **substrate_kw,
                              **elastic_kw)
    try:
        state = engine.init_state(jax.random.PRNGKey(args.seed))
        print(engine.memory_report(state))
        sim = engine.simulated_iteration_seconds()
        print(f"predicted iteration: {sim['iteration_s']*1e3:.1f} ms "
              f"({sim['throughput_samples_s']:.2f} samples/s)")
        state = _train_loop(engine, args, plan, state=state,
                            on_step=on_step)
        if args.elastic:
            for ev in engine.events:
                print(f"replan@{ev.step} adopted={ev.adopted}: {ev.reason}")
            if engine.plan is not plan:
                print("final plan after replanning:")
                print(engine.plan.summary())
        if args.checkpoint:
            from repro.checkpoint import checkpointing as C
            final_plan = engine.plan if args.elastic else plan
            if args.substrate == "multiproc":
                # worker-held shards → the substrate-independent
                # exported pytrees (see checkpointing module docstring)
                exported = engine.export_state(state)
                C.save(args.checkpoint, args.steps,
                       [{k: exported[k] for k in ("p", "m", "v")}],
                       {"step": exported["step"]},
                       meta={"plan": final_plan.to_json(),
                             "format": "exported"})
            else:
                C.save(args.checkpoint, args.steps, state, {},
                       meta={"plan": final_plan.to_json()})
            print(f"saved checkpoint to {args.checkpoint}")
    finally:
        engine.close()


def run_spmd(args) -> None:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n = jax.device_count()
    shape = {1: (1, 1)}.get(n) or (
        (n // 2, 2) if n % 2 == 0 else (n, 1))
    mesh = make_mesh(shape, ("data", "model"))
    per_dev = max(args.batch // n, 1)
    plan = homogeneous_plan(n, ell=args.ell,
                            m=max(per_dev // args.ell, 1), device="host")
    engine = build_train_step(cfg, plan, schedule=args.ga_mode,
                              substrate="shard_map", mesh=mesh,
                              adam=AdamConfig(lr=args.lr),
                              seq_len=args.seq)
    _train_loop(engine, args, plan)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--runtime", choices=("spmd", "mpmd"), default="mpmd")
    ap.add_argument("--cluster", default="mini", choices=list(CLUSTERS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ell", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ga-mode", default="layered",
                    choices=list_schedules())
    ap.add_argument("--substrate", default="loopback",
                    choices=("loopback", "multiproc"),
                    help="mpmd collective substrate: in-process loopback "
                         "or one OS process per rank (multiproc)")
    ap.add_argument("--nprocs", type=int, default=0,
                    help="size the rank fleet explicitly (cycles the "
                         "--cluster device specs); 0 = cluster size")
    ap.add_argument("--topology", default=None,
                    choices=list(TOPOLOGIES),
                    help="multiproc collective topology: hub routes "
                         "payloads through the coordinator, ring moves "
                         "them peer-to-peer (default: "
                         "$CEPHALO_MP_TOPOLOGY or hub)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap ring rounds: prefetch each round's "
                         "AllGatherv under the previous round's compute "
                         "on a per-worker comm thread (multiproc + "
                         "--topology ring; also $CEPHALO_MP_OVERLAP=1)")
    ap.add_argument("--elastic", action="store_true",
                    help="enable the replanning runtime (mpmd only)")
    ap.add_argument("--straggler", default="",
                    help="inject a slowdown: RANK:FACTOR@STEP "
                         "(requires --elastic)")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()
    enable_compile_cache()
    if args.runtime != "mpmd" and (args.elastic or args.straggler):
        raise SystemExit("--elastic/--straggler require --runtime mpmd "
                         "(the replanning loop drives the planner, which "
                         "the homogeneous SPMD launcher bypasses)")
    if args.runtime != "mpmd" and (args.substrate != "loopback"
                                   or args.nprocs):
        raise SystemExit("--substrate/--nprocs apply to --runtime mpmd")
    if args.topology is not None and args.substrate != "multiproc":
        # only an *explicit* flag errors; the CEPHALO_MP_TOPOLOGY env
        # default is a multiproc knob and stays inert elsewhere
        raise SystemExit("--topology applies to --substrate multiproc "
                         "(loopback has no wire at all)")
    if args.overlap and args.substrate != "multiproc":
        raise SystemExit("--overlap applies to --substrate multiproc "
                         "with --topology ring")
    if args.runtime == "mpmd":
        run_mpmd(args)
    else:
        run_spmd(args)


if __name__ == "__main__":
    main()
