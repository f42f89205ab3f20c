#!/usr/bin/env python3
"""Chip benchmark of Cephalo's training step.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: a training job on the normal
path, ``build_train_step(cfg, plan, substrate="shard_map")`` and
``SpmdEngine.step``, on the chips the cell names.

Set-up (``setup_s``, from process start to the first timed step):
weights drawn on the device from the seed by the reference's own
``init_params`` and laid out by ``engine.import_state``; a pool of
distinct token blocks from the traffic mix; then the first three steps
through ``engine.step`` on blocks 0..2.  The first of them compiles (or
finds the step in the compile cache); the optimizer state after step 1
and the parameters after step 3 are copied to the host for the check.

Window: a closed loop of ``engine.step`` on the next block of the pool
for ``--seconds`` seconds, then one ``block_until_ready``.  With
``--trace 1`` the window runs under the profiler and the per-layer
metrics come from its trace; otherwise the end-to-end metrics come from
the host clock.

Check (after the window, the program's state freed): the reference in
``reference/<family>.py`` trains three steps from the same weights on the
same blocks in float32, and the run is ``correct`` when each number in
``limits/<cell>.json`` is within its limit.

The run fails, and prints no result, without a TPU, with fewer chips
than the cell asks for, with ``REPRO_USE_PALLAS`` set, or without the
repository's ``src/`` beside this directory.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(ROOT, "src")
TRACE_DIR = os.path.join(HERE, ".trace")
GiB = float(1 << 30)

if HERE not in sys.path:
    sys.path.insert(0, HERE)

import registry  # noqa: E402
import traffic  # noqa: E402


# ---------------------------------------------------------------------------
# Building the program from the cell's files
# ---------------------------------------------------------------------------

def program_config(cfg: Dict[str, Any], reference):
    """The repository's ``ArchConfig`` for a configuration file: the
    registered architecture with the file's ``program`` overrides, checked
    against the file's sizes: depth, width and vocabulary, and the
    family's own (``reference.program_sizes``)."""
    import dataclasses
    from repro.configs.base import get_arch
    arch = dataclasses.replace(get_arch(cfg["arch"]), **cfg["program"])
    want = {"d_model": cfg["hidden_size"],
            "n_layers": cfg["num_hidden_layers"],
            "vocab_size": cfg["vocab_size"],
            **reference.program_sizes(cfg)}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise ValueError(f"{cfg['arch']} as registered does not match "
                         f"{cfg['name']}.json: {got} != {want}")
    return arch


def build(spec: Dict[str, Any], devices: List[Any]):
    """(arch, plan, engine) for the cell on ``devices``."""
    from repro.core.engine import build_train_step
    from repro.core.partition import Plan, RankPlan
    from repro.launch.mesh import make_mesh
    from repro.optim.adam import AdamConfig
    mix = spec["traffic"]
    arch = program_config(spec["config"], spec["reference"])
    ranks = [RankPlan(i, f"chip{i}", m=r["m"], ell=r["ell"],
                      state_ratio=r["state_ratio"])
             for i, r in enumerate(mix["ranks"])]
    plan = Plan(model=arch.name, cluster=spec["name"],
                global_batch=traffic.global_batch(mix), ranks=ranks)
    n = plan.n
    mesh = make_mesh((n,), ("data",), devices=devices[:n])
    engine = build_train_step(arch, plan, substrate="shard_map",
                              seq_len=mix["seq"], mesh=mesh,
                              adam=AdamConfig(**mix["optimizer"]))
    return arch, plan, engine


def weights(spec: Dict[str, Any], seed: int, shardings=None):
    """The cell's initial weights, drawn on the device in one call."""
    import jax
    from reference.common import seed_key
    init = spec["reference"].init_params
    kw = {"out_shardings": shardings} if shardings is not None else {}
    return jax.jit(lambda k: init(spec["config"], k), **kw)(seed_key(seed))


def reference_shardings(spec: Dict[str, Any], devices: List[Any]):
    """Where the reference keeps its weights and Adam state on more than
    one chip: each leaf split over the chips along its largest dimension
    that they divide (past the layer dimension of stacked leaves).  None
    on one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from reference.common import seed_key
    n = spec["chips"]
    if n == 1:
        return None
    mesh = Mesh(np.asarray(devices[:n]), ("ref",))
    shapes = jax.eval_shape(
        lambda k: spec["reference"].init_params(spec["config"], k),
        seed_key(0))

    def one(path, x):
        first = 1 if any(getattr(k, "key", None) == "stages"
                         for k in path) else 0
        dims = [i for i in range(first, len(x.shape)) if x.shape[i] % n == 0]
        spec_ = [None] * len(x.shape)
        if dims:
            spec_[max(dims, key=lambda i: x.shape[i])] = "ref"
        return NamedSharding(mesh, P(*spec_))
    return jax.tree_util.tree_map_with_path(one, shapes)


def check_tree(spec: Dict[str, Any], arch) -> None:
    """The reference's weights must have the program's tree and shapes."""
    import jax
    from reference.common import seed_key
    from repro.models import model as M
    mine = jax.eval_shape(
        lambda k: spec["reference"].init_params(spec["config"], k),
        seed_key(0))
    theirs = jax.eval_shape(lambda k: M.init_params(arch, k), seed_key(0))
    a = [(jax.tree_util.keystr(p), x.shape) for p, x in
         jax.tree_util.tree_flatten_with_path(mine)[0]]
    b = [(jax.tree_util.keystr(p), x.shape) for p, x in
         jax.tree_util.tree_flatten_with_path(theirs)[0]]
    if a != b:
        raise ValueError(f"reference weights {a} do not match the "
                         f"program's {b}")


def init_state(engine, spec: Dict[str, Any], seed: int):
    """The program's state from the cell's weights, in one jitted call."""
    import jax
    from reference.common import seed_key
    init = spec["reference"].init_params
    return jax.jit(lambda k: engine.import_state(
        {"p": init(spec["config"], k)}))(seed_key(seed))


def first_steps(engine, state, pool):
    """Steps 1..3 through ``engine.step`` on blocks 0..2.  Returns the
    state, the three losses, and host copies of the optimizer's first
    moments after step 1 and of the parameters after step 3."""
    import numpy as np
    losses, m1 = [], None
    for i in range(3):
        state, loss = engine.step(state, pool[i])
        losses.append(loss)
        if i == 0:
            m1 = {k: np.asarray(v) for k, v in state.items()
                  if k.endswith("/m")}
    p3 = {k: np.asarray(v) for k, v in state.items() if k.endswith("/p")}
    return state, losses, m1, p3


def step_memory(engine, state, plan, block) -> Dict[str, Any]:
    """Bytes of the step program on one device (the program is the same
    SPMD program on each), from the executable's memory analysis, and the
    executable's HLO text."""
    import jax.numpy as jnp
    from repro.data.pipeline import plan_grid_from_block
    grid = plan_grid_from_block(plan, block)
    batch = {k: jnp.asarray(v) for k, v in grid.items()}
    compiled = engine.program.jit_step().lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return {"bytes": int(total), "hlo": compiled.as_text()}


# ---------------------------------------------------------------------------
# Readings and the comparison that decides ``correct``
# ---------------------------------------------------------------------------

def program_readings(program, spec: Dict[str, Any], seed: int,
                     losses, m1, p3, shardings=None) -> Dict[str, Any]:
    """The program's numbers from the host copies of its state: each
    step's loss, the per-leaf norms of the first gradient (the first
    moment after one step over 1 - b1), and the per-leaf norms of the
    change of the parameters over the three steps."""
    import jax
    import numpy as np
    from reference.common import change_norms, leaf_norms
    b1 = spec["traffic"]["optimizer"]["b1"]
    m_tree = program.gather_part(m1, "m")
    g1 = np.asarray(jax.jit(leaf_norms)(m_tree)) / (1.0 - b1)
    del m_tree
    p_tree = program.gather_part(p3, "p")
    change = change_norms(p_tree, weights(spec, seed, shardings))
    return {"losses": list(losses), "grad1_norms": g1,
            "change_norms": change}


def reference_readings(spec: Dict[str, Any], seed: int, pool,
                       precision: str = "float32",
                       shardings=None) -> Dict[str, Any]:
    """The reference's numbers for the same weights and blocks."""
    from reference import common as C
    ref = spec["reference"]
    loss = ref.make_loss(spec["config"], C.Precision(precision))
    batches = [C.batch_from_block(pool[i]) for i in range(3)]
    out = C.train_three(loss, weights(spec, seed, shardings), batches,
                        spec["traffic"]["optimizer"],
                        spec["config"]["reference"]["rows_per_block"],
                        shardings)
    out["change_norms"] = C.change_norms(out.pop("params"),
                                         weights(spec, seed, shardings))
    return out


def leaf_gaps(got, want):
    """Each leaf's gap between two sets of per-leaf norms, over the
    reference's norm of that leaf or its median leaf's, whichever is
    larger."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want) / np.maximum(want, np.median(want))


def readings_gaps(got: Dict[str, Any], ref: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """The numbers a cell may compare: the largest gap of the three
    losses; the worst and the median leaf's gap of the first gradient;
    and the worst and the median leaf's gap of the change over three
    steps, among leaves whose reference gradient is at least a thousandth
    of the median leaf's (leaves with a gradient that is nought to
    rounding move under Adam by round-off alone)."""
    import numpy as np
    loss = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in got["losses"]):
        loss = math.inf
    g = np.asarray(ref["grad1_norms"])
    keep = g >= 1e-3 * np.median(g)
    grad = leaf_gaps(got["grad1_norms"], g)
    change = leaf_gaps(got["change_norms"], ref["change_norms"])[keep]
    return {"loss_gap": loss,
            "grad1_gap": float(grad.max()),
            "grad1_median_gap": float(np.median(grad)),
            "change3_gap": float(change.max()),
            "change3_median_gap": float(np.median(change)),
            "worst_leaf": {"grad1_gap": int(grad.argmax()),
                           "change3_gap": int(np.flatnonzero(keep)[
                               change.argmax()])},
            "excluded_leaves": int((~keep).sum())}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts compilations (and persistent-cache lookups) while active."""

    def __init__(self):
        import jax
        self.count, self.active = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _ev(self, event, **kw):
        if self.active and event in ("/jax/compilation_cache/cache_hits",
                                     "/jax/compilation_cache/cache_misses"):
            self.count += 1


def reader_facts(spec: Dict[str, Any], peaks: Dict[str, Any]
                 ) -> Dict[str, Any]:
    """What the per-layer readers get beside the reduced trace: the model's
    FLOPs a step, in all and by part (``flops/<family>.py``
    ``flops_per_token`` times the tokens of a step), its bytes a step by
    part (``bytes_per_token``, where the family's file has it), the
    device's bf16 and HBM peaks, and the named scopes the cell's readers
    split its ops by."""
    mix = spec["traffic"]
    tokens = traffic.global_batch(mix) * mix["seq"]
    fam, cfg = spec["flops"], spec["config"]
    flops = fam.flops_per_token(cfg, mix["seq"])
    nbytes = (fam.bytes_per_token(cfg, mix["seq"])
              if hasattr(fam, "bytes_per_token") else {})
    return {"flops_per_step": flops["total"] * tokens,
            "peak_flops_per_s": peaks["bf16_flops_per_s"],
            "flops_parts_per_step": {k: v * tokens for k, v in flops.items()
                                     if k != "total"},
            "bytes_parts_per_step": {k: v * tokens for k, v in nbytes.items()
                                     if k != "total"},
            "peak_hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
            "scopes": registry.scopes(spec["readers"])}


def device_facts(devices) -> Dict[str, Any]:
    import jax
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices())}


def run(spec: Dict[str, Any], seed: int, seconds: float, trace: bool,
        devices, t_start: float, peaks: Dict[str, Any]) -> Dict[str, Any]:
    """One run of one cell.  Returns the result line's object."""
    import jax
    from jax.profiler import TraceAnnotation

    counter = CompileCounter()
    chips = spec["chips"]
    arch, plan, engine = build(spec, devices)
    check_tree(spec, arch)
    mix = spec["traffic"]
    pool = traffic.make_pool(mix, spec["config"]["vocab_size"], seed)
    state = init_state(engine, spec, seed)
    state, losses, m1, p3 = first_steps(engine, state, pool)
    mem = step_memory(engine, state, plan, pool[0])
    tokens_per_step = plan.global_batch * mix["seq"]
    flops = spec["flops"].flops_per_token(spec["config"], mix["seq"])

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    counter.active = True
    window_losses = []
    t0 = time.monotonic()
    setup_s = t0 - t_start
    while True:
        with TraceAnnotation("traffic.next"):
            block = pool[(3 + len(window_losses)) % len(pool)]
        with TraceAnnotation("engine.step"):
            state, loss = engine.step(state, block)
        window_losses.append(loss)
        if time.monotonic() - t0 >= seconds:
            break
    with TraceAnnotation("sync"):
        jax.block_until_ready(state)
    elapsed = time.monotonic() - t0
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    print(f"[window] {len(window_losses)} steps in {elapsed:.6f} s; "
          f"compilations or cache lookups inside the window: "
          f"{counter.count}", flush=True)

    used = devices[:chips]
    peak_in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    steps = len(window_losses)
    tok_s = steps * tokens_per_step / elapsed
    result: Dict[str, Any] = {
        "correct": False,
        "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in window_losses),
        "metrics": {},
        "device": {**device_facts(devices),
                   "memory_peak_bytes": int(max(peak_in_use, mem["bytes"]))},
    }
    if not trace:
        values = {
            "tokens_per_s": tok_s,
            "mfu": 100.0 * tok_s * flops["total"]
            / (chips * peaks["bf16_flops_per_s"]),
            "hbm_gib": mem["bytes"] / GiB,
            "setup_s": setup_s,
        }
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
    else:
        import trace_reduce as T
        classes = T.op_classes(mem["hlo"])
        labels = T.op_labels(mem["hlo"])
        red = T.reduce(T.events_from_xspace(TRACE_DIR), classes)
        facts = reader_facts(spec, peaks)
        for m in spec["per_layer"]:
            v = spec["readers"][m["name"]].read(red, facts)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = sum(red.busy_s) / red.chips
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {
            "device_ops": [[f"{n} {classes.get(n, 'other')} "
                            f"{labels.get(n, '')}".strip(), s / red.chips]
                           for n, s in red.top_ops],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # the check: free the program's state first, so the reference fits
    program = engine.program
    del state, engine, mem
    gc.collect()
    shardings = reference_shardings(spec, devices)
    got = program_readings(program, spec, seed, losses, m1, p3, shardings)
    del m1, p3
    gc.collect()
    ref = reference_readings(spec, seed, pool, shardings=shardings)
    gaps = readings_gaps(got, ref)
    limits = spec["limits"]["limits"]
    checks = {k: {"value": gaps[k], "limit": limits[k]} for k in limits}
    result["correct"] = bool(
        all(c["value"] <= c["limit"] for c in checks.values())
        and result["failed"] == 0)
    result["checks"] = checks
    return result


def configure_jax() -> str:
    """Compile cache in ``$JAX_COMPILATION_CACHE_DIR`` or at a fixed path
    in the checkout; every program cached, however quick its compile."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def report(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_USE_PALLAS"):
        print("run.py measures the default path: unset REPRO_USE_PALLAS",
              file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"run.py needs the repository's src/ ({SRC} not found)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = registry.cell(args.workload)
    import jax
    cache = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX's first device is "
              f"{devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < spec["chips"]:
        print(f"run.py: {args.workload} needs {spec['chips']} chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    print(f"[setup] compile cache {cache}", flush=True)
    peaks = registry.peaks(devices[0].device_kind)
    result = run(spec, args.seed, args.seconds, bool(args.trace), devices,
                 T_START, peaks)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
