#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3 \\
        [--faults half_batch,row_altered --fault-seeds 1,2,3]

In one process, on the chips of the cell and at its own size, with the
step compiled once: for every seed, the program's first three steps
(exactly as ``run.py`` drives them) against the float32 reference; for
the control seeds, the reference computed with fp8 operands in the
program's place; for the fault seeds, the program with each fault of
``faults.py`` planted.  Prints one JSON line per reading and a summary:
the largest reading of the sound runs (the lower reading) and the
smallest of the control and of each fault (the upper readings).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402

NUMBERS = ("loss_gap", "grad1_gap", "grad1_median_gap", "change3_gap",
           "change3_median_gap")


def program_gaps(spec, engine, seed, devices):
    import traffic
    pool = traffic.make_pool(spec["traffic"], spec["config"]["vocab_size"],
                             seed)
    state = run.init_state(engine, spec, seed)
    state, losses, m1, p3 = run.first_steps(engine, state, pool)
    del state
    gc.collect()
    sh = run.reference_shardings(spec, devices)
    got = run.program_readings(engine.program, spec, seed, losses, m1, p3,
                               sh)
    del m1, p3
    ref = run.reference_readings(spec, seed, pool, shardings=sh)
    return run.readings_gaps(got, ref), pool, ref


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    sys.path.insert(0, run.SRC)
    import registry
    spec = registry.cell(args.workload)
    import jax
    run.configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 1
    import faults as F
    arch, plan, engine = run.build(spec, devices)
    run.check_tree(spec, arch)
    out = {"program": [], "control": [], "faults": {}}
    for seed in ints(args.seeds):
        gaps, pool, ref = program_gaps(spec, engine, seed, devices)
        out["program"].append(gaps)
        print(json.dumps({"kind": "program", "seed": seed, **gaps}),
              flush=True)
        if seed in ints(args.control_seeds):
            ctl = run.reference_readings(
                spec, seed, pool, "fp8",
                run.reference_shardings(spec, devices))
            gaps = run.readings_gaps(ctl, ref)
            out["control"].append(gaps)
            print(json.dumps({"kind": "control", "seed": seed, **gaps}),
                  flush=True)
        del ref
        for fault in [f for f in args.faults.split(",") if f]:
            if seed not in ints(args.fault_seeds):
                continue
            with F.planted(fault):
                eng = engine if fault != "no_exchange" else \
                    run.build(spec, devices)[2]
                gaps, _, _ = program_gaps(spec, eng, seed, devices)
            out["faults"].setdefault(fault, []).append(gaps)
            print(json.dumps({"kind": fault, "seed": seed, **gaps}),
                  flush=True)
    summary = {"lower": {k: max(g[k] for g in out["program"])
                         for k in NUMBERS}}
    for kind, rows in [("control", out["control"])] + list(
            out["faults"].items()):
        if rows:
            summary[kind] = {k: min(g[k] for g in rows) for k in NUMBERS}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
