#!/usr/bin/env python3
"""Record a traced run of a cell as a test fixture.

On the chip, from the root of the checkout:

    python3 benchmarks/chip/tests/record_fixture.py record \\
        --workload mamba2-370m.seq4k --seed <n> --seconds 2 \\
        --xplane chiprun_out/mamba2.xplane.pb.gz

runs ``run.py --trace 1`` and keeps a gzipped copy of its trace's
``.xplane.pb`` (``run.py`` deletes the trace once it has read it).  Then,
with no chip:

    python3 benchmarks/chip/tests/record_fixture.py excerpt \\
        --xplane mamba2.xplane.pb.gz --step 2 \\
        --out benchmarks/chip/tests/fixtures/v5e_mamba2_370m_step.json.xz \\
        --source "<device, cell, step and seed, in words>"

writes one whole step of it, xz-compressed, in the JSON form that
``program_trace.events_from_json`` reads: the device's "XLA Ops" events
that start within the step's ``engine.step`` span, the ``op_name`` of each
instruction among them, and the host spans inside that step, with a 1 us
``sync`` span added where the step's last op ends to close the window.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import lzma
import os
import shutil
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)


def record(args) -> int:
    import run
    import trace_reduce as T
    read = T.events_from_xspace

    def keep(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        with open(files[-1], "rb") as src, gzip.open(args.xplane, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return read(path)
    T.events_from_xspace = keep
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


def excerpt(args) -> int:
    import program_trace as P
    with gzip.open(args.xplane, "rb") as fh:
        whole = P.json_form(fh.read())
    steps = sorted(s for s in whole["spans"] if s[0] == "engine.step")
    _, t0, t1 = steps[args.step - 1]
    devices = [[o for o in dev if t0 <= o[1] < t1] for dev in whole["devices"]]
    end = max(o[2] for dev in devices for o in dev)
    ran = {P.T.instruction_name(o[0]) for dev in devices for o in dev}
    spans = [[n, s, min(e, end)] for n, s, e in whole["spans"]
             if t0 <= s < end]
    out = {"source": args.source, "devices": devices,
           "op_names": {k: v for k, v in whole["op_names"].items()
                        if k in ran},
           "spans": spans + [["sync", end, end + 1000]]}
    with lzma.open(args.out, "wt", preset=9) as fh:
        json.dump(out, fh)
    print(f"{args.out}: {sum(map(len, devices))} ops, "
          f"{len(out['op_names'])} op_names, {len(spans) + 1} spans")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("record")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, default=2.0)
    r.add_argument("--xplane", required=True)
    e = sub.add_parser("excerpt")
    e.add_argument("--xplane", required=True)
    e.add_argument("--step", type=int, default=2)
    e.add_argument("--out", required=True)
    e.add_argument("--source", required=True)
    args = ap.parse_args(argv)
    return record(args) if args.mode == "record" else excerpt(args)


if __name__ == "__main__":
    sys.exit(main())
