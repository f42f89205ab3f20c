import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination and dump memory/cost/collective analyses.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.  The
dry-run is compile-only host emulation, so it pins the CPU platform
before any backend starts (on a TPU host it must not take the chip).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod] [--unroll]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` —
EXPERIMENTS.md §Dry-run and §Roofline are generated from these.
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import numpy as np

jax.config.update("jax_platforms", "cpu")

from repro.configs.base import (ASSIGNED, INPUT_SHAPES, get_arch,
                                input_specs, shape_applicable)
from repro.core.engine import CephaloProgram
from repro.launch import serving
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as R

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mesh_name(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _mem_dict(compiled) -> Dict[str, float]:
    try:
        m = compiled.memory_analysis()
    except Exception:   # noqa: BLE001 - backend-optional API, {} recorded
        return {}
    if m is None:
        return {}
    out = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        c = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 - backend-optional API, {} recorded
        return {}
    # older jax returns a per-device list of dicts, newer a single dict
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    if not c:
        return {}
    keep = {}
    for k, v in c.items():
        if k in ("flops", "transcendentals", "bytes accessed") or \
                k.startswith("bytes accessed"):
            keep[k] = float(v)
    return keep


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               unroll: bool = False, verbose: bool = True,
               out_dir: Optional[str] = None) -> Dict:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    record: Dict = {
        "arch": arch, "shape": shape_name, "mesh": _mesh_name(multi_pod),
        "chips": chips, "kind": shape.kind,
    }
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        _save(record, out_dir)
        if verbose:
            print(f"[skip] {arch} × {shape_name} × {record['mesh']}: "
                  f"{reason}")
        return record

    t0 = time.perf_counter()
    try:
        if shape.kind == "train":
            # Cephalo FSDP step: every chip is a ZeRO-3 DP worker.  With
            # B < chips (multi-pod), surplus ranks idle compute but still
            # hold state shards — the planner's b_i = 0 case, expressed
            # as zero-weight padding rows (EXPERIMENTS.md §Dry-run).
            m = max(shape.global_batch // chips, 1)
            prog = CephaloProgram(cfg, mesh, ell=1, m=m,
                                  seq=shape.seq_len, unroll=unroll,
                                  gather_dtype="float32")
            step = prog.jit_step()
            state_sh = prog.state_shardings()
            batch_sh = prog.batch_shardings()
            state_args = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=state_sh[k])
                for k, v in prog.state_shapes().items()}
            batch_args = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=batch_sh[k])
                for k, v in prog.batch_shapes().items()}
            lowered = step.lower(state_args, batch_args)
            record["geometry"] = {"ell": 1, "m": m,
                                  "per_device_batch": m}
        elif shape.kind == "prefill":
            fn, args = serving.build_prefill(cfg, mesh, shape)
            lowered = fn.lower(*args)
        else:
            fn, args = serving.build_decode(cfg, mesh, shape)
            lowered = fn.lower(*args)
        record["lower_s"] = round(time.perf_counter() - t0, 2)

        t1 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_s"] = round(time.perf_counter() - t1, 2)
        record["memory_analysis"] = _mem_dict(compiled)
        record["cost_analysis"] = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:   # noqa: BLE001 - fall back to pre-compile HLO
            hlo = lowered.as_text()
        coll = R.parse_collectives(hlo)
        record["collectives"] = {
            "counts": coll.counts,
            "bytes_by_op": coll.bytes_by_op,
            "total_bytes": coll.total_bytes,
            "note": "while-loop bodies counted once unless --unroll",
        }
        terms = R.terms_for(cfg, shape, chips)
        record["roofline_analytic"] = terms.row()
        record["bottleneck_hint"] = R.what_would_move_it(terms, shape.kind)
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    _save(record, out_dir)
    if verbose:
        mark = "ok  " if record["status"] == "ok" else "FAIL"
        extra = ""
        if record["status"] == "ok":
            ma = record["memory_analysis"]
            tmp = ma.get("temp_size_in_bytes", 0) / (1 << 30)
            arg = ma.get("argument_size_in_bytes", 0) / (1 << 30)
            extra = (f" args={arg:.2f}GiB temp={tmp:.2f}GiB "
                     f"compile={record['compile_s']}s "
                     f"dominant={record['roofline_analytic']['dominant']}")
        else:
            extra = " " + record.get("error", "")[:160]
        print(f"[{mark}] {arch} × {shape_name} × {record['mesh']}{extra}",
              flush=True)
    return record


def _save(record: Dict, out_dir: Optional[str]) -> None:
    d = out_dir or OUT_DIR
    os.makedirs(d, exist_ok=True)
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    with open(os.path.join(d, name), "w") as f:
        json.dump(record, f, indent=2, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs × all shapes")
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ASSIGNED:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        combos = [(args.arch, args.shape)]

    results = []
    for arch, shape in combos:
        if args.skip_existing:
            name = (f"{arch}__{shape}__"
                    f"{_mesh_name(args.multi_pod)}.json")
            path = os.path.join(args.out or OUT_DIR, name)
            if os.path.exists(path):
                with open(path) as f:
                    rec = json.load(f)
                if rec.get("status") in ("ok", "skipped"):
                    print(f"[cached] {arch} × {shape}")
                    results.append(rec)
                    continue
        results.append(dryrun_one(arch, shape, args.multi_pod,
                                  unroll=args.unroll, out_dir=args.out))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
