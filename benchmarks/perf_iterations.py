"""§Perf hillclimbing harness.

Runs named variants of the three chosen (arch × shape) pairs on the
production mesh, recording memory/cost/collective analyses per variant to
``experiments/perf/<pair>__<variant>.json``.  EXPERIMENTS.md §Perf is the
narrative over these records.

Chosen pairs (from the baseline roofline table):
  A. qwen3-moe-30b-a3b × train_4k — most representative of the paper's
     technique (FSDP-gathering 128-expert units); collective-dominant.
  B. yi-34b × train_4k            — worst collective term (8.2 s) and
     over-budget HBM (27 GiB/dev vs 16 GB v5e).
  C. mixtral-8x7b × prefill_32k   — worst memory blowup at baseline
     (1.9 TiB temp from the dense MoE dispatch).

Run ONE variant per process (the 512-device XLA flag must be set before
jax init, and compile caches would pollute measurements):

    PYTHONPATH=src python -m benchmarks.perf_iterations --list
    PYTHONPATH=src python -m benchmarks.perf_iterations --run A0
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # compile-only; never the chip

import argparse
import json
import time
from typing import Dict

PERF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "perf")

#: variant id → (arch, shape, description, options)
VARIANTS: Dict[str, Dict] = {
    # --- pair A: qwen3-moe train ------------------------------------------
    "A0": {"arch": "qwen3-moe-30b-a3b", "shape": "train_4k",
           "desc": "baseline: paper-faithful fp32 gathers, full remat",
           "opts": {"gather_dtype": "float32"}},
    "A1": {"arch": "qwen3-moe-30b-a3b", "shape": "train_4k",
           "desc": "bf16 unit gathers (beyond-paper: halves AG wire bytes;"
                   " fp32 master + RS stay fp32)",
           "opts": {"gather_dtype": "bfloat16"}},
    "A2": {"arch": "qwen3-moe-30b-a3b", "shape": "train_4k",
           "desc": "bf16 gathers + bf16 grad reduce-scatter "
                   "(halves RS too; quality risk documented)",
           "opts": {"gather_dtype": "bfloat16", "grad_dtype": "bfloat16"}},
    # --- pair B: yi-34b train ------------------------------------------------
    "B0": {"arch": "yi-34b", "shape": "train_4k",
           "desc": "baseline: fp32 gathers",
           "opts": {"gather_dtype": "float32"}},
    "B1": {"arch": "yi-34b", "shape": "train_4k",
           "desc": "bf16 gathers",
           "opts": {"gather_dtype": "bfloat16"}},
    "B2": {"arch": "yi-34b", "shape": "train_4k",
           "desc": "bf16 gathers + bf16 RS",
           "opts": {"gather_dtype": "bfloat16", "grad_dtype": "bfloat16"}},
    "B3": {"arch": "yi-34b", "shape": "train_4k",
           "desc": "bf16 gathers + host-offloaded boundary activations "
                   "(paper's activation offloading, TPU pinned_host)",
           "opts": {"gather_dtype": "bfloat16", "remat": "offload"}},
    # --- pair C: mixtral prefill ------------------------------------------
    "C0": {"arch": "mixtral-8x7b", "shape": "prefill_32k",
           "desc": "baseline (recorded pre-fix): dense (T,E,C) MoE "
                   "dispatch — 1933 GiB temp",
           "opts": {}, "note": "see experiments/dryrun baseline record"},
    "C1": {"arch": "mixtral-8x7b", "shape": "prefill_32k",
           "desc": "chunked MoE dispatch (4096-token chunks, per-chunk "
                   "capacity)",
           "opts": {}},
    # --- bonus: zamba2 train nested remat ----------------------------------
    "D0": {"arch": "zamba2-7b", "shape": "train_4k",
           "desc": "baseline: remat at group level only (36 GiB temp)",
           "opts": {}},
    "D1": {"arch": "zamba2-7b", "shape": "train_4k",
           "desc": "nested remat inside the 6-mamba-block group "
                   "(recompute SSD intermediates per inner block)",
           "opts": {}},
    # --- pair E (beyond-paper): HSDP on a small arch --------------------------
    "E0": {"arch": "stablelm-1.6b", "shape": "train_4k",
           "desc": "baseline: ZeRO-3 over all 256 chips",
           "opts": {}},
    "E1": {"arch": "stablelm-1.6b", "shape": "train_4k",
           "desc": "HSDP: state over 'model' (16-deep gather rings), "
                   "replicated over 'data'; grad AR across replicas",
           "opts": {"state_axes": ("model",)}},
}


def run_variant(vid: str) -> Dict:
    import jax
    from repro.configs.base import INPUT_SHAPES, get_arch
    from repro.core.engine import CephaloProgram
    from repro.launch import serving
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import analysis as R
    from repro.launch.dryrun import _cost_dict, _mem_dict

    v = VARIANTS[vid]
    cfg = get_arch(v["arch"])
    shape = INPUT_SHAPES[v["shape"]]
    mesh = make_production_mesh(multi_pod=False)
    rec = {"variant": vid, "arch": v["arch"], "shape": v["shape"],
           "desc": v["desc"], "opts": v["opts"]}
    t0 = time.perf_counter()
    if shape.kind == "train":
        m = max(shape.global_batch // 256, 1)
        prog = CephaloProgram(cfg, mesh, ell=1, m=m, seq=shape.seq_len,
                              **v["opts"])
        step = prog.jit_step()
        state_sh = prog.state_shardings()
        batch_sh = prog.batch_shardings()
        state_args = {k: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=state_sh[k])
                      for k, s in prog.state_shapes().items()}
        batch_args = {k: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                              sharding=batch_sh[k])
                      for k, s in prog.batch_shapes().items()}
        lowered = step.lower(state_args, batch_args)
    elif shape.kind == "prefill":
        fn, args = serving.build_prefill(cfg, mesh, shape)
        lowered = fn.lower(*args)
    else:
        fn, args = serving.build_decode(cfg, mesh, shape)
        lowered = fn.lower(*args)
    mlir = lowered.as_text()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    rec["memory_analysis"] = _mem_dict(compiled)
    rec["cost_analysis"] = _cost_dict(compiled)
    # StableHLO parse: the CPU test backend legalizes bf16 collectives
    # (and buffers) to f32, so the jax-level program is the TPU-faithful
    # byte count; memory_analysis here is an f32-legalized UPPER bound.
    c = R.parse_collectives_stablehlo(mlir)
    rec["collectives"] = {"counts": c.counts, "bytes_by_op": c.bytes_by_op,
                          "total_bytes": c.total_bytes,
                          "source": "stablehlo (pre-legalization)"}
    os.makedirs(PERF_DIR, exist_ok=True)
    path = os.path.join(PERF_DIR, f"{v['arch']}__{v['shape']}__{vid}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=str)
    tmp = rec["memory_analysis"].get("temp_size_in_bytes", 0) / (1 << 30)
    arg = rec["memory_analysis"].get("argument_size_in_bytes", 0) / (1 << 30)
    print(f"[{vid}] {v['arch']} × {v['shape']}: temp={tmp:.2f}GiB "
          f"args={arg:.2f}GiB coll_bytes={c.total_bytes / (1 << 30):.2f}GiB "
          f"(while-bodies once) compile={rec['compile_s']}s")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list or not args.run:
        for k, v in VARIANTS.items():
            print(f"{k}: {v['arch']} × {v['shape']} — {v['desc']}")
        return
    run_variant(args.run)


if __name__ == "__main__":
    main()
