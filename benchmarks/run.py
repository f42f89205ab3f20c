"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the headline
number for that artifact) followed by the full tables.  The multiproc
section (skipped under ``--fast``) runs the ring topology sync *and*
overlapped and writes the machine-readable ``BENCH_multiproc.json``
artifact (step time + hidden-comm fraction per variant) next to the
working directory — the repo's multiproc perf trajectory, archived by
the slow CI job.

    PYTHONPATH=src python -m benchmarks.run [--fast] \
        [--multiproc-json BENCH_multiproc.json]
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, List


def _fmt_table(rows: List[dict]) -> str:
    if not rows:
        return "(empty)"
    keys: List[str] = []
    for r in rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    lines = ["  " + " | ".join(f"{k:>14}" for k in keys)]
    for r in rows:
        lines.append("  " + " | ".join(f"{str(r.get(k, '')):>14}"
                                       for k in keys))
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the subprocess/HLO and Cluster-B sections")
    ap.add_argument("--multiproc-json", default="BENCH_multiproc.json",
                    help="path for the multiproc perf artifact "
                         "(written unless --fast; '' disables)")
    args = ap.parse_args()

    from benchmarks import elastic_recovery, grad_accum, model_accuracy
    from benchmarks import tables as T
    from benchmarks import uneven_overhead

    sections: List[tuple] = [
        ("table4_cluster_a", T.table4_cluster_a,
         lambda rows: f"mean_rel_err={sum(r.get('rel_err', 0) for r in rows if 'rel_err' in r) / max(sum(1 for r in rows if 'rel_err' in r), 1):.3f}"),
        ("fig7_ablation", T.fig7_ablation,
         lambda rows: f"rows={len(rows)}"),
        ("fig9_configs", T.fig9_configs, lambda rows: "see plans below"),
        ("fig6_scaling", T.fig6_scaling,
         lambda rows: f"hetero_gain={_hetero_gain(rows)}"),
        ("fig8_modeled_timeline", grad_accum.modeled_timeline,
         lambda rows: f"total_speedup={rows[-1]['speedup_vs_fsdp_ga']}x"),
        ("a3_model_accuracy", model_accuracy.run,
         lambda rows: f"mean_are={rows[-1]['are']}"),
        ("appc_padding_model", uneven_overhead.padding_overhead_model,
         lambda rows: f"max_spmd_overhead={max(r['spmd_padded_overhead'] for r in rows)}"),
        ("elastic_recovery", elastic_recovery.rows,
         lambda rows: f"recovery_ratio={next(r['ratio'] for r in rows if r['scenario'] == 'recovery_ratio')}"),
    ]
    if not args.fast:
        from benchmarks import multiproc_throughput

        def _multiproc_rows():
            # ring sync + overlapped side by side; the artifact is the
            # perf-trajectory headline (step time, hidden-comm fraction).
            # One kwargs dict feeds both the run and the artifact
            # metadata, so the recorded config can't drift from the run.
            kw = dict(nprocs=2, steps=4, overlap="both",
                      schedule=multiproc_throughput.effective_schedule(
                          None, "both"))
            rows = multiproc_throughput.rows(**kw)
            if args.multiproc_json:
                multiproc_throughput.write_artifact(
                    args.multiproc_json, rows, nprocs=kw["nprocs"],
                    schedule=kw["schedule"], steps=kw["steps"])
            return rows

        sections += [
            ("table5_cluster_b", T.table5_cluster_b,
             lambda rows: f"rows={len(rows)}"),
            ("multiproc_throughput", _multiproc_rows,
             lambda rows: "parity_err=" + str(max(
                 r["max_abs_err_vs_loopback"] for r in rows
                 if "max_abs_err_vs_loopback" in r))),
            ("fig8_measured_hlo", grad_accum.measured_collective_bytes,
             lambda rows: f"rs_ratio={rows[-1].get('reducescatter_count', '?')}"),
            ("appc_measured_hlo", uneven_overhead.measured_hlo_overhead,
             lambda rows: f"overhead={rows[-1].get('allgather_bytes', '?')}"),
        ]

    csv_lines = ["name,us_per_call,derived"]
    details = []
    for name, fn, derive in sections:
        t0 = time.perf_counter()
        try:
            rows = fn()
            derived = derive(rows)
        except Exception as e:  # noqa: BLE001 - section failure lands in the CSV
            rows = [{"error": f"{type(e).__name__}: {e}"}]
            derived = "ERROR"
        us = (time.perf_counter() - t0) * 1e6
        csv_lines.append(f"{name},{us:.0f},{derived}")
        if name == "fig9_configs":
            details.append(f"\n== {name} ==\n" + "\n\n".join(rows))
        else:
            details.append(f"\n== {name} ==\n" + _fmt_table(rows))
        print(csv_lines[-1], flush=True)

    print("\n".join(details))
    print("\n--- CSV ---")
    print("\n".join(csv_lines))


def _hetero_gain(rows) -> str:
    try:
        base = next(r for r in rows if r["cluster"] == "16xA10G")
        full = next(r for r in rows if r["cluster"] == "all-64")
        return f"{full['train_tflops'] / base['train_tflops']:.2f}x"
    except Exception:  # noqa: BLE001 - missing row renders as "?"
        return "?"


if __name__ == "__main__":
    main()
