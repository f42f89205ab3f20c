"""Fig. 8 reproduction: gradient-accumulation optimizations.

Two measurements:

* **HLO collective bytes** (real, from the compiled SPMD step on 8 fake
  devices): layered GA vs per-microbatch FSDP-GA — the paper's "ℓ× fewer
  AllGathers" claim, measured on actual XLA output.
* **Modeled timeline** (cost-model): FSDP-GA / +LGA / +CO (overlap) /
  +S+O (sync & offload) on the paper's 16xV100 homogeneous cluster with
  GPT-6.7B, batch 256, 16 microbatches of 1 per GPU — the Fig. 8 setup.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List

from repro.configs.base import get_arch
from repro.core import device_specs as D
from repro.core.cost_model import analytic_cluster_model
from repro.core.model_stats import build_model_stats

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

_SUBPROC_CODE = """
import jax
from repro.configs.base import get_arch
from repro.core.engine import CephaloProgram
from repro.launch.mesh import make_mesh
from repro.roofline.analysis import parse_collectives
cfg = get_arch("stablelm-1.6b").reduced()
mesh = make_mesh((2, 4), ("data", "model"))
for mode in ("layered", "per_microbatch"):
    prog = CephaloProgram(cfg, mesh, ell=4, m=1, seq=32, ga_mode=mode,
                          unroll=True)
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.state_shapes().items()}
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.batch_shapes().items()}
    hlo = jax.jit(prog.build()).lower(state, batch).compile().as_text()
    c = parse_collectives(hlo)
    print(f"RESULT {mode} agc={c.counts.get('all-gather', 0)} "
          f"rsc={c.counts.get('reduce-scatter', 0)} "
          f"rs={c.bytes_by_op.get('reduce-scatter', 0):.0f}")
"""


def measured_collective_bytes() -> List[Dict]:
    """Layered vs per-microbatch on real compiled HLO (8 devices, ℓ=4).

    The ReduceScatter count exposes FSDP-GA's raw ℓ× per-unit collective
    structure; the baseline's redundant AllGathers are CSE'd by XLA when
    the loop is unrolled (at the cost of holding gathered params live —
    the memory layered GA avoids structurally; see EXPERIMENTS §Perf).
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"     # host emulation; never the chip
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROC_CODE], env=env,
                          capture_output=True, text=True, timeout=1800)
    rows = []
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            _, mode, agc, rsc, rs = line.split()
            rows.append({"mode": mode,
                         "allgather_count": int(agc.split("=")[1]),
                         "reducescatter_count": int(rsc.split("=")[1]),
                         "reducescatter_bytes": float(rs.split("=")[1])})
    if len(rows) == 2:
        rows.append({
            "mode": "RS ratio (per_mb / layered)",
            "reducescatter_count": round(
                rows[1]["reducescatter_count"] /
                max(rows[0]["reducescatter_count"], 1), 2)})
    if proc.returncode != 0:
        rows.append({"mode": "ERROR", "stderr": proc.stderr[-500:]})
    return rows


def modeled_timeline() -> List[Dict]:
    """Paper Fig. 8 setup: GPT-6.7B, 16xV100, batch 256 → ell=16, m=1."""
    cluster = D.v100_cluster(16)
    cfg = get_arch("gpt-6.7b")
    stats = build_model_stats(cfg, 512)
    cm = analytic_cluster_model(cluster, stats)
    ell, m = 16, 1
    tf = cm.per_rank[0].t_fwd
    tb = cm.per_rank[0].t_bwd
    ag = cm.ag_latency()
    rs = cm.rs_latency()
    L = stats.n_layers
    comp = (tf.one(m) + tb.one(m)) * ell      # per layer, all microbatches

    # FSDP-GA: ell separate passes; each pays AG(fwd)+AG(bwd)+RS per layer,
    # communication NOT overlapped (the paper's observed bottleneck).
    t_fsdp_ga = L * (ell * (2 * ag + rs) + comp)
    # +LGA: one AG(fwd)+AG(bwd)+RS per layer, still serial comm.
    t_lga = L * (2 * ag + rs + comp)
    # +CO: comm overlapped with the ell-microbatch compute window.
    t_lga_co = L * max(2 * ag + rs, comp)
    # +S+O: paper's +11% from fragmentation-free memory & offload overlap.
    t_all = t_lga_co / 1.11

    rows = []
    for name, t in (("FSDP-GA", t_fsdp_ga), ("+LGA", t_lga),
                    ("+CO", t_lga_co), ("+S+O", t_all)):
        rows.append({"variant": name, "iter_s": round(t, 3),
                     "throughput": round(256 / t, 2),
                     "speedup_vs_fsdp_ga": round(t_fsdp_ga / t, 2)})
    return rows
