#!/usr/bin/env python3
"""Smoke run of the Cephalo training path on TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips of one host

One chip runs three phases, in order:

* device  -- the first device must be a TPU; its peaks are looked up by
  ``device_kind`` (an unknown kind is an error);
* kernels -- the Pallas flash-attention kernel at stablelm-1.6b widths and
  the SSD scan kernel at mamba2-370m widths, compiled for the chip
  (``interpret=False``) and compared with their ``ref.py`` oracles;
* train   -- stablelm-1.6b at its published widths, cut to 4 layers, through
  ``build_train_step(substrate="shard_map")`` on a one-chip mesh: one
  warm-up step and a few timed steps, all on one block from the synthetic
  stream.  The first loss must match
  ``M.loss_fn`` on the same initial parameters and block, every loss must
  be finite, and the loss must fall.

``--four-chips`` runs only the four-chip phase: the full 24-layer
stablelm-1.6b (its state does not fit one chip) under ZeRO-3, first on the
even plan, then on an uneven ``auto_solve`` plan for a mixed v5e/v4 fleet.
Both plans see the same blocks, so by Eq. 1 their losses agree step for
step up to rounding; no device may hold the whole state.

Any failed check raises and the script exits non-zero.  On success the
last line of stdout is ``{"ok": true, "device": {...}}``.  Times and
memory printed on the way are from one smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# Tolerances, each with its reason.
#: flash kernel vs the f32 reference, absolute, on bf16 inputs: the kernel
#: writes bf16 (half an ulp is 8e-3 at |y| ~ 4) and the chip's matmuls may
#: round operands to bf16; a masking or softmax bug is off by O(0.1-1).
FLASH_TOL = 3e-2
#: SSD kernel vs the sequential f32 reference, relative to max|ref|: bf16
#: output rounding (2^-9) plus bf16 matmul passes; a decay or carried-state
#: bug is off by O(1).
SSD_TOL = 2e-2
#: first loss of the train step vs ``M.loss_fn`` on the same params and
#: block, absolute: both compute in the config's bf16 and differ only in
#: fusion and reduction order (per-token CE noise ~1e-2 averaged over
#: thousands of tokens); a misplaced weight or Eq. 1 weight moves it more.
FIRST_LOSS_TOL = 5e-3
#: even vs uneven plan, absolute, per step: the same tokens and weights,
#: different padding grids and reduction orders; a rounding-level gradient
#: difference can flip Adam's first update on entries whose gradient is
#: within rounding of zero, so later steps drift by a little more.
PLAN_LOSS_TOL = 1e-2
#: no device may hold more than this share of the whole training state.
MAX_STATE_SHARE = 0.5

GiB = float(1 << 30)


class NoChip(RuntimeError):
    """The smoke run found no TPU."""


def device_phase():
    """Fail unless JAX's first device is a TPU; print its peaks."""
    import jax
    from repro.core import device_specs as D
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {dev.platform!r} "
                     f"({dev.device_kind!r})")
    spec = D.for_device_kind(dev.device_kind)
    print(f"[device] {dev.device_kind} x{len(jax.devices())}: "
          f"{spec.peak_tflops} TFLOP/s bf16, {spec.hbm_gbps} GB/s HBM, "
          f"{spec.memory_gib} GiB ({spec.name})", flush=True)
    return dev


def kernel_phase(*, interpret: bool = False,
                 flash_shape=(1, 32, 4096, 64),
                 ssd_shape=(1, 32, 4096, 64, 128), ssd_chunk: int = 256,
                 seed: int = 0) -> dict:
    """Run both Pallas kernels and compare each with its reference.

    ``flash_shape`` is (B, H, S, D) with H query and H key/value heads;
    ``ssd_shape`` is (B, H, L, P, N)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_reference
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_reference

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    b, h, s, d = flash_shape
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
               for kk in ks[:3])
    out = flash_attention(q, k, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = attention_reference(*(t.astype(jnp.float32) for t in (q, k, v)),
                                  causal=True)
    flash_err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    print(f"[kernels] flash_attention {flash_shape} bf16: max abs err "
          f"{flash_err:.3e} (bound {FLASH_TOL:.0e})", flush=True)
    if not flash_err <= FLASH_TOL:
        raise AssertionError(f"flash_attention err {flash_err} > {FLASH_TOL}")

    b, h, l, p, n = ssd_shape
    x = jax.random.normal(ks[3], (b, h, l, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, h, l), jnp.float32))
    a = -jnp.exp(jnp.linspace(0.0, 1.5, h))
    bm = jax.random.normal(ks[5], (b, l, n), jnp.bfloat16)
    cm = jax.random.normal(ks[6], (b, l, n), jnp.bfloat16)
    y = ssd_scan(x, dt, a, bm, cm, chunk=ssd_chunk, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref = ssd_reference(x.astype(jnp.float32), dt, a,
                              bm.astype(jnp.float32), cm.astype(jnp.float32))
    ssd_err = float(jnp.abs(y.astype(jnp.float32) - y_ref).max()
                    / jnp.abs(y_ref).max())
    print(f"[kernels] ssd_scan {ssd_shape} chunk {ssd_chunk}: max err "
          f"{ssd_err:.3e} of max|ref| (bound {SSD_TOL:.0e})", flush=True)
    if not ssd_err <= SSD_TOL:
        raise AssertionError(f"ssd_scan err {ssd_err} > {SSD_TOL}")
    return {"flash_err": flash_err, "ssd_err": ssd_err}


def _peaks(devices) -> list:
    """``peak_bytes_in_use`` per device (None where not reported)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _gib(x) -> str:
    return "n/a" if x is None else f"{x / GiB:.2f} GiB"


def train_phase(cfg, *, seq: int = 2048, ell: int = 2, m: int = 1,
                steps: int = 5, seed: int = 0) -> dict:
    """One warm-up and ``steps`` timed steps on a one-device mesh."""
    import jax
    import numpy as np
    from repro.core.engine import build_train_step, homogeneous_plan
    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.models import model as M

    plan = homogeneous_plan(1, ell=ell, m=m)
    engine = build_train_step(cfg, plan, substrate="shard_map", seq_len=seq)
    stream = SyntheticStream(DataConfig(cfg.vocab_size, seq, seed=seed))
    state = engine.init_state(jax.random.PRNGKey(seed))

    # Every step sees the same block, so the loss must fall (a model that
    # cannot fit one block is broken); the work per step is unchanged.
    big = stream.sample(0, plan.global_batch)
    # reference: the plain model loss on the same params and block
    params0 = engine.gather_params(state)
    ref_batch = {"tokens": big[:, :-1], "labels": big[:, 1:],
                 "weights": np.full((plan.global_batch, seq),
                                    1.0 / (plan.global_batch * seq),
                                    np.float32)}
    ref_loss = float(jax.jit(lambda p, bt: M.loss_fn(cfg, p, bt)[0])(
        params0, ref_batch))
    del params0

    losses, times = [], []
    for step in range(1 + steps):
        t0 = time.perf_counter()
        state, loss = engine.step(state, big)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        print(f"[train] step {step} loss {loss:.6f} "
              f"{'warm-up' if step == 0 else 'timed'} {times[-1]:.4f} s",
              flush=True)

    dev = engine.mesh.devices.flat[0]
    peak = _peaks([dev])[0]
    timed = times[1:]
    tokens = plan.global_batch * seq
    print(f"[train] smoke run, not a benchmark: {cfg.name} "
          f"{cfg.n_layers} layers, seq {seq}, ell {ell}, m {m}: step time "
          f"mean {sum(timed) / len(timed):.4f} s, min {min(timed):.4f} s "
          f"({tokens} tokens/step); peak_bytes_in_use {_gib(peak)}",
          flush=True)
    first_err = abs(losses[0] - ref_loss)
    print(f"[train] first loss {losses[0]:.6f} vs M.loss_fn {ref_loss:.6f}: "
          f"|diff| {first_err:.3e} (bound {FIRST_LOSS_TOL:.0e})", flush=True)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not first_err <= FIRST_LOSS_TOL:
        raise AssertionError(f"first loss {losses[0]} vs reference "
                             f"{ref_loss}: {first_err} > {FIRST_LOSS_TOL}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {"losses": losses, "ref_loss": ref_loss, "times": times,
            "peak_bytes": peak}


def uneven_plan(cfg, seq: int, batch: int):
    """The Cephalo plan for a mixed two-v5e + two-v4 fleet: batch sizes
    and state ratios that differ by rank."""
    from repro.core import device_specs as D
    from repro.core.cost_model import analytic_cluster_model
    from repro.core.model_stats import build_model_stats
    from repro.core.planner import auto_solve
    cm = analytic_cluster_model(D.mixed_tpu_fleet(v5e=2, v4=2),
                                build_model_stats(cfg, seq))
    plan = auto_solve(cm, batch)
    if not plan.feasible:
        raise AssertionError(f"uneven plan infeasible: "
                             f"{plan.infeasible_reason}")
    return plan


def four_chip_phase(cfg, devices, uneven, *, seq: int = 2048,
                    ell: int = 2, steps: int = 3, seed: int = 0) -> dict:
    """The even plan, then ``uneven``, over ``devices``, on the same
    blocks.  Both plans must have the same global batch."""
    import jax
    from repro.core.engine import build_train_step, homogeneous_plan
    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.launch.mesh import make_mesh

    n = len(devices)
    mesh = make_mesh((n,), ("data",), devices=devices)
    plans = {"even": homogeneous_plan(n, ell=ell, m=1), "uneven": uneven}
    if uneven.n != n or uneven.global_batch != plans["even"].global_batch:
        raise ValueError(f"uneven plan has {uneven.n} ranks and batch "
                         f"{uneven.global_batch}; the even plan has {n} "
                         f"and {plans['even'].global_batch}")
    if len({round(r.state_ratio, 6) for r in uneven.ranks}) < 2 or \
            len({r.b for r in uneven.ranks}) < 2:
        raise AssertionError("uneven plan is not uneven: "
                             + uneven.summary())
    stream = SyntheticStream(DataConfig(cfg.vocab_size, seq, seed=seed))
    result = {}
    for name, plan in plans.items():
        print(f"[four] {name} plan:\n{plan.summary()}", flush=True)
        engine = build_train_step(cfg, plan, substrate="shard_map",
                                  mesh=mesh, seq_len=seq)
        state = engine.init_state(jax.random.PRNGKey(seed))
        held = {d.id: 0 for d in devices}
        for leaf in state.values():
            for shard in leaf.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        prog = engine.program
        whole = sum(g.count * g.layout.size for g in prog.groups) * 12
        padded = sum(g.count * g.layout.p_max for g in prog.groups) * 12
        for i, d in enumerate(devices):
            print(f"[four] {name} device {d.id}: state {_gib(held[d.id])} "
                  f"= {held[d.id] / whole:.3f} of {_gib(whole)}; plan "
                  f"r_{i} {prog.ratios[i]:.3f}, padded share "
                  f"{padded / whole:.3f}", flush=True)
            if held[d.id] > MAX_STATE_SHARE * whole:
                raise AssertionError(f"device {d.id} holds "
                                     f"{held[d.id] / whole:.2f} of the state")
            if abs(held[d.id] - padded) > 64:
                raise AssertionError(f"device {d.id} holds {held[d.id]} B, "
                                     f"layout says {padded} B")
        losses = []
        for step in range(steps):
            big = stream.sample(step, plan.global_batch)
            t0 = time.perf_counter()
            state, loss = engine.step(state, big)
            jax.block_until_ready(state)
            losses.append(loss)
            print(f"[four] {name} step {step} loss {loss:.6f} "
                  f"{time.perf_counter() - t0:.4f} s", flush=True)
        peaks = _peaks(devices)
        print(f"[four] {name} peak_bytes_in_use by device: "
              + ", ".join(_gib(p) for p in peaks), flush=True)
        result[name] = {"losses": losses, "held": held, "peaks": peaks,
                        "ratios": list(prog.ratios)}
        del state, engine
    diffs = [abs(a - b) for a, b in zip(result["even"]["losses"],
                                        result["uneven"]["losses"])]
    print(f"[four] even vs uneven |loss diff| by step: "
          + ", ".join(f"{x:.3e}" for x in diffs)
          + f" (bound {PLAN_LOSS_TOL:.0e})", flush=True)
    for name in plans:
        if not all(math.isfinite(x) for x in result[name]["losses"]):
            raise AssertionError(f"non-finite loss under {name} plan")
    if not max(diffs) <= PLAN_LOSS_TOL:
        raise AssertionError(f"even and uneven losses differ by "
                             f"{max(diffs)} > {PLAN_LOSS_TOL}")
    result["diffs"] = diffs
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip even-vs-uneven phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke.py needs the repository's src/ beside it "
              f"({SRC} not found)", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)

    import jax
    from repro.configs.base import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    try:
        dev = device_phase()
    except NoChip as e:
        print(f"chip_smoke.py: {e}", file=sys.stderr)
        return 1
    print(f"[setup] compile cache: {enable_compile_cache()}", flush=True)
    stablelm = get_arch("stablelm-1.6b")
    if args.four_chips:
        devices = jax.devices()
        if len(devices) < 4:
            raise NoChip(f"--four-chips needs 4 devices, have "
                         f"{len(devices)}")
        four_chip_phase(stablelm, devices[:4],
                        uneven_plan(stablelm, seq=2048, batch=8),
                        seed=args.seed)
    else:
        kernel_phase(seed=args.seed)
        train_phase(dataclasses.replace(stablelm, n_layers=4),
                    seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
