"""CollectiveSubstrate — how gather/scatter are actually performed.

Schedules (``repro.core.engine.schedules``) decide *when* the per-unit
collectives of the paper's Fig. 4 rounds happen; substrates decide
*how* (uneven-shard AllGather/ReduceScatter, paper Sec. 2 / App. C):

* :class:`ShardMapSubstrate` — in-graph ``lax`` collectives inside a
  ``jax.shard_map`` SPMD program.  Forward AllGather and backward
  ReduceScatter are fused into one differentiable gather
  (``fsdp.make_mixed_gather`` custom_vjp) with independent forward /
  backward precision, plus the HSDP replica all-reduce.
* :class:`LoopbackSubstrate` — host-side software collectives for the
  MPMD process model: full-pytree reassembly from per-rank ragged shards
  (AllGatherv semantics, zero padding overhead) and full-grad →
  per-rank-slice scatter.  On a real fleet each rank is one JAX process
  and these calls become NCCL/gloo collectives; the surface stays the
  same, which is the seam
  :class:`repro.core.engine.multiproc.MultiProcessSubstrate` implements
  with one OS process per rank (the shards live in the workers, the
  collectives move real bytes between processes — synchronously per
  round, or pipelined under compute on the ring topology's overlapped
  mode).  Substrates decide *how* and may decide *when the bytes move*,
  but never the reduction order: that is what keeps every substrate in
  the bitwise-parity club.

The loopback substrate counts collective *events* (``stats``) so tests
can assert a schedule's round structure without parsing HLO.  The
shard_map substrate's collectives live inside a traced program, where
Python-side counters would reflect tracing (once per jit cache entry,
re-traces under remat), not execution — assert its collective structure
on compiled HLO instead (``repro.roofline.analysis.parse_collectives``).
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fsdp
from repro.core.engine.units import UnitGroup, UnitPlanner


def shard_map_call(fn, mesh, in_specs, out_specs):
    """The substrate's one ``shard_map`` binding (no replication check:
    the step's outputs are per-device shards plus a psum'd loss)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class CollectiveSubstrate(abc.ABC):
    """Common surface of the per-unit gather/scatter machinery."""

    name: str = "abstract"

    def __init__(self) -> None:
        self.stats: Dict[str, int] = {"all_gather": 0, "reduce_scatter": 0}

    def reset_stats(self) -> None:
        for k in self.stats:
            self.stats[k] = 0


class ShardMapSubstrate(CollectiveSubstrate):
    """In-graph lax collectives for the shard_map SPMD runtime.

    ``state_axes`` — mesh axes the state is sharded over (ZeRO-3 over all
    axes by default); ``replica_axes`` — HSDP replication axes whose
    gradient all-reduce rides on the gather's backward pass.
    """

    name = "shard_map"

    def __init__(self, state_axes: Sequence[str],
                 replica_axes: Sequence[str] = (),
                 gather_dtype=jnp.float32, grad_dtype=jnp.float32):
        super().__init__()
        self.state_axes = tuple(state_axes)
        self.replica_axes = tuple(replica_axes)
        self.gather_dtype = gather_dtype
        self.grad_dtype = grad_dtype

    def unit_gather_fn(self, group: UnitGroup) -> Callable[[jax.Array], Any]:
        """(P_max,) local shard → full param tree for one unit.

        Differentiable: the VJP is one ReduceScatter of the cotangent (plus
        the HSDP replica psum) — the schedule's per-round collective pair.
        """
        fn = fsdp.make_mixed_gather(group.layout, self.state_axes,
                                    self.gather_dtype, self.grad_dtype,
                                    replica_axes=self.replica_axes)

        def gather(shard: jax.Array) -> Any:
            full = fn(shard)
            return fsdp.unflatten_unit(group.layout, full,
                                       dtype=self.gather_dtype)

        return gather


class LoopbackSubstrate(CollectiveSubstrate):
    """Host-side software collectives for the MPMD loopback runtime.

    State lives as per-rank *ragged* shards (physical memory ∝ r_i — the
    paper's memory-balancing claim); gather reassembles the full pytree,
    scatter slices a full gradient pytree back into rank shards.
    """

    name = "loopback"

    def __init__(self, planner: UnitPlanner):
        super().__init__()
        self.planner = planner
        self.n = planner.n

    # --- flat wire format ---------------------------------------------------
    # The three primitives below are the single layout path shared by the
    # loopback collectives AND the multiproc substrate's coordinator /
    # workers: a model-shaped pytree ⇄ per-unit flat fp32 buffers
    # (``(padded,)``, or ``(count, padded)`` for stacked stage units)
    # ⇄ per-rank ragged slices.  Params, gradients, optimizer moments,
    # and elastic state migration all route through them, so the layouts
    # can never desynchronize.

    def flatten_tree(self, tree: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Full model-shaped pytree → {unit: flat padded buffer}."""
        grouped = self.planner.split(tree)
        out: Dict[str, np.ndarray] = {}
        for g in self.planner.groups:
            sub = grouped[g.name]
            if g.count > 1:
                out[g.name] = np.stack([
                    np.asarray(fsdp.flatten_unit(
                        g.layout, jax.tree.map(lambda a, i=i: a[i], sub)))
                    for i in range(g.count)])
            else:
                out[g.name] = np.asarray(fsdp.flatten_unit(g.layout, sub))
        return out

    def slice_flats(self, flats: Dict[str, np.ndarray]
                    ) -> List[Dict[str, np.ndarray]]:
        """{unit: flat buffer} → per-rank {unit: ragged slice} (the
        scatter half of AllGatherv/ReduceScatterv)."""
        out: List[Dict[str, np.ndarray]] = [dict() for _ in range(self.n)]
        for g in self.planner.groups:
            flat = flats[g.name]
            off = 0
            for r, s in enumerate(g.layout.shard_sizes):
                out[r][g.name] = np.asarray(flat[..., off: off + s]).copy()
                off += s
        return out

    def concat_slices(self, slices: Sequence[Dict[str, Any]],
                      key: Optional[str] = None) -> Dict[str, np.ndarray]:
        """Per-rank ragged slices → {unit: flat buffer} (the gather half
        of AllGatherv).  ``key`` indexes {"p","m","v"} state shards;
        ``None`` takes the slice itself (gradient buffers)."""
        out: Dict[str, np.ndarray] = {}
        for g in self.planner.groups:
            parts = []
            for r in range(self.n):
                s = slices[r][g.name]
                if key is not None:
                    s = s[key]
                parts.append(np.asarray(s)[..., : g.layout.shard_sizes[r]])
            out[g.name] = np.concatenate(parts, axis=-1)
        return out

    def unflatten_flats(self, flats: Dict[str, np.ndarray]
                        ) -> Dict[str, Any]:
        """{unit: flat buffer} → full model-shaped pytree."""
        grouped: Dict[str, Any] = {}
        for g in self.planner.groups:
            flat = flats[g.name]
            if g.count > 1:
                elems = [fsdp.unflatten_unit(g.layout, jnp.asarray(flat[i]))
                         for i in range(g.count)]
                grouped[g.name] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *elems)
            else:
                grouped[g.name] = fsdp.unflatten_unit(
                    g.layout, jnp.asarray(flat))
        return self.planner.merge(grouped)

    # --- state layout -------------------------------------------------------
    def shard_tree(self, tree: Dict[str, Any]
                   ) -> List[Dict[str, np.ndarray]]:
        """Any full model-shaped pytree → per-rank {unit: ragged buffer}.

        The single layout path for params, gradients, and optimizer
        moments — state sharding, gradient scatter, and elastic state
        migration all go through here, so they can never desynchronize.
        """
        return self.slice_flats(self.flatten_tree(tree))

    def shard_state(self, params: Dict[str, Any],
                    m_tree: Optional[Dict[str, Any]] = None,
                    v_tree: Optional[Dict[str, Any]] = None,
                    ) -> List[Dict[str, Dict[str, np.ndarray]]]:
        """Full params (+ optional Adam moment trees) → per-rank
        {unit: {"p","m","v"}} ragged shards.  Missing moments init to 0."""
        p_shards = self.shard_tree(params)
        m_shards = self.shard_tree(m_tree) if m_tree is not None else None
        v_shards = self.shard_tree(v_tree) if v_tree is not None else None
        shards: List[Dict[str, Any]] = [dict() for _ in range(self.n)]
        for g in self.planner.groups:
            for r in range(self.n):
                p = p_shards[r][g.name]
                shards[r][g.name] = {
                    "p": p,
                    "m": (m_shards[r][g.name] if m_shards is not None
                          else np.zeros_like(p)),
                    "v": (v_shards[r][g.name] if v_shards is not None
                          else np.zeros_like(p)),
                }
        return shards

    # --- collectives --------------------------------------------------------
    def allgather_params(self, shards: List[Dict[str, Any]],
                         key: str = "p") -> Dict[str, Any]:
        """Reassemble the full params pytree from all ranks' shards."""
        self.stats["all_gather"] += 1
        return self.unflatten_flats(self.concat_slices(shards, key))

    def reduce_scatter_grads(self, grads_full: Any
                             ) -> List[Dict[str, np.ndarray]]:
        """Full-grad pytree → per-rank shard slices (already summed).
        Uses the same ragged layout path as :meth:`shard_state`
        (:meth:`shard_tree`), so the gradient scatter can never
        desynchronize from the state layout."""
        self.stats["reduce_scatter"] += 1
        return self.shard_tree(grads_full)

    def accumulate_grad_shards(self, acc, new):
        """Shard-space gradient accumulation across collective rounds."""
        if acc is None:
            return new
        return [{name: acc[r][name] + new[r][name] for name in new[r]}
                for r in range(self.n)]
