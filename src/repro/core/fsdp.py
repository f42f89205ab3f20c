"""Uneven FSDP/ZeRO-3 state sharding on flat per-unit buffers.

Every FSDP *unit* (one transformer block, or the embed/head) is flattened
into a single fp32 vector, padded to a 128-element quantum, and split into
per-rank shards sized by the planner's ratios ``r_i``.  All ranks hold a
``(P_max,)`` buffer (padded uneven shards — the XLA-static analogue of the
paper's generalized AllGatherv, DESIGN.md §2); collectives therefore move
``N · P_max`` bytes, and the measured overhead vs. even sharding is the
analogue of the paper's ≤15% (App. C) — see
``benchmarks/appc_uneven_overhead.py``.

The gather/scatter pair is differentiable: ``all_gather``'s transpose is
``psum_scatter``, so ``jax.grad`` through :func:`gather_unit` produces
exactly one ReduceScatter per unit per backward pass (the paper's Fig. 4
schedule falls out of the loop structure + remat policy in
:mod:`repro.core.layered_ga`).

This module is the engine's *primitive* layer: unit grouping and layout
construction live in :mod:`repro.core.engine.units` (UnitPlanner), and
the substrates (:mod:`repro.core.engine.substrate`) bind these flat
layouts to either in-graph lax collectives (shard_map) or host loopback
gather/scatter (MPMD).  Nothing above the engine should call the
collective helpers here directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import even_shard_sizes

QUANTUM = 128

#: Named scopes of the unit exchange, for the device trace: the forward
#: AllGather with its cast, and the backward ReduceScatter of the
#: gradient with its casts.  (Not ``gather``/``scatter``: those are the
#: names of lax primitives, the last component of every op's name.)
GATHER_SCOPE = "unit_gather"
SCATTER_SCOPE = "unit_scatter"


# ---------------------------------------------------------------------------
# Flat layout of one unit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class UnitLayout:
    """Static description of one unit's flattened parameter buffer."""

    name: str
    treedef: Any
    shapes: List[Tuple[int, ...]]
    size: int                    # true element count
    padded: int                  # padded to Σ shard_sizes
    shard_sizes: List[int]       # per-rank valid lengths (sum == padded)

    @property
    def p_max(self) -> int:
        return max(self.shard_sizes)

    @property
    def n(self) -> int:
        return len(self.shard_sizes)

    def offsets(self) -> List[int]:
        out, off = [], 0
        for s in self.shard_sizes:
            out.append(off)
            off += s
        return out


def make_layout(name: str, tree: Any, ratios: Sequence[float],
                ) -> UnitLayout:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [tuple(x.shape) for x in leaves]
    size = sum(int(np.prod(s)) for s in shapes)
    n = len(ratios)
    padded = ((size + n * QUANTUM - 1) // (n * QUANTUM)) * (n * QUANTUM)
    shard_sizes = even_shard_sizes(padded, ratios, quantum=QUANTUM)
    return UnitLayout(name, treedef, shapes, size, padded, shard_sizes)


def flatten_unit(layout: UnitLayout, tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([x.astype(jnp.float32).reshape(-1)
                            for x in leaves])
    return jnp.pad(flat, (0, layout.padded - layout.size))


def unflatten_unit(layout: UnitLayout, flat: jax.Array,
                   dtype=jnp.float32) -> Any:
    leaves, off = [], 0
    for shape in layout.shapes:
        n = int(np.prod(shape))
        leaves.append(flat[off: off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(layout.treedef, leaves)


def shard_unit(layout: UnitLayout, flat: jax.Array,
               rank: jax.Array) -> jax.Array:
    """flat (padded,) → rank's (P_max,) shard in the SPMD wire format:
    its ``shard_sizes[rank]`` values, then zeros (XLA arrays must be
    uniform per device).  ``rank`` may be traced (``lax.axis_index``), so
    each device of a ``shard_map`` cuts out only its own shard."""
    offsets = jnp.asarray(layout.offsets(), jnp.int32)
    sizes = jnp.asarray(layout.shard_sizes, jnp.int32)
    buf = jax.lax.dynamic_slice(jnp.pad(flat, (0, layout.p_max)),
                                (offsets[rank],), (layout.p_max,))
    return jnp.where(jnp.arange(layout.p_max) < sizes[rank], buf,
                     jnp.zeros_like(buf))


def shard_unit_ragged(layout: UnitLayout, flat) -> List[np.ndarray]:
    """Host-side: flat (padded,) → exact per-rank slices, *no padding*.

    This is the MPMD storage format: physical memory per rank is truly
    ∝ r_i (the paper's memory-balancing claim).  Padding to P_max is an
    SPMD-only wire-format artifact (DESIGN.md §7.1)."""
    arr = np.asarray(flat)
    out, off = [], 0
    for s in layout.shard_sizes:
        out.append(arr[off: off + s].copy())
        off += s
    return out


# ---------------------------------------------------------------------------
# Collectives (inside shard_map)
# ---------------------------------------------------------------------------

def gather_unit(layout: UnitLayout, shard: jax.Array,
                axis_names) -> jax.Array:
    """(P_max,) local shard → (padded,) full flat buffer.  One AllGather.

    The buffer is reassembled from per-rank slices, even shards included:
    a reshape of the gathered rows instead made the TPU compile about 9×
    slower (266 s vs 30 s for the 24-layer stablelm-1.6b step over a
    v5e:2x2, compiled on an 8-core host) to save one copy.
    """
    stacked = jax.lax.all_gather(shard, axis_names)      # (N, P_max)
    parts = [stacked[i, : layout.shard_sizes[i]] for i in range(layout.n)]
    return jnp.concatenate(parts)


def make_mixed_gather(layout: UnitLayout, axis_names, fwd_dtype,
                      bwd_dtype, replica_axes=()):
    """Gather with independent forward/backward precision.

    Forward: AllGather in ``fwd_dtype`` (bf16 halves wire bytes), under
    the named scope ``GATHER_SCOPE``.  Backward: ReduceScatter of the
    cotangent in ``bwd_dtype`` (fp32 keeps the paper's full-precision
    gradient averaging even with bf16 gathers), under ``SCATTER_SCOPE``.
    The fp32 master shard never leaves the owning rank.

    ``replica_axes`` — HSDP mode: state is sharded over ``axis_names``
    only and replicated over these axes; the backward additionally
    all-reduces the scattered shard across the replicas (the classic
    hierarchical-FSDP gradient sync).
    """
    @jax.custom_vjp
    def gather(shard):
        with jax.named_scope(GATHER_SCOPE):
            return gather_unit(layout, shard.astype(fwd_dtype), axis_names)

    def fwd(shard):
        return gather(shard), None

    def bwd(_, ct):
        with jax.named_scope(SCATTER_SCOPE):
            g = scatter_grad(layout, ct.astype(bwd_dtype), axis_names)
            if replica_axes:
                g = jax.lax.psum(g, replica_axes)
            return (g.astype(jnp.float32),)

    gather.defvjp(fwd, bwd)
    return gather


def scatter_grad(layout: UnitLayout, grad_flat: jax.Array,
                 axis_names) -> jax.Array:
    """(padded,) full grad → (P_max,) reduced local shard: per-rank rows
    padded to P_max, then one ReduceScatter (no reshape path for even
    shards, for the compile time noted in :func:`gather_unit`)."""
    rows = []
    for i, off in enumerate(layout.offsets()):
        seg = grad_flat[off: off + layout.shard_sizes[i]]
        rows.append(jnp.pad(seg, (0, layout.p_max - layout.shard_sizes[i])))
    return jax.lax.psum_scatter(jnp.stack(rows), axis_names,
                                scatter_dimension=0, tiled=False)


# ---------------------------------------------------------------------------
# Vocab-sharded embedding / head (embeddings are too large to gather)
# ---------------------------------------------------------------------------

def embed_rows_for_rank(vocab: int, n: int) -> List[Tuple[int, int]]:
    """Row ranges of the vocab-sharded embedding table."""
    per = (vocab + n - 1) // n
    return [(i * per, min((i + 1) * per, vocab)) for i in range(n)]


def sharded_embed_lookup(embed_shard: jax.Array, tokens: jax.Array,
                         v_start: int, axis_names) -> jax.Array:
    """Embedding lookup with a row-sharded table.

    embed_shard: (V_loc, D) this rank's rows [v_start, v_start+V_loc).
    Lookup = local masked gather + psum over the state axis.
    """
    v_loc = embed_shard.shape[0]
    local = tokens - v_start
    valid = (local >= 0) & (local < v_loc)
    idx = jnp.clip(local, 0, v_loc - 1)
    x = embed_shard[idx] * valid[..., None].astype(embed_shard.dtype)
    return jax.lax.psum(x, axis_names)


def sharded_ce(h: jax.Array, embed_shard: jax.Array, labels: jax.Array,
               weights: jax.Array, v_start: int, axis_names,
               final_softcap: float = 0.0) -> jax.Array:
    """Σ w·CE with a row-sharded (tied) unembedding.

    h: (..., D); embed_shard: (V_loc, D).  Per-shard logits → global
    logsumexp via exp-sum psum; the picked logit via masked psum.
    """
    z = (h.astype(jnp.float32)
         @ embed_shard.astype(jnp.float32).T)            # (..., V_loc)
    if final_softcap > 0:
        z = final_softcap * jnp.tanh(z / final_softcap)
    m_loc = z.max(axis=-1)
    m_glob = jax.lax.pmax(m_loc, axis_names)
    sumexp = jnp.sum(jnp.exp(z - m_glob[..., None]), axis=-1)
    sumexp = jax.lax.psum(sumexp, axis_names)
    lse = m_glob + jnp.log(sumexp)
    local = labels - v_start
    v_loc = embed_shard.shape[0]
    valid = (local >= 0) & (local < v_loc)
    idx = jnp.clip(local, 0, v_loc - 1)
    picked = jnp.take_along_axis(z, idx[..., None], axis=-1)[..., 0]
    picked = jax.lax.psum(picked * valid.astype(jnp.float32), axis_names)
    return jnp.sum(weights * (lse - picked))
