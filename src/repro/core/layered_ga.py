"""SPMD Cephalo train step: uneven FSDP + layered gradient accumulation.

Builds a ``jax.jit``-able train step that runs inside ``shard_map`` over
the *flattened* data-parallel axis (every chip is a ZeRO-3 worker; the
``model`` mesh axis shards state only — paper Sec. 2).  The unit
grouping, GA schedule, and collective machinery all come from the shared
execution engine (:mod:`repro.core.engine`, DESIGN.md §Engine):

* **UnitPlanner** supplies the canonical param→unit grouping and flat
  shard layouts (one copy, shared with the MPMD runtime).
* **Schedule** partitions the ℓ microbatches into collective rounds:
  ``layered`` (Cephalo, paper Fig. 4 bottom — one AllGather per unit per
  forward, one re-gather + one ReduceScatter per unit per backward, all
  microbatches between collectives), ``per_microbatch`` (FSDP-GA
  baseline, Fig. 4 top — every microbatch pays the full per-unit
  collective bill), ``interleaved``, or any registered schedule.  The
  layered schedule falls out of the loop structure (unit loop outer,
  microbatch scan inner) plus full rematerialization, which has two
  levels: the unit (the bwd re-gathers instead of saving gathered
  params) and, inside it, the microbatch (the bwd recomputes and
  transposes one microbatch at a time, so one microbatch's residuals
  live at a time, never a stack of ℓ).
* **ShardMapSubstrate** provides the differentiable mixed-precision
  gather whose VJP is the per-unit ReduceScatter (plus the HSDP replica
  all-reduce).

Per-device batch layout is the plan's padded grid ``(ell, m, seq)`` with
Eq. 1 weights zeroing the padding (repro.data.pipeline).

Knobs beyond the paper (recorded separately in EXPERIMENTS.md §Perf):
``gather_dtype`` (fp32 paper-faithful / bf16 halves collective bytes),
``remat`` ("full" recompute / "offload" host-offloads boundary
activations), ``unroll`` (unroll unit loops so HLO collective counts are
exact for the roofline parser).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import fsdp
from repro.core.engine.schedules import Schedule, get_schedule
from repro.core.engine.substrate import ShardMapSubstrate, shard_map_call
from repro.core.engine.units import (UnitGroup, UnitPlanner, merge_params,
                                     split_params)
from repro.models import model as M
from repro.optim.adam import AdamConfig, adam_update


class CephaloProgram:
    """Everything needed to build/run the SPMD train step for one arch."""

    def __init__(self, cfg: ArchConfig, mesh: Mesh,
                 ratios: Optional[Sequence[float]] = None,
                 ell: int = 1, m: int = 1, seq: int = 512,
                 ga_mode: Union[str, Schedule] = "layered",
                 gather_dtype: str = "float32",
                 grad_dtype: str = "float32",
                 remat: str = "full",
                 unroll: bool = False,
                 adam: AdamConfig = AdamConfig(),
                 ce_chunk: int = 512,
                 has_frontend_batch: bool = False,
                 state_axes: Optional[Sequence[str]] = None,
                 schedule: Union[str, Schedule, None] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        # HSDP (beyond-paper): shard state over a SUBSET of mesh axes and
        # replicate across the rest — 16-deep gather rings instead of
        # 256-deep, at a replication-factor memory cost.  Default: pure
        # ZeRO-3 over all axes (the paper's design point).
        self.state_axes = tuple(state_axes) if state_axes is not None \
            else self.axes
        self.replica_axes = tuple(a for a in self.axes
                                  if a not in self.state_axes)
        self.n = int(np.prod(mesh.devices.shape))
        self.n_state = int(np.prod([mesh.shape[a]
                                    for a in self.state_axes]))
        self.ratios = list(ratios) if ratios is not None \
            else [1.0 / self.n_state] * self.n_state
        assert len(self.ratios) == self.n_state
        self.ell, self.m, self.seq = ell, m, seq
        # ``schedule`` (engine API) wins over the legacy ``ga_mode`` alias.
        self.schedule = get_schedule(schedule if schedule is not None
                                     else ga_mode)
        self.ga_mode = self.schedule.name
        self.gather_dtype = jnp.bfloat16 if gather_dtype == "bfloat16" \
            else jnp.float32
        self.grad_dtype = jnp.bfloat16 if grad_dtype == "bfloat16" \
            else jnp.float32
        self.remat = remat
        self.unroll = unroll
        self.adam = adam
        self.ce_chunk = ce_chunk
        self.has_frontend = bool(cfg.frontend_dim) and has_frontend_batch
        self.planner = UnitPlanner(cfg, self.ratios)
        self.stages = self.planner.stages
        self.groups = self.planner.groups
        self.substrate = ShardMapSubstrate(
            self.state_axes, replica_axes=self.replica_axes,
            gather_dtype=self.gather_dtype, grad_dtype=self.grad_dtype)

    # --- layouts ----------------------------------------------------------
    def group(self, name: str) -> UnitGroup:
        return self.planner.group(name)

    def has_group(self, name: str) -> bool:
        return self.planner.has_group(name)

    # --- state ------------------------------------------------------------
    def state_shapes(self) -> Dict[str, Any]:
        """Global (pre-shard_map) array shapes for the training state."""
        out: Dict[str, Any] = {"step": jax.ShapeDtypeStruct((), jnp.int32)}
        for g in self.groups:
            shape = (g.count, self.n_state * g.layout.p_max) \
                if g.count > 1 else (self.n_state * g.layout.p_max,)
            for part in ("p", "m", "v"):
                out[f"{g.name}/{part}"] = jax.ShapeDtypeStruct(
                    shape, jnp.float32)
        return out

    def _state_spec(self, name: str) -> P:
        if name == "step":
            return P()
        g = self.group(name.split("/")[0])
        return P(None, self.state_axes) if g.count > 1 \
            else P(self.state_axes)

    def state_shardings(self) -> Dict[str, Any]:
        return {n: NamedSharding(self.mesh, self._state_spec(n))
                for n in self._state_names()}

    def batch_shapes(self) -> Dict[str, Any]:
        b = (self.n, self.ell, self.m, self.seq)
        out = {
            "tokens": jax.ShapeDtypeStruct(b, jnp.int32),
            "labels": jax.ShapeDtypeStruct(b, jnp.int32),
            "weights": jax.ShapeDtypeStruct(b, jnp.float32),
        }
        if self.has_frontend:
            out["frontend_embed"] = jax.ShapeDtypeStruct(
                b + (self.cfg.frontend_dim,), jnp.float32)
        return out

    def batch_shardings(self) -> Dict[str, Any]:
        s = NamedSharding(self.mesh, P(self.axes))
        return {k: s for k in self.batch_shapes()}

    def _layout_state(self, trees: Dict[str, Any], step: jax.Array
                      ) -> Dict[str, jax.Array]:
        """Full model-shaped trees (``trees["p"]`` and optionally "m"/"v",
        replicated) → sharded state.  Runs as a ``shard_map``: each device
        cuts only its own (P_max,) shard of every unit, so no device holds
        the whole laid-out state.  Missing moments are zero."""
        def local(trees, step):
            rank = jax.lax.axis_index(self.state_axes)
            grouped = {k: split_params(self.cfg, t) for k, t in trees.items()}
            out = {"step": step}
            for g in self.groups:
                def one(elem, _g=g):
                    flat = fsdp.flatten_unit(_g.layout, elem)
                    return fsdp.shard_unit(_g.layout, flat, rank)

                # stage units: one traced body for the whole stack
                shard = jax.vmap(one) if g.count > 1 else one
                for part in ("p", "m", "v"):
                    out[f"{g.name}/{part}"] = (
                        shard(grouped[part][g.name]) if part in grouped
                        else jnp.zeros_like(out[f"{g.name}/p"]))
            return out

        out_specs = {n: self._state_spec(n) for n in self._state_names()}
        return shard_map_call(local, self.mesh, (P(), P()), out_specs)(
            trees, step)

    def state_from_trees(self, params: Dict[str, Any],
                         m_tree: Optional[Dict[str, Any]] = None,
                         v_tree: Optional[Dict[str, Any]] = None,
                         step: int = 0) -> Dict[str, jax.Array]:
        """Materialize sharded state from full model-shaped pytrees.

        The import half of the elastic state-migration seam: params and
        (optionally) Adam moment trees are laid out on THIS program's
        shard layouts.  Missing moments initialize to zero."""
        trees = {k: t for k, t in (("p", params), ("m", m_tree),
                                   ("v", v_tree)) if t is not None}
        build = jax.jit(self._layout_state,
                        out_shardings=self.state_shardings())
        return build(trees, jnp.int32(step))

    def init_state(self, key: jax.Array) -> Dict[str, jax.Array]:
        """Materialize real state from ``M.init_params(cfg, key)``: the
        draw and the layout are one ``jit``, so the state is built in
        place, each device making only its own shards."""
        def build(k):
            return self._layout_state({"p": M.init_params(self.cfg, k)},
                                      jnp.int32(0))
        return jax.jit(build, out_shardings=self.state_shardings())(key)

    def gather_part(self, state: Dict[str, jax.Array],
                    part: str = "p") -> Dict[str, Any]:
        """Host-side: reassemble one full model-shaped pytree from the
        sharded state.  ``part`` — "p" (params), "m" or "v" (moments).
        The export half of the elastic state-migration seam."""
        grouped: Dict[str, Any] = {}
        for g in self.groups:
            buf = np.asarray(state[f"{g.name}/{part}"])
            if g.count > 1:
                elems = []
                for i in range(g.count):
                    flat = self._unshard_host(g.layout, buf[i])
                    elems.append(fsdp.unflatten_unit(g.layout, flat))
                grouped[g.name] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *elems)
            else:
                flat = self._unshard_host(g.layout, buf)
                grouped[g.name] = fsdp.unflatten_unit(g.layout, flat)
        return merge_params(grouped, len(self.stages))

    def gather_params(self, state: Dict[str, jax.Array]) -> Dict[str, Any]:
        """Host-side: reassemble the full model params pytree (tests)."""
        return self.gather_part(state, "p")

    def _unshard_host(self, layout: fsdp.UnitLayout,
                      buf: np.ndarray) -> jnp.ndarray:
        stacked = buf.reshape(self.n_state, layout.p_max)
        parts = [stacked[i, : layout.shard_sizes[i]]
                 for i in range(self.n_state)]
        return jnp.asarray(np.concatenate(parts))

    # -----------------------------------------------------------------
    # The step itself
    # -----------------------------------------------------------------
    def _gather(self, g: UnitGroup, shard: jax.Array) -> Any:
        # bf16 gathers halve the AllGather wire bytes (beyond-paper knob;
        # fp32 is the paper-faithful default); the grad ReduceScatter
        # precision is independent (fsdp.make_mixed_gather custom_vjp).
        return self.substrate.unit_gather_fn(g)(shard)

    def _apply_remat(self, fn):
        if self.remat == "none":
            return fn
        if self.remat == "offload":
            from jax.ad_checkpoint import checkpoint_policies as cp
            policy = cp.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["boundary"],
                offload_src="device", offload_dst="pinned_host")
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)

    def _loss_from_shards(self, pshards: Dict[str, jax.Array],
                          tokens, labels, weights, frontend
                          ) -> jax.Array:
        """Forward + loss for this device's (ell, m, seq) grid, collectives
        inside.  Differentiating w.r.t. pshards yields one ReduceScatter
        per unit gather."""
        cfg = self.cfg
        cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        positions = jnp.broadcast_to(
            jnp.arange(self.seq, dtype=jnp.int32)[None],
            (self.m, self.seq))

        embed_g = self.group("embed")
        misc_g = self.group("misc")

        def embed_fn(eshard, mshard, toks, fe):
            etree = self._gather(embed_g, eshard)
            mtree = self._gather(misc_g, mshard)

            def one(tok_mb, fe_mb):
                p = {"embed": etree["embed"], **mtree}
                return M.embed_tokens(cfg, p, tok_mb, positions, fe_mb)

            if fe is None:
                return jax.vmap(lambda t: one(t, None))(toks)
            return jax.vmap(one)(toks, fe)

        x_all = self._apply_remat(embed_fn)(
            pshards["embed"], pshards["misc"], tokens,
            frontend.astype(cdt) if frontend is not None else None)
        x_all = x_all.astype(cdt)
        aux = jnp.float32(0.0)

        shared_tree = None
        if self.has_group("shared"):
            sh_g = self.group("shared")
            shared_tree = jax.tree.map(
                lambda a: a.astype(cdt),
                self._gather(sh_g, pshards["shared"]))

        for g in self.groups:
            if g.stage_idx < 0:
                continue
            spec = self.stages[g.stage_idx]
            shard_stack = pshards[g.name]          # (count, P_max)

            def elem_body(carry, elem_shard, _g=g, _spec=spec):
                x_all, aux = carry
                w_tree = jax.tree.map(
                    lambda a: a.astype(cdt), self._gather(_g, elem_shard))

                def mb_body(_, x_mb):
                    y, a = M.element_apply(cfg, _spec, w_tree, x_mb,
                                           positions, shared_tree)
                    return None, (y, a)

                if self.remat == "full":
                    # nested remat: without it the bwd stacks every
                    # microbatch's residuals over ell (PERF.md §5); w_tree
                    # stays closed over, so the unit is gathered once.
                    mb_body = jax.checkpoint(mb_body)

                _, (ys, auxs) = jax.lax.scan(mb_body, None, x_all)
                return (ys, aux + jnp.sum(auxs)), None

            body = self._apply_remat(elem_body)
            (x_all, aux), _ = jax.lax.scan(
                body, (x_all, aux), shard_stack,
                unroll=g.count if self.unroll else 1)

        # head / loss: gather once, CE over all microbatches in the round
        def head_fn(eshard, mshard, hshard, x_all):
            etree = self._gather(embed_g, eshard)
            mtree = self._gather(misc_g, mshard)
            p = {"embed": etree["embed"], **mtree}
            if hshard is not None:
                p["head"] = self._gather(self.group("head"), hshard)["head"]

            def mb_ce(x_mb, y_mb, w_mb):
                return M.chunked_ce(cfg, p, x_mb, y_mb, w_mb, self.ce_chunk)

            return jnp.sum(jax.vmap(mb_ce)(x_all, labels, weights))

        hshard = pshards.get("head")
        ce = self._apply_remat(head_fn)(
            pshards["embed"], pshards["misc"], hshard, x_all)
        return ce + cfg.router_aux_coef * aux

    def _run_schedule(self, pshards, tokens, labels, weights, frontend
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Loss + shard-space grads under the configured GA schedule.

        The schedule partitions the ℓ microbatches into collective rounds;
        each round re-gathers every unit (full remat: the bwd re-gathers
        too) and ReduceScatters its gradient contribution.  One round ==
        layered GA; ℓ rounds of 1 == the FSDP-GA baseline.
        """
        chunks = self.schedule.chunks(self.ell)

        def round_loss(ps, toks, labs, w, fe):
            return self._loss_from_shards(ps, toks, labs, w, fe)

        if len(chunks) == 1:
            # Single-round (layered) fast path: one value_and_grad over
            # the whole grid — bit-identical to the historical ga_mode.
            return jax.value_and_grad(
                lambda ps: round_loss(ps, tokens, labels, weights,
                                      frontend))(pshards)

        def round_grad(ps, start, size):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, 0)
            t, l_, w = sl(tokens), sl(labels), sl(weights)
            f = sl(frontend) if frontend is not None else None
            return jax.value_and_grad(
                lambda p: round_loss(p, t, l_, w, f))(ps)

        # Group the rounds into runs of equal size and scan each run (one
        # compiled body per distinct size — e.g. interleaved with odd ℓ is
        # one scan over the [2]-rounds plus a single trailing [1] round).
        # FSDP reshards (frees) gathered params after each round; the
        # barrier ties each round's gathers to the running accumulator so
        # XLA cannot CSE the re-gathers away when the loop is unrolled.
        runs: List[List[int]] = []       # [offset, round size, count]
        off = 0
        for size in chunks:
            if runs and runs[-1][1] == size:
                runs[-1][2] += 1
            else:
                runs.append([off, size, 1])
            off += size

        loss = jnp.float32(0.0)
        grads = jax.tree.map(jnp.zeros_like, pshards)
        for run_off, size, count in runs:
            if count == 1:
                ps, _ = jax.lax.optimization_barrier((pshards, loss))
                li, gi = round_grad(ps, run_off, size)
                loss = loss + li
                grads = jax.tree.map(jnp.add, grads, gi)
                continue

            starts = run_off + jnp.arange(count) * size

            def scan_body(carry, start):
                loss_acc, gacc = carry
                ps, _ = jax.lax.optimization_barrier((pshards, loss_acc))
                li, gi = round_grad(ps, start, size)
                gacc = jax.tree.map(jnp.add, gacc, gi)
                return (loss_acc + li, gacc), None

            (loss, grads), _ = jax.lax.scan(
                scan_body, (loss, grads), starts,
                unroll=count if self.unroll else 1)
        return loss, grads

    def _device_step(self, *flat_args):
        """Runs inside shard_map.  Args: state leaves + batch leaves."""
        names = self._state_names()
        nstate = len(names)
        state = dict(zip(names, flat_args[:nstate]))
        batch = dict(zip(self._batch_names(), flat_args[nstate:]))
        # squeeze the rank dim the shard_map sharding leaves as 1
        tokens = batch["tokens"][0]
        labels = batch["labels"][0]
        weights = batch["weights"][0]
        frontend = batch.get("frontend_embed")
        if frontend is not None:
            frontend = frontend[0]

        pshards = {g.name: state[f"{g.name}/p"] for g in self.groups}
        loss, grads = self._run_schedule(pshards, tokens, labels, weights,
                                         frontend)

        # Adam on local shards (ZeRO-3: fully local update)
        new_state = {"step": state["step"] + 1}
        with jax.named_scope("adam"):
            for g in self.groups:
                p = state[f"{g.name}/p"]
                gm = state[f"{g.name}/m"]
                gv = state[f"{g.name}/v"]
                gr = grads[g.name].astype(jnp.float32)
                np_, nm, nv = adam_update(self.adam, p, gr, gm, gv,
                                          state["step"] + 1)
                new_state[f"{g.name}/p"] = np_
                new_state[f"{g.name}/m"] = nm
                new_state[f"{g.name}/v"] = nv
        return tuple(new_state[k] for k in names) + (loss,)

    def _state_names(self) -> List[str]:
        names = ["step"]
        for g in self.groups:
            names += [f"{g.name}/p", f"{g.name}/m", f"{g.name}/v"]
        return names

    def _batch_names(self) -> List[str]:
        names = ["tokens", "labels", "weights"]
        if self.has_frontend:
            names.append("frontend_embed")
        return names

    # --- public: the jitted step ------------------------------------------
    def build(self) -> Callable:
        names = self._state_names()
        bnames = self._batch_names()
        in_specs = tuple(self._state_spec(n) for n in names) + \
            tuple(P(self.axes) for _ in bnames)
        out_specs = tuple(self._state_spec(n) for n in names) + (P(),)

        def wrapped(*args):
            outs = self._device_step(*args)
            # loss: every device computed its local Σ w·ce; reduce to the
            # true global loss for logging
            *state_out, loss = outs
            loss = jax.lax.psum(loss, self.axes)
            return tuple(state_out) + (loss,)

        sharded = shard_map_call(wrapped, self.mesh, in_specs, out_specs)

        def step(state: Dict[str, jax.Array],
                 batch: Dict[str, jax.Array]):
            args = tuple(state[n] for n in names) + \
                tuple(batch[n] for n in bnames)
            outs = sharded(*args)
            new_state = dict(zip(names, outs[:-1]))
            return new_state, outs[-1]

        return step

    def jit_step(self) -> Callable:
        # Profiler traces attribute device time by the op metadata (the
        # named scopes), which JAX's persistent compilation cache leaves
        # out of its key by default: an executable cached from the same
        # step with other scopes would be reused, stale names and all.
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        step = self.build()
        state_sh = self.state_shardings()
        batch_sh = self.batch_shardings()
        in_sh = ({k: state_sh[k] for k in self._state_names()},
                 {k: batch_sh[k] for k in self._batch_names()})
        out_sh = ({k: state_sh[k] for k in self._state_names()},
                  NamedSharding(self.mesh, P()))
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0,))
