"""The model zoo's spine: config-driven decoder (or encoder) stacks.

An architecture compiles to a list of :class:`StageSpec`s — homogeneous
groups of blocks that are scanned over.  This keeps compile times flat in
depth, gives FSDP a natural "unit" granularity, and lets mixed stacks
(gemma2 local/global pairs, zamba2 mamba-groups + shared attention) keep
*static* per-block hyperparameters inside one scan.

Public API (all pure functions over a params pytree):

* :func:`init_params`
* :func:`loss_fn`             — training loss (chunked CE, aux losses)
* :func:`forward_hidden`      — activations for train/prefill
* :func:`prefill`             — build KV/SSM caches, return last logits
* :func:`decode_step`         — one-token serving step
* unit-level API for the Cephalo MPMD trainer (``unit_*``)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig, ArchType, AttnKind
from repro.models import blocks as B
from repro.models import kvcache as KV
from repro.models.layers.init_utils import dense_init, embed_init


# ---------------------------------------------------------------------------
# Stage compilation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageSpec:
    kind: str          # dense | pair | ssm | zamba
    count: int
    local: bool = False
    inner: int = 0     # zamba: mamba blocks per group


def build_stages(cfg: ArchConfig) -> List[StageSpec]:
    if cfg.is_ssm:
        return [StageSpec("ssm", cfg.n_layers)]
    if cfg.is_hybrid:
        groups = cfg.n_layers // cfg.hybrid_attn_every
        tail = cfg.n_layers - groups * cfg.hybrid_attn_every
        out = [StageSpec("zamba", groups, inner=cfg.hybrid_attn_every)]
        if tail:
            out.append(StageSpec("ssm", tail))
        return out
    if cfg.attn_kind == AttnKind.LOCAL_GLOBAL:
        pairs = cfg.n_layers // 2
        out = [StageSpec("pair", pairs)]
        if cfg.n_layers % 2:
            out.append(StageSpec("dense", 1, local=False))
        return out
    local = cfg.attn_kind == AttnKind.SLIDING
    return [StageSpec("dense", cfg.n_layers, local=local)]


def _stack(trees: Sequence[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _element_init(key: jax.Array, cfg: ArchConfig, spec: StageSpec) -> Any:
    if spec.kind == "dense":
        return B.dense_block_init(key, cfg, local=spec.local)
    if spec.kind == "pair":
        kl, kg = jax.random.split(key)
        return {"local": B.dense_block_init(kl, cfg, local=True),
                "global": B.dense_block_init(kg, cfg, local=False)}
    if spec.kind == "ssm":
        return B.ssm_block_init(key, cfg)
    if spec.kind == "zamba":
        keys = jax.random.split(key, spec.inner)
        return {"mamba": _stack([B.ssm_block_init(k, cfg) for k in keys])}
    raise ValueError(spec.kind)


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    keys = iter(jax.random.split(key, 64))
    params: Dict[str, Any] = {
        "embed": embed_init(next(keys), cfg.vocab_size, cfg.d_model),
        "final_norm": B.norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(next(keys), (cfg.d_model, cfg.vocab_size))
    if cfg.learned_pos:
        params["pos_embed"] = 0.02 * jax.random.normal(
            next(keys), (cfg.max_seq, cfg.d_model), jnp.float32)
    if cfg.frontend_dim:
        params["frontend_proj"] = dense_init(
            next(keys), (cfg.frontend_dim, cfg.d_model))
    if cfg.is_hybrid:
        params["shared"] = B.dense_block_init(next(keys), cfg, local=False)
    stages = []
    for spec in build_stages(cfg):
        # vmap over the element keys: the same draws as one call per
        # element, in one traced body however deep the stage is
        stages.append(jax.vmap(lambda k, s=spec: _element_init(k, cfg, s))(
            jax.random.split(next(keys), spec.count)))
    params["stages"] = stages
    return params


def param_count(params: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ArchConfig, params: Dict[str, Any], tokens: jax.Array,
                 positions: jax.Array,
                 frontend_embed: Optional[jax.Array] = None) -> jax.Array:
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = params["embed"][tokens].astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if frontend_embed is not None and "frontend_proj" in params:
        # Stubbed modality frontend: precomputed patch/frame embeddings are
        # projected and added (interleave handled by the data pipeline).
        x = x + (frontend_embed.astype(dtype)
                 @ params["frontend_proj"].astype(dtype))
    if cfg.learned_pos:
        x = x + params["pos_embed"].astype(dtype)[positions]
    return x


def head_logits(cfg: ArchConfig, params: Dict[str, Any],
                h: jax.Array) -> jax.Array:
    h = B.norm_apply(cfg, params["final_norm"], h)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    z = (h @ w.astype(h.dtype)).astype(jnp.float32)
    if cfg.final_softcap > 0:
        z = cfg.final_softcap * jnp.tanh(z / cfg.final_softcap)
    return z


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "offload":
        from jax.ad_checkpoint import checkpoint_policies as cp
        policy = cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["boundary"],
            offload_src="device", offload_dst="pinned_host")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def element_apply(cfg: ArchConfig, spec: StageSpec, bp: Any, x: jax.Array,
                  positions: jax.Array,
                  shared: Any = None,
                  dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Apply ONE stage element (= one Cephalo FSDP unit) to ``x``.

    Returns (y, aux).  ``shared`` is the zamba2 shared-block params.
    ``dropless`` selects the MoE drop-free eval dispatch (training keeps
    the capacity path).
    """
    if spec.kind == "dense":
        y, a, _ = B.dense_block_apply(bp, x, cfg, positions,
                                      local=spec.local, dropless=dropless)
        return y, a
    if spec.kind == "pair":
        y, a1, _ = B.dense_block_apply(bp["local"], x, cfg, positions,
                                       local=True, dropless=dropless)
        y, a2, _ = B.dense_block_apply(bp["global"], y, cfg, positions,
                                       local=False, dropless=dropless)
        return y, a1 + a2
    if spec.kind == "ssm":
        y, _ = B.ssm_block_apply(bp, x, cfg)
        return y, jnp.float32(0.0)
    if spec.kind == "zamba":
        # nested remat: without it the backward of a 6-block group keeps
        # every SSD intermediate live at once (36 GiB temp on the zamba2
        # train_4k dry-run → 12.7 GiB with it; §Perf "zamba-nested-remat")
        @jax.checkpoint
        def inner(xc, ip):
            xc, _ = B.ssm_block_apply(ip, xc, cfg)
            return xc, None
        y, _ = jax.lax.scan(inner, x, bp["mamba"])
        y, a, _ = B.dense_block_apply(shared, y, cfg, positions,
                                      local=False, dropless=dropless)
        return y, a
    raise ValueError(spec.kind)


def _stage_apply_train(cfg: ArchConfig, spec: StageSpec, stage_params: Any,
                       x: jax.Array, positions: jax.Array, aux: jax.Array,
                       shared: Any, remat: str,
                       dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    def body(carry, bp):
        x, aux = carry
        x = checkpoint_name(x, "boundary")
        y, a = element_apply(cfg, spec, bp, x, positions, shared,
                             dropless=dropless)
        return (y, aux + a), None

    (x, aux), _ = jax.lax.scan(_remat(body, remat), (x, aux), stage_params)
    return x, aux


def forward_hidden(cfg: ArchConfig, params: Dict[str, Any],
                   tokens: jax.Array,
                   frontend_embed: Optional[jax.Array] = None,
                   remat: str = "full",
                   dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (hidden, aux_loss).

    ``dropless=True`` is the eval-reference mode: MoE layers use the
    drop-free dispatch, making the result comparable to prefill/decode."""
    bsz, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                 (bsz, seq))
    x = embed_tokens(cfg, params, tokens, positions, frontend_embed)
    aux = jnp.float32(0.0)
    for spec, sp in zip(build_stages(cfg), params["stages"]):
        x, aux = _stage_apply_train(cfg, spec, sp, x, positions, aux,
                                    params.get("shared"), remat,
                                    dropless=dropless)
    return x, aux


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy)
# ---------------------------------------------------------------------------

@jax.named_scope("ce")
def chunked_ce(cfg: ArchConfig, params: Dict[str, Any], h: jax.Array,
               labels: jax.Array, weights: jax.Array,
               chunk: int = 512) -> jax.Array:
    """Σ_ij w_ij · CE_ij without materializing (B, S, V) logits.

    Scans over sequence chunks; with remat the backward recomputes each
    chunk's logits, bounding memory at O(B · chunk · V).
    """
    bsz, seq, d = h.shape
    chunk = min(chunk, seq)
    if seq % chunk != 0:
        pad = chunk - seq % chunk
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
        seq += pad
    n = seq // chunk
    hs = h.reshape(bsz, n, chunk, d).swapaxes(0, 1)
    ys = labels.reshape(bsz, n, chunk).swapaxes(0, 1)
    ws = weights.reshape(bsz, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(tot, inp):
        hc, yc, wc = inp
        z = head_logits(cfg, params, hc)                 # (B, C, V) f32
        lse = jax.nn.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, yc[..., None], axis=-1)[..., 0]
        ce = lse - picked
        return tot + jnp.sum(wc * ce), None

    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ys, ws))
    return tot


def loss_fn(cfg: ArchConfig, params: Dict[str, Any], batch: Dict[str, Any],
            remat: str = "full", ce_chunk: int = 512) -> Tuple[jax.Array, Dict]:
    """Weighted-sum CE + router aux.  ``batch["weights"]`` carries the
    Eq. 1 normalization (uniform 1/(B·S·) for homogeneous training)."""
    h, aux = forward_hidden(cfg, params, batch["tokens"],
                            batch.get("frontend_embed"), remat)
    ce = chunked_ce(cfg, params, h, batch["labels"], batch["weights"],
                    ce_chunk)
    total_w = jnp.maximum(jnp.sum(batch["weights"]), 1e-9)
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce_sum": ce, "aux": aux, "weight_sum": total_w}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def _cache_len(cfg: ArchConfig, local: bool, max_len: int) -> int:
    spec = B.attn_spec(cfg, local)
    return min(spec.window, max_len) if spec.window > 0 else max_len


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> List[Dict]:
    """Empty caches, one entry per stage."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    caches: List[Dict] = []
    for spec in build_stages(cfg):
        if spec.kind == "dense":
            cl = _cache_len(cfg, spec.local, max_len)
            caches.append(KV.init_kv(spec.count, batch, cl, cfg.n_kv_heads,
                                     cfg.head_dim, dtype))
        elif spec.kind == "pair":
            cl_l = _cache_len(cfg, True, max_len)
            cl_g = _cache_len(cfg, False, max_len)
            caches.append({
                "local": KV.init_kv(spec.count, batch, cl_l, cfg.n_kv_heads,
                                    cfg.head_dim, dtype),
                "global": KV.init_kv(spec.count, batch, cl_g,
                                     cfg.n_kv_heads, cfg.head_dim, dtype)})
        elif spec.kind == "ssm":
            h, conv = B.init_ssm_state(cfg, batch, dtype)
            caches.append({
                "h": jnp.broadcast_to(h, (spec.count,) + h.shape).copy(),
                "conv": jnp.broadcast_to(
                    conv, (spec.count,) + conv.shape).copy()})
        elif spec.kind == "zamba":
            h, conv = B.init_ssm_state(cfg, batch, dtype)
            cl = _cache_len(cfg, False, max_len)
            caches.append({
                "h": jnp.broadcast_to(
                    h, (spec.count, spec.inner) + h.shape).copy(),
                "conv": jnp.broadcast_to(
                    conv, (spec.count, spec.inner) + conv.shape).copy(),
                "attn": KV.init_kv(spec.count, batch, cl, cfg.n_kv_heads,
                                   cfg.head_dim, dtype)})
    return caches


def prefill(cfg: ArchConfig, params: Dict[str, Any], tokens: jax.Array,
            max_len: int,
            frontend_embed: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, List[Dict]]:
    """Run the full prompt, build caches.  Returns (last-token logits,
    caches)."""
    bsz, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None],
                                 (bsz, seq))
    x = embed_tokens(cfg, params, tokens, positions, frontend_embed)
    caches: List[Dict] = []
    for spec, sp in zip(build_stages(cfg), params["stages"]):
        if spec.kind == "dense":
            cl = _cache_len(cfg, spec.local, max_len)

            def body(xc, bp, _cl=cl, _local=spec.local):
                y, _, kv = B.dense_block_apply(bp, xc, cfg, positions,
                                               local=_local, return_kv=True,
                                               dropless=True)
                c = KV.fill_kv_from_prefill(
                    kv[0], kv[1], positions, _cl,
                    window=B.attn_spec(cfg, _local).window)
                return y, c

            x, cache = jax.lax.scan(body, x, sp)
            caches.append(cache)
        elif spec.kind == "pair":
            cl_l = _cache_len(cfg, True, max_len)
            cl_g = _cache_len(cfg, False, max_len)

            def body(xc, bp):
                y, _, kvl = B.dense_block_apply(bp["local"], xc, cfg,
                                                positions, local=True,
                                                return_kv=True,
                                                dropless=True)
                y, _, kvg = B.dense_block_apply(bp["global"], y, cfg,
                                                positions, local=False,
                                                return_kv=True,
                                                dropless=True)
                cl_ = KV.fill_kv_from_prefill(kvl[0], kvl[1], positions,
                                              cl_l, window=cfg.window)
                cg_ = KV.fill_kv_from_prefill(kvg[0], kvg[1], positions,
                                              cl_g, window=0)
                return y, {"local": cl_, "global": cg_}

            x, cache = jax.lax.scan(body, x, sp)
            caches.append(cache)
        elif spec.kind == "ssm":
            def body(xc, bp):
                y, st = B.ssm_block_apply(bp, xc, cfg)
                return y, st
            x, states = jax.lax.scan(body, x, sp)
            caches.append({"h": states[0], "conv": states[1]})
        elif spec.kind == "zamba":
            cl = _cache_len(cfg, False, max_len)

            def body(xc, bp):
                def inner(xi, ip):
                    yi, st = B.ssm_block_apply(ip, xi, cfg)
                    return yi, st
                xc, states = jax.lax.scan(inner, xc, bp["mamba"])
                xc, _, kv = B.dense_block_apply(params["shared"], xc, cfg,
                                                positions, local=False,
                                                return_kv=True,
                                                dropless=True)
                c = KV.fill_kv_from_prefill(kv[0], kv[1], positions, cl,
                                            window=0)
                return xc, {"h": states[0], "conv": states[1], "attn": c}

            x, cache = jax.lax.scan(body, x, sp)
            caches.append(cache)
    logits = head_logits(cfg, params, x[:, -1:])
    return logits, caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_step(cfg: ArchConfig, params: Dict[str, Any],
                caches: List[Dict], tokens: jax.Array,
                positions: jax.Array,
                shard_start: int = 0,
                seq_shard_axis: Optional[str] = None,
                cache_total: Optional[Dict[str, int]] = None,
                ) -> Tuple[jax.Array, List[Dict]]:
    """One serving step: ``tokens`` (B, 1) at absolute ``positions`` (B,).

    With ``seq_shard_axis`` the KV caches are sequence-sharded across that
    mesh axis; this function then runs *inside* shard_map and merges
    attention partials with the LSE trick.  ``cache_total`` maps cache
    group → global cache length (defaults to the local shard length).
    """
    x = embed_tokens(cfg, params, tokens, positions[:, None])
    new_caches: List[Dict] = []

    def attend_dense(bp, xc, cache, local, total):
        k_new, v_new = B.decode_project_kv(bp, xc, cfg, positions,
                                           local=local)
        kc, vc, pos_arr = KV.write_kv(
            cache["k"], cache["v"], cache["pos"], k_new, v_new, positions,
            cache_total=total, shard_start=shard_start)
        y, _, _ = B.dense_block_apply(
            bp, xc, cfg, positions, local=local,
            kv_cache=(kc, vc, pos_arr), seq_shard_axis=seq_shard_axis,
            dropless=True)
        return y, {"k": kc, "v": vc, "pos": pos_arr}

    def group_total(cache, key):
        return (cache_total or {}).get(key, cache["k"].shape[-3])

    # Layer caches are carried as FULL stacks and updated in place with
    # dynamic_update_index: scanning them as xs/ys double-buffers the
    # whole KV cache (measured ~2.3x cache bytes of temp on the 32k
    # decode dry-runs; EXPERIMENTS.md §Perf iteration "decode-inplace").
    def _idx(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                   keepdims=False), tree)

    def _upd(tree, new, i):
        return jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
            tree, new)

    for spec, sp, cache in zip(build_stages(cfg), params["stages"], caches):
        idxs = jnp.arange(spec.count)
        if spec.kind == "dense":
            tot = group_total(cache, "k")

            def body(carry, inp, _local=spec.local, _tot=tot):
                xc, full = carry
                bp, i = inp
                y, nc = attend_dense(bp, xc, _idx(full, i), _local, _tot)
                return (y, _upd(full, nc, i)), None

            (x, full), _ = jax.lax.scan(body, (x, cache), (sp, idxs))
            new_caches.append(full)
        elif spec.kind == "pair":
            tot_l = group_total(cache["local"], "local")
            tot_g = group_total(cache["global"], "global")

            def body(carry, inp):
                xc, full = carry
                bp, i = inp
                y, ncl = attend_dense(bp["local"], xc,
                                      _idx(full["local"], i), True, tot_l)
                y, ncg = attend_dense(bp["global"], y,
                                      _idx(full["global"], i), False,
                                      tot_g)
                full = {"local": _upd(full["local"], ncl, i),
                        "global": _upd(full["global"], ncg, i)}
                return (y, full), None

            (x, full), _ = jax.lax.scan(body, (x, cache), (sp, idxs))
            new_caches.append(full)
        elif spec.kind == "ssm":
            def body(carry, inp):
                xc, full = carry
                bp, i = inp
                st = _idx(full, i)
                y, new_st = B.ssm_block_apply(
                    bp, xc, cfg, state=(st["h"], st["conv"]), decode=True)
                full = _upd(full, {"h": new_st[0], "conv": new_st[1]}, i)
                return (y, full), None

            (x, full), _ = jax.lax.scan(body, (x, cache), (sp, idxs))
            new_caches.append(full)
        elif spec.kind == "zamba":
            tot_a = group_total(cache["attn"], "attn")

            def body(carry, inp):
                xc, full = carry
                bp, i = inp
                st = _idx({"h": full["h"], "conv": full["conv"]}, i)

                def inner(xi, ip):
                    blkp, h, conv = ip
                    yi, s = B.ssm_block_apply(blkp, xi, cfg,
                                              state=(h, conv), decode=True)
                    return yi, s
                xc, states = jax.lax.scan(
                    inner, xc, (bp["mamba"], st["h"], st["conv"]))
                y, nc = attend_dense(params["shared"], xc,
                                     _idx(full["attn"], i), False, tot_a)
                full = {"h": _upd(full["h"], states[0], i),
                        "conv": _upd(full["conv"], states[1], i),
                        "attn": _upd(full["attn"], nc, i)}
                return (y, full), None

            (x, full), _ = jax.lax.scan(body, (x, cache), (sp, idxs))
            new_caches.append(full)
    logits = head_logits(cfg, params, x)
    return logits, new_caches
