"""Per-layer blocks: dense/MoE transformer blocks and Mamba2 blocks.

A *block* is the unit the layer stack scans over and the unit Cephalo wraps
as one FSDP unit.  Each block kind provides ``init`` and an ``apply`` that
works in three modes:

* ``train``   — full sequence, no cache;
* ``prefill`` — full sequence, returns fresh KV / SSM state for the cache;
* ``decode``  — one token against an existing cache shard.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, AttnKind
from repro.models.layers.attention import (AttnSpec, attention_apply,
                                           attention_init, decode_attend,
                                           merge_decode_partials, project,
                                           rotate)
from repro.models.layers.mlp import mlp_apply, mlp_init
from repro.models.layers.moe import moe_apply, moe_init
from repro.models.layers.norms import (layernorm_apply, layernorm_init,
                                       rmsnorm_apply, rmsnorm_init)
from repro.models.layers.ssd import (SSMSpec, ssd_apply, ssd_decode_step,
                                     ssd_init)


def norm_init(cfg: ArchConfig, d: int) -> dict:
    return layernorm_init(d) if cfg.norm_kind == "layernorm" \
        else rmsnorm_init(d)


def norm_apply(cfg: ArchConfig, params: dict, x: jax.Array) -> jax.Array:
    fn = layernorm_apply if cfg.norm_kind == "layernorm" else rmsnorm_apply
    return fn(params, x, eps=cfg.norm_eps)


def attn_spec(cfg: ArchConfig, local: bool) -> AttnSpec:
    if cfg.attn_kind == AttnKind.SLIDING:
        window = cfg.window
    elif cfg.attn_kind == AttnKind.LOCAL_GLOBAL and local:
        window = cfg.window
    else:
        window = 0
    return AttnSpec(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        causal=cfg.causal,
        window=window,
        softcap=cfg.logit_softcap,
        rope_theta=cfg.rope_theta,
        use_rope=not cfg.learned_pos,
        rope_fraction=cfg.rope_fraction,
        qkv_bias=cfg.qkv_bias,
    )


def ssm_spec(cfg: ArchConfig) -> SSMSpec:
    return SSMSpec(d_model=cfg.d_model, d_inner=cfg.d_inner,
                   n_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                   chunk=cfg.ssm_chunk, conv_width=cfg.ssm_conv_width)


# ---------------------------------------------------------------------------
# Dense / MoE transformer block
# ---------------------------------------------------------------------------

def dense_block_init(key: jax.Array, cfg: ArchConfig,
                     local: bool = False) -> dict:
    ka, km = jax.random.split(key)
    p = {
        "ln_attn": norm_init(cfg, cfg.d_model),
        "attn": attention_init(ka, cfg.d_model, attn_spec(cfg, local)),
        "ln_mlp": norm_init(cfg, cfg.d_model),
    }
    if cfg.is_moe:
        p["moe"] = moe_init(km, cfg.d_model, cfg.d_ff, cfg.n_experts)
    else:
        p["mlp"] = mlp_init(km, cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    if cfg.post_norm:
        p["ln_attn_post"] = norm_init(cfg, cfg.d_model)
        p["ln_mlp_post"] = norm_init(cfg, cfg.d_model)
    return p


@jax.named_scope("mlp")
def _ffn(params: dict, x: jax.Array, cfg: ArchConfig,
         dropless: bool = False):
    if cfg.is_moe:
        return moe_apply(params["moe"], x, top_k=cfg.experts_per_token,
                         dropless=dropless)
    return mlp_apply(params["mlp"], x, cfg.mlp_kind), jnp.float32(0.0)


def dense_block_apply(params: dict, x: jax.Array, cfg: ArchConfig,
                      positions: jax.Array, *, local: bool = False,
                      kv_cache: Optional[Tuple] = None,
                      return_kv: bool = False,
                      seq_shard_axis: Optional[str] = None,
                      dropless: bool = False):
    """Returns (y, aux_loss, new_kv_or_None).

    ``kv_cache = (k, v, kv_positions)`` → decode mode (x is one token).
    ``seq_shard_axis`` — mesh axis name for sequence-sharded decode merge.
    ``dropless`` — MoE eval dispatch with no capacity dropping (the
    serving paths pass True so decode matches a drop-free full forward).
    """
    spec = attn_spec(cfg, local)
    h = norm_apply(cfg, params["ln_attn"], x)
    new_kv = None
    if kv_cache is not None:
        # decode: project q from h, attend over the cache shard
        dtype = h.dtype
        q = rotate(project(params["attn"], h, "q", spec), positions[:, None],
                   spec)
        k_cache, v_cache, kv_pos = kv_cache
        wv, m, l = decode_attend(q, k_cache, v_cache, kv_pos, positions, spec)
        out = merge_decode_partials(wv, m, l, seq_shard_axis)
        attn_out = jnp.einsum("bshk,hkd->bsd", out.astype(dtype),
                              params["attn"]["wo"].astype(dtype))
    else:
        res = attention_apply(params["attn"], h, spec, positions,
                              return_kv=return_kv)
        if return_kv:
            attn_out, new_kv = res
        else:
            attn_out = res
    if cfg.post_norm:
        attn_out = norm_apply(cfg, params["ln_attn_post"], attn_out)
    x = x + attn_out
    h = norm_apply(cfg, params["ln_mlp"], x)
    ffn_out, aux = _ffn(params, h, cfg, dropless=dropless)
    if cfg.post_norm:
        ffn_out = norm_apply(cfg, params["ln_mlp_post"], ffn_out)
    return x + ffn_out, aux, new_kv


def decode_project_kv(params: dict, x: jax.Array, cfg: ArchConfig,
                      positions: jax.Array, local: bool = False):
    """Project this token's (k, v) for the cache write (decode mode)."""
    spec = attn_spec(cfg, local)
    h = norm_apply(cfg, params["ln_attn"], x)
    k = project(params["attn"], h, "k", spec)
    v = project(params["attn"], h, "v", spec)
    return rotate(k, positions[:, None], spec), v


# ---------------------------------------------------------------------------
# Mamba2 (SSM) block
# ---------------------------------------------------------------------------

def ssm_block_init(key: jax.Array, cfg: ArchConfig) -> dict:
    return {
        "ln": norm_init(cfg, cfg.d_model),
        "ssd": ssd_init(key, ssm_spec(cfg)),
    }


def ssm_block_apply(params: dict, x: jax.Array, cfg: ArchConfig,
                    state: Optional[Tuple[jax.Array, jax.Array]] = None,
                    decode: bool = False):
    """Returns (y, (ssm_state, conv_state))."""
    spec = ssm_spec(cfg)
    h = norm_apply(cfg, params["ln"], x)
    if decode:
        assert state is not None
        out, new_state = ssd_decode_step(params["ssd"], h, spec,
                                         state[0], state[1])
    else:
        h0, conv0 = state if state is not None else (None, None)
        out, new_state = ssd_apply(params["ssd"], h, spec, h0=h0,
                                   conv0=conv0)
    return x + out, new_state


def init_ssm_state(cfg: ArchConfig, batch: int, dtype) -> Tuple:
    spec = ssm_spec(cfg)
    h = jnp.zeros((batch, spec.heads, spec.head_dim, spec.n_state),
                  jnp.float32)
    conv = jnp.zeros((batch, spec.conv_width - 1, spec.conv_dim), dtype)
    return h, conv
