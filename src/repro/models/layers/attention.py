"""Multi-head attention with GQA/MQA, sliding windows, and logit softcaps.

Three compute paths, all numerically interchangeable:

* ``dense``      — naive O(S^2) scores; used for short sequences and as the
                   oracle for everything else;
* ``blockwise``  — flash-style online-softmax scan over KV blocks in pure
                   jnp; bounds activation memory for 32k+ sequences;
* Pallas kernel  — :mod:`repro.kernels.flash_attention` (TPU target,
                   validated in interpret mode against ``dense``).

Layout convention: activations ``(B, S, D)``, heads ``(B, S, H, hd)``,
KV cache ``(B, S_max, KV, hd)``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.init_utils import dense_init
from repro.models.layers.rope import apply_rope

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static attention hyperparameters for one layer."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    window: int = 0            # 0 = full attention
    softcap: float = 0.0
    rope_theta: float = 10_000.0
    use_rope: bool = True      # encoders use learned/absolute positions
    rope_fraction: float = 1.0  # share of each head's dims rotated
    qkv_bias: bool = False     # q/k/v projections carry a bias
    query_scale: float = 0.0   # 0 → 1/sqrt(head_dim)

    @property
    def scale(self) -> float:
        return self.query_scale or self.head_dim ** -0.5

    @property
    def rotary_dims(self) -> int:
        return int(self.rope_fraction * self.head_dim)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


def attention_init(key: jax.Array, d_model: int, spec: AttnSpec) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    hd = spec.head_dim
    p = {
        "wq": dense_init(kq, (d_model, spec.n_heads, hd), fan_in=d_model),
        "wk": dense_init(kk, (d_model, spec.n_kv_heads, hd), fan_in=d_model),
        "wv": dense_init(kv, (d_model, spec.n_kv_heads, hd), fan_in=d_model),
        "wo": dense_init(ko, (spec.n_heads, hd, d_model),
                         fan_in=spec.n_heads * hd),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((spec.n_heads, hd), jnp.float32)
        p["bk"] = jnp.zeros((spec.n_kv_heads, hd), jnp.float32)
        p["bv"] = jnp.zeros((spec.n_kv_heads, hd), jnp.float32)
    return p


def project(params: dict, x: jax.Array, name: str,
            spec: AttnSpec) -> jax.Array:
    """``x`` (B, S, D) through the ``name`` ("q", "k" or "v") projection
    to (B, S, heads, hd), plus its bias where the spec has one."""
    dtype = x.dtype
    y = jnp.einsum("bsd,dhk->bshk", x, params["w" + name].astype(dtype))
    if spec.qkv_bias:
        y = y + params["b" + name].astype(dtype)
    return y


def rotate(x: jax.Array, positions: jax.Array, spec: AttnSpec) -> jax.Array:
    """Rotary embedding of q or k by ``positions`` (B, S), over the
    spec's rotary dims; identity where the spec uses no rotary."""
    if not spec.use_rope:
        return x
    return apply_rope(x, positions, spec.rope_theta, spec.rotary_dims)


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


def _expand_kv(x: jax.Array, q_per_kv: int) -> jax.Array:
    """(B, S, KV, hd) → (B, S, KV*q_per_kv, hd) by repetition."""
    if q_per_kv == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, s, kv, q_per_kv, hd)).reshape(
        b, s, kv * q_per_kv, hd)


# ---------------------------------------------------------------------------
# Dense (oracle) path
# ---------------------------------------------------------------------------

def _group_q(q: jax.Array, q_per_kv: int) -> jax.Array:
    """(B, S, H, hd) → (B, S, KV, G, hd): GQA-grouped query layout so the
    KV tensors are never materially expanded (a 7x activation saving for
    yi-34b-style 56q/8kv)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, h // q_per_kv, q_per_kv, hd)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    spec: AttnSpec,
                    q_positions: jax.Array,
                    kv_positions: jax.Array) -> jax.Array:
    """q: (B, Sq, H, hd);  k, v: (B, Sk, KV, hd);  positions: (B, S*)."""
    b, sq, h, hd = q.shape
    qg = _group_q(q, spec.q_per_kv)
    logits = jnp.einsum("bqcgd,bkcd->bcgqk", qg, k,
                        preferred_element_type=jnp.float32) * spec.scale
    logits = _softcap(logits, spec.softcap)
    qp = q_positions[:, None, None, :, None]
    kp = kv_positions[:, None, None, None, :]
    mask = kp >= 0
    if spec.causal:
        mask &= kp <= qp
    if spec.window > 0:
        mask &= qp - kp < spec.window
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bcgqk,bkcd->bqcgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) path
# ---------------------------------------------------------------------------

def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        spec: AttnSpec,
                        q_positions: jax.Array,
                        kv_positions: jax.Array,
                        block_kv: int = 1024,
                        block_q: int = 4096) -> jax.Array:
    """Online-softmax scan over KV blocks, outer-blocked over Q.
    Memory: O(block_q * block_kv) logits — both dims must be tiled at 32k+
    sequence lengths (an un-blocked Q materializes Sq x block_kv logits:
    8.6 GiB/layer on the mixtral prefill dry-run)."""
    b, sq, h, hd = q.shape
    if sq > block_q and sq % block_q == 0:
        nq = sq // block_q
        qb = q.reshape(b, nq, block_q, h, hd).swapaxes(0, 1)
        pb = q_positions.reshape(b, nq, block_q).swapaxes(0, 1)

        def one(args):
            qi, pi = args
            return blockwise_attention(qi, k, v, spec, pi, kv_positions,
                                       block_kv, block_q)

        out = jax.lax.map(one, (qb, pb))
        return out.swapaxes(0, 1).reshape(b, sq, h, hd)
    sk = k.shape[1]
    if sk % block_kv != 0:
        pad = block_kv - sk % block_kv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-1)
        sk += pad
    nblk = sk // block_kv
    kvh = k.shape[2]
    g = spec.q_per_kv
    k = k.reshape(b, nblk, block_kv, kvh, hd)
    v = v.reshape(b, nblk, block_kv, kvh, hd)
    kp = kv_positions.reshape(b, nblk, block_kv)
    qg = _group_q(q, g).astype(jnp.float32)      # (B, Sq, KV, G, hd)

    def step(carry, blk):
        acc, m, l = carry
        kb, vb, kpb = blk
        logits = jnp.einsum("bqcgd,bkcd->bcgqk", qg,
                            kb.astype(jnp.float32)) * spec.scale
        logits = _softcap(logits, spec.softcap)
        qp = q_positions[:, None, None, :, None]
        kpb_ = kpb[:, None, None, None, :]
        mask = kpb_ >= 0
        if spec.causal:
            mask &= kpb_ <= qp
        if spec.window > 0:
            mask &= qp - kpb_ < spec.window
        logits = jnp.where(mask, logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bcgqk,bkcd->bcgqd", p, vb.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, kvh, g, sq, hd), jnp.float32)
    m0 = jnp.full((b, kvh, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (k.swapaxes(0, 1), v.swapaxes(0, 1), kp.swapaxes(0, 1)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, KV, G, Sq, hd)
    return out.reshape(b, h, sq, hd).swapaxes(1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single new token against a cache)
# ---------------------------------------------------------------------------

def decode_attend(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                  cache_positions: jax.Array, q_positions: jax.Array,
                  spec: AttnSpec,
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial attention for one query token over a (shard of a) cache.

    Returns ``(weighted_values, lse_max, lse_sum)`` so shards can be merged
    with the log-sum-exp trick (sequence-sharded decode, DESIGN.md §5):
    ``merge = Σ_s exp(m_s - m*) * wv_s / Σ_s exp(m_s - m*) * l_s``.

    q: (B, 1, H, hd);  cache: (B, S, KV, hd);  cache_positions: (B, S).
    """
    b, sq, h, hd = q.shape
    qg = _group_q(q, spec.q_per_kv).astype(jnp.float32)
    logits = jnp.einsum("bqcgd,bkcd->bcgqk", qg,
                        cache_k.astype(jnp.float32)) * spec.scale
    logits = _softcap(logits, spec.softcap)
    qp = q_positions[:, None, None, None, None]
    kp = cache_positions[:, None, None, None, :]
    mask = (kp >= 0) & (kp <= qp)
    if spec.window > 0:
        mask &= qp - kp < spec.window
    logits = jnp.where(mask, logits, _NEG_INF)   # (B, KV, G, 1, S)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    wv = jnp.einsum("bcgqk,bkcd->bcgqd", p,
                    cache_v.astype(jnp.float32))
    return (wv.reshape(b, h, sq, hd), m.reshape(b, h, sq),
            l.reshape(b, h, sq))


def merge_decode_partials(wv: jax.Array, m: jax.Array, l: jax.Array,
                          axis_name: Optional[str] = None) -> jax.Array:
    """Merge per-shard decode partials; with ``axis_name`` the merge runs
    across a mesh axis (sequence-sharded KV), else it is a no-op merge."""
    if axis_name is not None:
        m_glob = jax.lax.pmax(m, axis_name)
        scale = jnp.exp(m - m_glob)
        wv = jax.lax.psum(wv * scale[..., None], axis_name)
        l = jax.lax.psum(l * scale, axis_name)
    out = wv / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2)   # (B, 1, H, hd)


# ---------------------------------------------------------------------------
# Full layer application
# ---------------------------------------------------------------------------

def _kernel_mode() -> str:
    """Pallas kernel opt-in: REPRO_USE_PALLAS = off | interpret | tpu.

    'interpret' runs the TPU kernel body in the Pallas interpreter (CPU
    validation); 'tpu' compiles it natively.  Requires contiguous
    0..S-1 positions (train/prefill), which is when the kernel applies.
    """
    import os
    return os.environ.get("REPRO_USE_PALLAS", "off")


def _pallas_attention(q, k, v, spec: AttnSpec, interpret: bool):
    from repro.kernels.flash_attention.ops import flash_attention
    # kernel layout (B, H, S, D)
    out = flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=spec.causal, window=spec.window, softcap=spec.softcap,
        interpret=interpret)
    return out.swapaxes(1, 2)


@jax.named_scope("attention")
def attention_apply(params: dict, x: jax.Array, spec: AttnSpec,
                    positions: jax.Array,
                    kv_override: Optional[Tuple[jax.Array, jax.Array,
                                                jax.Array]] = None,
                    return_kv: bool = False,
                    blockwise_threshold: int = 2048,
                    force_blockwise: bool = False):
    """Self-attention over ``x`` (B, S, D).

    ``kv_override = (k, v, kv_positions)`` switches to cross-cache mode
    (decode).  ``return_kv`` also returns the fresh (k, v) for cache fills.
    """
    dtype = x.dtype
    q = rotate(project(params, x, "q", spec), positions, spec)
    if kv_override is None:
        k = project(params, x, "k", spec)
        v = project(params, x, "v", spec)
        k = rotate(k, positions, spec)
        kv_positions = positions
    else:
        k, v, kv_positions = kv_override
    sq = x.shape[1]
    mode = _kernel_mode()
    if mode != "off" and kv_override is None:
        out = _pallas_attention(q, k, v, spec,
                                interpret=(mode == "interpret"))
    elif force_blockwise or sq > blockwise_threshold or \
            k.shape[1] > blockwise_threshold:
        out = blockwise_attention(q, k, v, spec, positions, kv_positions)
    else:
        out = dense_attention(q, k, v, spec, positions, kv_positions)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(dtype),
                   params["wo"].astype(dtype))
    if return_kv:
        return y, (k, v)
    return y
