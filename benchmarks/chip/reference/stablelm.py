"""Plain reference of StableLM 2 as published (stablelm-2-1.6b).

Per layer, in float32:

    h = layernorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k) on the leading partial_rotary_factor x head
           dims of each head; the rest of the head passes unrotated
    x = x + softmax(causal(q k^T / sqrt(d_head))) v Wo
    h = layernorm(x);  x = x + (silu(h Wgate) * (h Wup)) Wdown

then a final LayerNorm and the untied head.  LayerNorm has a scale and a
bias; heads are plain multi-head attention (as many key/value heads as
query heads, as the configuration states); attention runs one row of the
batch at a time so that one layer's S x S logits stay small.  The
residual is sequential (``use_parallel_residual`` false) and q and k get
no norm of their own (``qk_layernorm`` false).

The parameter tree is the one the benchmark hands to the program:
``embed``, ``final_norm``, ``head`` and one ``stages`` entry whose leaves
are stacked over the layers.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference import common as C

F32 = jnp.float32
#: standard deviation of the drawn biases (q/k/v and LayerNorm)
BIAS_STD = 0.1


def _dims(cfg: Dict[str, Any]):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != h:
        raise ValueError("the stablelm reference is multi-head attention; "
                         f"this config has {cfg['num_key_value_heads']} "
                         f"key/value heads for {h} query heads")
    if cfg["use_parallel_residual"] or cfg["qk_layernorm"] \
            or cfg["hidden_act"] != "silu":
        raise ValueError("the stablelm reference is the sequential residual "
                         "with SiLU and no q/k norm")
    hd = d // h
    return d, h, hd, int(cfg["partial_rotary_factor"] * hd), \
        cfg["intermediate_size"], cfg["num_hidden_layers"]


def program_sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ArchConfig`` fields that the configuration fixes,
    beside depth, width and vocabulary: multi-head attention with heads of
    hidden_size / num_attention_heads, LayerNorm, the rotary fraction and
    the q/k/v bias."""
    return {"d_ff": cfg["intermediate_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["layer_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "norm_kind": "layernorm",
            "rope_fraction": cfg["partial_rotary_factor"],
            "qkv_bias": cfg["use_qkv_bias"]}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from ``key``: embeddings N(0, 1), projections truncated
    normal with std 1/sqrt(fan in), LayerNorm scales 1, and every bias
    (q/k/v and LayerNorm) N(0, BIAS_STD^2), so that each bias moves the
    loss (the published initialisation, zeros, would leave the forward's
    bias terms unexercised)."""
    d, h, hd, _, ff, n = _dims(cfg)
    v = cfg["vocab_size"]
    k = iter(jax.random.split(key, 24))
    fin = C.fan_in_normal
    bias = lambda *s: BIAS_STD * jax.random.normal(next(k), s, F32)
    norm = lambda *s: {"bias": bias(*s), "scale": jnp.ones(s, F32)}
    layer = {
        "attn": {"bk": bias(n, h, hd), "bq": bias(n, h, hd),
                 "bv": bias(n, h, hd),
                 "wk": fin(next(k), (n, d, h, hd), d),
                 "wo": fin(next(k), (n, h, hd, d), h * hd),
                 "wq": fin(next(k), (n, d, h, hd), d),
                 "wv": fin(next(k), (n, d, h, hd), d)},
        "ln_attn": norm(n, d),
        "ln_mlp": norm(n, d),
        "mlp": {"w_down": fin(next(k), (n, ff, d), ff),
                "w_gate": fin(next(k), (n, d, ff), d),
                "w_up": fin(next(k), (n, d, ff), d)},
    }
    return {"embed": jax.random.normal(next(k), (v, d), F32),
            "final_norm": norm(d),
            "head": fin(next(k), (d, v), d),
            "stages": [layer]}


def layernorm(x: jax.Array, w: Dict[str, jax.Array], eps: float
              ) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]


def partial_rope(x: jax.Array, rotary_dims: int, theta: float
                 ) -> jax.Array:
    """Rotary embedding of the leading ``rotary_dims`` of each head, with
    the frequencies and the rotate-half pairing of a head that size; the
    rest of the head unchanged.  x: (B, S, H, hd)."""
    return jnp.concatenate([C.rope(x[..., :rotary_dims], theta),
                            x[..., rotary_dims:]], -1)


def make_loss(cfg: Dict[str, Any], prec: C.Precision):
    """``loss(params, tokens, labels, weights)``: the weighted CE sum."""
    d, h, hd, rd, ff, n = _dims(cfg)
    eps, theta = cfg["layer_norm_eps"], cfg["rope_theta"]
    mm = prec.einsum

    def attention(q, k, v):                      # one row: (S, H, hd)
        s = q.shape[0]
        logits = mm("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        return mm("hqk,khd->qhd", probs, v)

    @jax.checkpoint
    def layer(x, w):
        a = layernorm(x, w["ln_attn"], eps)
        att = w["attn"]
        q = mm("bsd,dhk->bshk", a, att["wq"]) + att["bq"]
        k = mm("bsd,dhk->bshk", a, att["wk"]) + att["bk"]
        v = mm("bsd,dhk->bshk", a, att["wv"]) + att["bv"]
        q, k = partial_rope(q, rd, theta), partial_rope(k, rd, theta)
        o = jax.lax.map(lambda qkv: attention(*qkv), (q, k, v))
        x = x + mm("bshk,hkd->bsd", o, att["wo"])
        m = layernorm(x, w["ln_mlp"], eps)
        gate = mm("bsd,df->bsf", m, w["mlp"]["w_gate"])
        up = mm("bsd,df->bsf", m, w["mlp"]["w_up"])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   w["mlp"]["w_down"])
        return x, None

    def ce_sum(h, params, labels, weights, rows=512):
        """C.ce_sum with the final LayerNorm in place of RMSNorm: ``rows``
        positions at a time, so that no (B, S, V) block of logits is ever
        whole."""
        b, s, _ = h.shape
        rows = min(rows, b * s)
        blocks = (h.reshape(-1, rows, d), labels.reshape(-1, rows),
                  weights.reshape(-1, rows))

        @jax.checkpoint
        def block(tot, inp):
            hc, yc, wc = inp
            z = mm("rd,dv->rv", layernorm(hc, params["final_norm"], eps),
                   params["head"])
            lse = jax.nn.logsumexp(z, axis=-1)
            picked = jnp.take_along_axis(z, yc[:, None], axis=-1)[:, 0]
            return tot + jnp.sum(wc * (lse - picked)), None

        tot, _ = jax.lax.scan(block, jnp.zeros((), F32), blocks)
        return tot

    def loss(params, tokens, labels, weights):
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, params["stages"][0])
        return ce_sum(x, params, labels, weights)

    return loss
