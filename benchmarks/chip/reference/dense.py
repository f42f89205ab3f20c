"""Plain reference of the dense decoder (stablelm-1.6b and its cuts).

Per layer, in float32:

    h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv;  q, k = rope(q), rope(k)
    x = x + softmax(causal(q k^T / sqrt(d_head))) v Wo
    h = rmsnorm(x);  x = x + (silu(h Wgate) * (h Wup)) Wdown

then a final RMSNorm and the untied head.  Heads are plain multi-head
attention (as many key/value heads as query heads, as the configuration
states); attention runs one row of the batch at a time so that one
layer's S x S logits stay small.

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


def _dims(cfg: Dict[str, Any]):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != h:
        raise ValueError("the dense reference is multi-head attention; "
                         f"this config has {cfg['num_key_value_heads']} "
                         f"key/value heads for {h} query heads")
    return d, h, d // h, cfg["intermediate_size"], cfg["num_hidden_layers"]


def program_sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ArchConfig`` fields that the configuration fixes,
    beside depth, width and vocabulary: multi-head attention with heads of
    hidden_size / num_attention_heads."""
    return {"d_ff": cfg["intermediate_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"]}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from ``key``: embeddings N(0, 1), projections truncated
    normal with std 1/sqrt(fan in), norm weights 0 (a scale of 1)."""
    d, h, hd, ff, n = _dims(cfg)
    v = cfg["vocab_size"]
    k = iter(jax.random.split(key, 16))
    fin = C.fan_in_normal
    zeros = lambda *s: jnp.zeros(s, F32)
    layer = {
        "attn": {"wq": fin(next(k), (n, d, h, hd), d),
                 "wk": fin(next(k), (n, d, h, hd), d),
                 "wv": fin(next(k), (n, d, h, hd), d),
                 "wo": fin(next(k), (n, h, hd, d), h * hd)},
        "ln_attn": {"scale": zeros(n, d)},
        "ln_mlp": {"scale": zeros(n, d)},
        "mlp": {"w_down": fin(next(k), (n, ff, d), ff),
                "w_gate": fin(next(k), (n, d, ff), d),
                "w_up": fin(next(k), (n, d, ff), d)},
    }
    return {"embed": jax.random.normal(next(k), (v, d), F32),
            "final_norm": {"scale": zeros(d)},
            "head": fin(next(k), (d, v), d),
            "stages": [layer]}


def make_loss(cfg: Dict[str, Any], prec: C.Precision):
    """``loss(params, tokens, labels, weights)``: the weighted CE sum."""
    d, h, hd, ff, n = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mm = prec.einsum

    def attention(q, k, v):                      # one row: (S, H, hd)
        s = q.shape[0]
        logits = mm("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        return mm("hqk,khd->qhd", probs, v)

    @jax.checkpoint
    def layer(x, w):
        a = C.rmsnorm(x, w["ln_attn"]["scale"], eps)
        q = C.rope(mm("bsd,dhk->bshk", a, w["attn"]["wq"]), theta)
        k = C.rope(mm("bsd,dhk->bshk", a, w["attn"]["wk"]), theta)
        v = mm("bsd,dhk->bshk", a, w["attn"]["wv"])
        o = jax.lax.map(lambda qkv: attention(*qkv), (q, k, v))
        x = x + mm("bshk,hkd->bsd", o, w["attn"]["wo"])
        m = C.rmsnorm(x, w["ln_mlp"]["scale"], eps)
        gate = mm("bsd,df->bsf", m, w["mlp"]["w_gate"])
        up = mm("bsd,df->bsf", m, w["mlp"]["w_up"])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   w["mlp"]["w_down"])
        return x, None

    def loss(params, tokens, labels, weights):
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, params["stages"][0])
        return C.ce_sum(prec, x, params["head"],
                        params["final_norm"]["scale"], eps, labels, weights)

    return loss
