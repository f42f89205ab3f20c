"""Plain reference of the Mamba2 (SSD) model, in float32.

Per layer (arXiv:2405.21060, one group of B and C shared by the heads):

    h = rmsnorm(x);  [z | x' | B | C | dt] = h W_in
    [x' | B | C] = silu(causal depthwise conv, width 4, of [x' | B | C])
    dt = softplus(dt + dt_bias);  a = -exp(a_log)
    y = SSD(x' dt, a dt, B, C) + d_skip x'
    x = x + rmsnorm(y * silu(z)) W_out

The SSD is the block decomposition of the paper's listing ("ssd_minimal"):
within a chunk the masked decay matrix exp(segsum(a dt)); across chunks
the states, passed on by the decays between chunk ends, also as one
segsum matrix (no scan).  Then a final RMSNorm and the untied head.

The gated norm's epsilon is 1e-6 and the other norms' the configuration's
``rms_norm_eps`` (see the configuration's ``assumed``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference import common as C

F32 = jnp.float32
GATE_EPS = 1e-6


def _dims(cfg: Dict[str, Any]):
    d = cfg["hidden_size"]
    di = cfg["expand"] * d
    n, p = cfg["state_size"], cfg["head_dim"]
    return d, di, n, p, di // p, cfg["conv_kernel"], cfg["num_hidden_layers"]


def program_sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program's ``ArchConfig`` fields that the configuration fixes,
    beside depth, width and vocabulary."""
    return {"ssm_state": cfg["state_size"], "ssm_expand": cfg["expand"],
            "ssm_head_dim": cfg["head_dim"],
            "ssm_chunk": cfg["chunk_size"],
            "ssm_conv_width": cfg["conv_kernel"],
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_embeddings"]}


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from ``key``: projections truncated normal with std
    1/sqrt(fan in), dt_bias uniform in [-4, -1], a_log = log(1..16) over
    the heads, d_skip 1, norm weights and conv bias 0."""
    d, di, n, p, h, w, layers = _dims(cfg)
    v = cfg["vocab_size"]
    cd = di + 2 * n
    k = iter(jax.random.split(key, 8))
    fin = C.fan_in_normal
    zeros = lambda *s: jnp.zeros(s, F32)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=F32))
    layer = {
        "ln": {"scale": zeros(layers, d)},
        "ssd": {
            "a_log": jnp.broadcast_to(a_log, (layers, h)),
            "conv_b": zeros(layers, cd),
            "conv_w": fin(next(k), (layers, w, cd), w),
            "d_skip": jnp.ones((layers, h), F32),
            "dt_bias": jax.random.uniform(next(k), (layers, h), F32,
                                          -4.0, -1.0),
            "gate_norm": {"scale": zeros(layers, di)},
            "in_proj": fin(next(k), (layers, d, 2 * di + 2 * n + h), d),
            "out_proj": fin(next(k), (layers, di, d), di),
        },
    }
    return {"embed": jax.random.normal(next(k), (v, d), F32),
            "final_norm": {"scale": zeros(d)},
            "head": fin(next(k), (d, v), d),
            "stages": [layer]}


def segsum(x: jax.Array) -> jax.Array:
    """(..., T) -> (..., T, T): out[i, j] = x[j+1] + ... + x[i] for
    i >= j, and -inf above the diagonal."""
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (t,))
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xx, 0.0)
    s = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)


def ssd(prec: C.Precision, x, a, b, c, chunk: int):
    """x: (B, L, H, P) already times dt; a: (B, L, H) = a dt; b, c:
    (B, L, N).  Returns y: (B, L, H, P) from a zero initial state."""
    bs, l, h, p = x.shape
    nc = l // chunk
    x = x.reshape(bs, nc, chunk, h, p)
    b = b.reshape(bs, nc, chunk, -1)
    c = c.reshape(bs, nc, chunk, -1)
    a = a.reshape(bs, nc, chunk, h).transpose(0, 3, 1, 2)   # (B, H, C, L)
    a_cum = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(segsum(a))                               # (B,H,C,L,L)
    cb = prec.einsum("bcln,bcsn->bcls", c, b)
    y_diag = prec.einsum("bcls,bhcls,bcshp->bclhp", cb, decay, x)
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)                 # (B,H,C,L)
    states = prec.einsum("bcln,bhcl,bclhp->bchpn", b, to_end, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    between = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                      (1, 0)))))
    states = prec.einsum("bhzc,bchpn->bzhpn", between, states)[:, :-1]
    y_off = prec.einsum("bcln,bchpn,bhcl->bclhp", c, states,
                        jnp.exp(a_cum))
    return (y_diag + y_off).reshape(bs, l, h, p)


def make_loss(cfg: Dict[str, Any], prec: C.Precision):
    """``loss(params, tokens, labels, weights)``: the weighted CE sum."""
    d, di, n, p, h, w, layers = _dims(cfg)
    eps, chunk = cfg["rms_norm_eps"], cfg["chunk_size"]
    mm = prec.einsum

    @jax.checkpoint
    def layer(x, wt):
        s = wt["ssd"]
        hn = C.rmsnorm(x, wt["ln"]["scale"], eps)
        proj = mm("bsd,de->bse", hn, s["in_proj"])
        z, xbc, dt = (proj[..., :di], proj[..., di: 2 * di + 2 * n],
                      proj[..., 2 * di + 2 * n:])
        pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
        ln = xbc.shape[1]
        conv = sum(pad[:, i: i + ln] * s["conv_w"][i] for i in range(w))
        xbc = jax.nn.silu(conv + s["conv_b"])
        xs, bm, cm = xbc[..., :di], xbc[..., di: di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + s["dt_bias"])               # (B, S, H)
        a = -jnp.exp(s["a_log"])
        xh = xs.reshape(xs.shape[0], ln, h, p)
        y = ssd(prec, xh * dt[..., None], a * dt, bm, cm, chunk)
        y = y + s["d_skip"][:, None] * xh
        y = y.reshape(xs.shape) * jax.nn.silu(z)
        y = C.rmsnorm(y, s["gate_norm"]["scale"], GATE_EPS)
        return x + mm("bse,ed->bsd", y, s["out_proj"]), None

    def loss(params, tokens, labels, weights):
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, params["stages"][0])
        return C.ce_sum(prec, x, params["head"],
                        params["final_norm"]["scale"], eps, labels, weights)

    return loss
