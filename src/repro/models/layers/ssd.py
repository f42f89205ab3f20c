"""Mamba2 block — State Space Duality (SSD), chunked parallel form.

Implements the Mamba2 (arXiv:2405.21060) block:

    in_proj → [z | x | B | C | dt] → causal depthwise conv (x,B,C) → SSD →
    gated RMSNorm → out_proj

The SSD recurrence per head (state ``h ∈ R^{P×N}``):

    h_t = exp(dt_t·A) · h_{t-1} + dt_t · x_t ⊗ B_t
    y_t = h_t · C_t + D · x_t

computed chunk-parallel: intra-chunk via a masked decay matmul (the
"duality" — it is exactly masked attention), inter-chunk via a scan over
chunk states.  :func:`ssd_reference` is the pure recurrent oracle used by
the tests and the Pallas kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.init_utils import dense_init
from repro.models.layers.norms import rmsnorm_apply, rmsnorm_init


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    d_model: int
    d_inner: int
    n_state: int          # N
    head_dim: int         # P
    chunk: int = 256
    conv_width: int = 4

    @property
    def heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_state


def ssd_init(key: jax.Array, spec: SSMSpec) -> dict:
    k_in, k_conv, k_out, k_dt = jax.random.split(key, 4)
    h = spec.heads
    proj_out = 2 * spec.d_inner + 2 * spec.n_state + h
    return {
        "in_proj": dense_init(k_in, (spec.d_model, proj_out)),
        "conv_w": dense_init(k_conv, (spec.conv_width, spec.conv_dim),
                             fan_in=spec.conv_width),
        "conv_b": jnp.zeros((spec.conv_dim,), jnp.float32),
        "dt_bias": jax.random.uniform(
            k_dt, (h,), jnp.float32, minval=-4.0, maxval=-1.0),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "gate_norm": rmsnorm_init(spec.d_inner),
        "out_proj": dense_init(k_out, (spec.d_inner, spec.d_model)),
    }


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array, h0: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Pure recurrent oracle.

    x: (B,L,H,P)  dt: (B,L,H)  a: (H,) negative  b, c: (B,L,N)
    Returns y: (B,L,H,P) and final state (B,H,P,N).
    """
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(hs, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * a)[..., None, None]           # (B,H,1,1)
        upd = (dtt[..., None, None] * xt[..., :, None]
               * bt[:, None, None, :])                      # (B,H,P,N)
        hs = hs * decay + upd
        yt = jnp.einsum("bhpn,bn->bhp", hs, ct)
        return hs, yt

    xs = (x.swapaxes(0, 1).astype(jnp.float32),
          dt.swapaxes(0, 1).astype(jnp.float32),
          b.swapaxes(0, 1).astype(jnp.float32),
          c.swapaxes(0, 1).astype(jnp.float32))
    hT, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), hT


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunk-parallel SSD (the training/prefill path).

    Same signature/semantics as :func:`ssd_reference`; O(L·Q) memory with
    Q = chunk instead of the O(L·P·N) of materializing every state.
    """
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    if l % chunk != 0:
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    lp = x.shape[1]
    nc = lp // chunk
    xf = x.reshape(bsz, nc, chunk, h, p).astype(jnp.float32)
    dtf = dt.reshape(bsz, nc, chunk, h).astype(jnp.float32)
    bf = b.reshape(bsz, nc, chunk, n).astype(jnp.float32)
    cf = c.reshape(bsz, nc, chunk, n).astype(jnp.float32)

    log_a = dtf * a                                        # (B,C,Q,H) ≤ 0
    la = jnp.cumsum(log_a, axis=2)                         # within-chunk cumsum
    la_last = la[:, :, -1:, :]                             # (B,C,1,H)

    # --- intra-chunk (masked attention duality) ---------------------------
    scores = jnp.einsum("bcqn,bcsn->bcqs", cf, bf)         # (B,C,Q,Q)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
    # mask the exponent *before* exp: exp of a positive (future) gap can
    # overflow and inf*0 poisons the backward pass
    gap = la[:, :, :, None, :] - la[:, :, None, :, :]      # (B,C,Q,S,H)
    m = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    xdt = xf * dtf[..., None]                              # (B,C,Q,H,P)
    y_intra = jnp.einsum("bcqs,bcqsh,bcshp->bcqhp", scores, m, xdt)

    # --- chunk states ------------------------------------------------------
    decay_to_end = jnp.exp(la_last - la)                   # (B,C,Q,H)
    s_chunk = jnp.einsum("bcsh,bcsn,bcshp->bchpn",
                         decay_to_end, bf, xdt)            # (B,C,H,P,N)

    # --- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(la_last[:, :, 0, :])             # (B,C,H)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def chunk_step(hs, inp):
        dec, s_c = inp                                     # (B,H), (B,H,P,N)
        h_prev = hs
        hs = hs * dec[..., None, None] + s_c
        return hs, h_prev

    hT, h_prevs = jax.lax.scan(
        chunk_step, h0,
        (chunk_decay.swapaxes(0, 1), s_chunk.swapaxes(0, 1)))
    h_prevs = h_prevs.swapaxes(0, 1)                       # (B,C,H,P,N)

    # --- inter-chunk contribution ------------------------------------------
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                         cf, jnp.exp(la), h_prevs)
    y = (y_intra + y_inter).reshape(bsz, lp, h, p)[:, :l]
    return y, hT


# ---------------------------------------------------------------------------
# Full block
# ---------------------------------------------------------------------------

def _split_proj(proj: jax.Array, spec: SSMSpec):
    di, n, h = spec.d_inner, spec.n_state, spec.heads
    z = proj[..., :di]
    xbc = proj[..., di: di + spec.conv_dim]
    dt = proj[..., di + spec.conv_dim:]
    assert dt.shape[-1] == h
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, bias: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv along seq.  xbc: (B,L,Cd); w: (W,Cd).
    Returns (out, new_state) where state is the last W-1 inputs."""
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[-1]),
                          xbc.dtype)
    full = jnp.concatenate([state, xbc], axis=1)
    out = sum(full[:, i: i + xbc.shape[1]] * w[i]
              for i in range(width))
    out = out + bias.astype(out.dtype)
    new_state = full[:, -(width - 1):]
    return jax.nn.silu(out), new_state


@jax.named_scope("ssd")
def ssd_apply(params: dict, x: jax.Array, spec: SSMSpec,
              h0: Optional[jax.Array] = None,
              conv0: Optional[jax.Array] = None,
              use_chunked: bool = True):
    """Full Mamba2 block over a sequence.  x: (B, L, D).
    Returns (y, (ssm_state, conv_state))."""
    dtype = x.dtype
    proj = x @ params["in_proj"].astype(dtype)
    z, xbc, dt_raw = _split_proj(proj, spec)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"].astype(dtype),
                                   params["conv_b"], conv0)
    xs = xbc[..., : spec.d_inner]
    b = xbc[..., spec.d_inner: spec.d_inner + spec.n_state]
    c = xbc[..., spec.d_inner + spec.n_state:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    xh = xs.reshape(*xs.shape[:-1], spec.heads, spec.head_dim)
    import os
    kmode = os.environ.get("REPRO_USE_PALLAS", "off")
    if kmode != "off" and h0 is None:
        # Pallas SSD kernel (TPU target; interpret mode on CPU).
        # Kernel layout: x (B,H,L,P), dt (B,H,L).
        from repro.kernels.ssd_scan.ops import ssd_scan
        y = ssd_scan(xh.transpose(0, 2, 1, 3),          # (B,H,L,P)
                     dt.transpose(0, 2, 1), a, b, c, chunk=spec.chunk,
                     interpret=(kmode == "interpret"))
        y = y.transpose(0, 2, 1, 3)                     # back to (B,L,H,P)
        hT = jnp.zeros((xh.shape[0], spec.heads, spec.head_dim,
                        spec.n_state), jnp.float32)  # kernel: train path
    elif use_chunked:
        y, hT = ssd_chunked(xh, dt, a, b, c, spec.chunk, h0=h0)
    else:
        y, hT = ssd_reference(xh, dt, a, b, c, h0=h0)
    y = y + params["d_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(*xs.shape[:-1], spec.d_inner).astype(dtype)
    y = rmsnorm_apply(params["gate_norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"].astype(dtype)
    return out, (hT, conv_state)


def ssd_decode_step(params: dict, x: jax.Array, spec: SSMSpec,
                    h: jax.Array, conv_state: jax.Array):
    """One-token recurrent step.  x: (B, 1, D);
    h: (B,H,P,N); conv_state: (B, W-1, conv_dim)."""
    dtype = x.dtype
    proj = x @ params["in_proj"].astype(dtype)
    z, xbc, dt_raw = _split_proj(proj, spec)
    w = params["conv_w"].astype(dtype)
    full = jnp.concatenate([conv_state, xbc], axis=1)      # (B, W, Cd)
    conv_out = jnp.einsum("bwc,wc->bc", full, w) + \
        params["conv_b"].astype(dtype)
    conv_out = jax.nn.silu(conv_out)[:, None]
    new_conv = full[:, 1:]
    xs = conv_out[..., : spec.d_inner]
    b = conv_out[..., spec.d_inner: spec.d_inner + spec.n_state]
    c = conv_out[..., spec.d_inner + spec.n_state:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    xh = xs.reshape(xs.shape[0], spec.heads, spec.head_dim)
    dt1 = dt[:, 0]                                         # (B,H)
    decay = jnp.exp(dt1 * a)[..., None, None]
    upd = dt1[..., None, None] * xh.astype(jnp.float32)[..., :, None] \
        * b[:, 0][:, None, None, :].astype(jnp.float32)
    h_new = h * decay + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, c[:, 0].astype(jnp.float32))
    y = y + params["d_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(x.shape[0], 1, spec.d_inner).astype(dtype)
    y = rmsnorm_apply(params["gate_norm"], y * jax.nn.silu(z))
    out = y @ params["out_proj"].astype(dtype)
    return out, (h_new, new_conv)
