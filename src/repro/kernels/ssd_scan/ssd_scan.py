"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid ``(batch, head, chunk)`` with the chunk axis innermost and
sequential; the inter-chunk SSM state ``h ∈ R^{P×N}`` lives in VMEM
scratch and is carried across chunk steps — the TPU-native analogue of the
CUDA SSD kernel's persistent-block state (DESIGN.md §2).

Per chunk (length Q, all in VMEM):
  la   = cumsum(dt * a)                            (Q,)
  Yin  = ((C Bᵀ) ∘ causal-decay) (dt ∘ X)          intra-chunk, MXU matmuls
  Yout = exp(la) ∘ (C h_prevᵀ)                     inter-chunk
  h    = exp(la_Q) h_prev + (B ∘ dt ∘ exp(la_Q−la))ᵀ X
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, h_scr, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    hi = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)             # (1, Q) row
    a = a_ref[0, hi]                                 # scalar (SMEM)
    b = b_ref[...].astype(jnp.float32)               # (Q, N)
    c = c_ref[...].astype(jnp.float32)               # (Q, N)

    # dt arrives as a lane row; per-position columns come from masked
    # reductions over (Q, Q), which keeps every value 2-D and tile-aligned
    # (no in-kernel transpose or cumsum).
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= ik
    diag = iq == ik
    log_a = dt * a                                   # (1, Q) ≤ 0
    la = jnp.sum(jnp.where(causal, log_a, 0.0), axis=1,
                 keepdims=True)                      # (Q, 1) cumsum
    la_row = jnp.sum(jnp.where(diag, la, 0.0), axis=0,
                     keepdims=True)                  # (1, Q)
    la_last = jnp.sum(log_a, axis=1, keepdims=True)  # (1, 1)
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1,
                     keepdims=True)                  # (Q, 1)

    # intra-chunk: masked decay attention (MXU matmul duality)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    gap = la - la_row                                # (Q, Q)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    xdt = x * dt_col                                 # (Q, P)
    y_intra = jax.lax.dot_general(scores * decay, xdt,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    h_prev = h_scr[...]                              # (P, N)
    y_inter = jnp.exp(la) * jax.lax.dot_general(
        c, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (Q, P)

    o_ref[...] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update (dt is already folded into xdt)
    w = jnp.exp(la_last - la) * b                    # (Q, N)
    h_scr[...] = jnp.exp(la_last) * h_prev + jax.lax.dot_general(
        xdt, w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)


def ssd_scan_kernel(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """x: (B, H, L, P); dt: (B, H, L); a: (H,); b, c: (B, L, N).
    L must be a multiple of ``chunk`` (ops.py pads).  Returns (B, H, L, P).

    ``dt`` is viewed as (B, H, 1, L) so its block's last two dims are
    (1, chunk): a full dim and a lane multiple, as the TPU tiling rule
    asks.  ``a`` sits whole in SMEM and is read as a scalar per head.
    """
    bsz, h, l, p = x.shape
    n = b.shape[-1]
    nchunks = l // chunk
    grid = (bsz, h, nchunks)
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(bsz, h, 1, l), a.reshape(1, h).astype(jnp.float32),
      b, c)
