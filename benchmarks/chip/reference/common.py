"""What the plain references of every family share.

Plain ``jax.numpy`` in float32: RMSNorm, rotary embeddings, the head with
its cross-entropy, Adam, and the three-step training run that the
benchmark compares the program with.  Nothing here imports the program.

Every matrix product goes through a :class:`Precision`.  ``"float32"`` is
the reference itself, at ``jax.lax.Precision.HIGHEST``.  ``"fp8"`` is the
control of ``How correct is decided``: the same products with both
operands rounded to float8 e4m3, each tensor scaled so that its largest
magnitude maps to e4m3's largest finite value, and accumulated in float32.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, also one over 32 bits."""
    s = seed % (1 << 64)
    key = jax.random.PRNGKey(s & 0x7FFFFFFF)
    return jax.random.fold_in(key, (s >> 31) & 0x7FFFFFFF)


@jax.custom_vjp
def _to_fp8(x: jax.Array) -> jax.Array:
    """x rounded to scaled e4m3.  Its gradient passes straight through, so
    the backward products take the rounded operands and a float32
    cotangent (a convert's own gradient would round the cotangent
    unscaled, and flush most of it to zero)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q / scale


_to_fp8.defvjp(lambda x: (_to_fp8(x), None), lambda _, ct: (ct,))


class Precision:
    """How the reference multiplies: ``float32`` or ``fp8`` operands."""

    def __init__(self, name: str = "float32"):
        if name not in ("float32", "fp8"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name

    def einsum(self, eq: str, *ops: jax.Array) -> jax.Array:
        ops = tuple(o.astype(F32) for o in ops)
        if self.name == "fp8":
            ops = tuple(_to_fp8(o) for o in ops)
        return jnp.einsum(eq, *ops, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with the scale as (1 + w), w initialised to zero."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the whole head, halves rotated together.
    x: (B, S, H, D) at positions 0..S-1."""
    d, s = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ce_sum(prec: Precision, h: jax.Array, w_head: jax.Array,
           norm_scale: jax.Array, eps: float, labels: jax.Array,
           weights: jax.Array, rows: int = 512) -> jax.Array:
    """Sum over positions of weight x cross-entropy of the final norm and
    the head, ``rows`` positions at a time so that no (B, S, V) block of
    logits is ever whole."""
    b, s, d = h.shape
    rows = min(rows, b * s)
    n = (b * s) // rows
    hs = h.reshape(n, rows, d)
    ys = labels.reshape(n, rows)
    ws = weights.reshape(n, rows)

    @jax.checkpoint
    def block(tot, inp):
        hc, yc, wc = inp
        z = prec.einsum("rd,dv->rv", rmsnorm(hc, norm_scale, eps), w_head)
        lse = jax.nn.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, yc[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(wc * (lse - picked)), None

    tot, _ = jax.lax.scan(block, jnp.zeros((), F32), (hs, ys, ws))
    return tot


# ---------------------------------------------------------------------------
# Adam and the three-step run
# ---------------------------------------------------------------------------

def adam(opt: Dict[str, float], p, g, m, v, t: int):
    """One Adam step (``t`` is 1-based, and may be traced) on whole
    trees."""
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(
        lambda p_, m_, v_: p_ - opt["lr"] * (m_ / c1)
        / (jnp.sqrt(v_ / c2) + opt["eps"]), p, m, v)
    return p, m, v


def leaf_norms(tree: Any) -> jax.Array:
    """Norms of every leaf, one per layer for leaves stacked over layers
    (every leaf under ``stages``), in :func:`leaf_names` order."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(F32)
        if _stacked(path):
            out.append(jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


def leaf_names(tree: Any) -> List[str]:
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if _stacked(path):
            names += [f"{name}[{i}]" for i in range(x.shape[0])]
        else:
            names.append(name)
    return names


def _stacked(path) -> bool:
    return any(getattr(k, "key", None) == "stages" for k in path)


def train_three(loss_fn: Callable, params: Any, batches: Sequence[Dict],
                opt: Dict[str, float], rows_per_block: int,
                shardings: Any = None) -> Dict[str, Any]:
    """Three Adam steps from ``params`` on ``batches`` (each a dict of
    (B, S) ``tokens``, ``labels``, ``weights``).

    Returns each step's loss, the per-leaf norms of the first gradient,
    and the parameters after the third step.  ``loss_fn(params, tokens,
    labels, weights)`` gives the weighted CE sum of a block of rows; the
    gradient is summed over blocks of ``rows_per_block`` rows.
    ``params`` is consumed."""

    def grad(p, batch):
        b = batch["tokens"].shape[0]
        k = min(rows_per_block, b)
        blocks = {n: a.reshape((b // k, k) + a.shape[1:])
                  for n, a in batch.items()}
        vg = jax.value_and_grad(
            lambda q, bl: loss_fn(q, bl["tokens"], bl["labels"],
                                  bl["weights"]))
        if b == k:
            return vg(p, {n: a[0] for n, a in blocks.items()})

        def body(acc, bl):
            lo, gr = vg(p, bl)
            return (acc[0] + lo, jax.tree.map(jnp.add, acc[1], gr)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p))
        (lo, gr), _ = jax.lax.scan(body, zero, blocks)
        return lo, gr

    def step(p, m, v, batch, t):
        lo, g = grad(p, batch)
        p, m, v = adam(opt, p, g, m, v, t)
        return p, m, v, lo, leaf_norms(g)

    kw = {}
    if shardings is not None:
        kw = {"out_shardings": (shardings, shardings, shardings, None, None)}
    jstep = jax.jit(step, donate_argnums=(0, 1, 2), **kw)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    **({"out_shardings": shardings} if shardings else {}))
    m, v = zeros(params), zeros(params)
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        params, m, v, lo, gn = jstep(params, m, v, batch, jnp.float32(t))
        losses.append(float(lo))
        if t == 1:
            g1 = np.asarray(gn)
    del m, v
    return {"losses": losses, "grad1_norms": g1, "params": params}


def change_norms(p_after: Any, p_before: Any) -> np.ndarray:
    """Per-leaf norms of ``p_after - p_before``."""
    return np.asarray(jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p_after, p_before))


def fan_in_normal(key: jax.Array, shape: Sequence[int],
                  fan_in: int) -> jax.Array:
    """Truncated normal (at 2 sigma) with standard deviation
    1/sqrt(fan_in): the scale every projection starts from."""
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) \
        / math.sqrt(fan_in)


def batch_from_block(block: np.ndarray) -> Dict[str, np.ndarray]:
    """A (B, S+1) token block as the reference's (B, S) batch, each real
    token weighted 1/(B S) so the weighted sum is the mean CE."""
    b, s = block.shape[0], block.shape[1] - 1
    return {"tokens": block[:, :-1], "labels": block[:, 1:],
            "weights": np.full((b, s), 1.0 / (b * s), np.float32)}
