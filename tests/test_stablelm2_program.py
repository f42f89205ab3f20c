"""What the published StableLM 2 adds to the program (partial rotary, q/k/v
and LayerNorm biases, the exchange's named scopes), and that the defaults
leave every other architecture's program as it was."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.engine.units import UnitPlanner
from repro.models import model as M
from repro.models.layers.rope import apply_rope


def test_partial_rotary_is_rotate_half_on_the_leading_dims():
    """16 of 64 dims rotated as a 16-dim head (pairs i and i + 8, the
    frequencies of a 16-dim head), the other 48 passed through."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, 64)).astype(np.float32)
    pos = np.array([[0, 1, 2, 7, 100], [3, 4, 5, 6, 2047]], np.int32)
    got = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), 10_000.0,
                                rotary_dims=16))
    want = x.copy()
    for b, s, h in np.ndindex(2, 5, 3):
        for i in range(8):
            ang = pos[b, s] / 10_000.0 ** (2 * i / 16)
            c, sn = math.cos(ang), math.sin(ang)
            a, bb = x[b, s, h, i], x[b, s, h, i + 8]
            want[b, s, h, i] = a * c - bb * sn
            want[b, s, h, i + 8] = bb * c + a * sn
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[..., 16:], x[..., 16:])


def test_published_model_holds_its_biases():
    """LayerNorm scale and bias, and q/k/v biases, in every layer; the
    flat unit layout counts them, and the uneven shards cover it."""
    cfg = get_arch("stablelm-2-1.6b")
    assert (cfg.norm_kind, cfg.rope_fraction, cfg.qkv_bias,
            cfg.norm_eps) == ("layernorm", 0.25, True, 1e-5)
    small = cfg.reduced()
    shapes = jax.eval_shape(lambda: M.init_params(small,
                                                  jax.random.PRNGKey(0)))
    layer = shapes["stages"][0]
    h, hd, d = small.n_heads, small.head_dim, small.d_model
    for b in ("bq", "bk", "bv"):
        assert layer["attn"][b].shape == (small.n_layers, h, hd)
    for ln in ("ln_attn", "ln_mlp"):
        assert set(layer[ln]) == {"bias", "scale"}
    assert set(shapes["final_norm"]) == {"bias", "scale"}
    plan = UnitPlanner(small, [0.126953125, 0.2060546875, 0.3330078125,
                               0.333984375])
    stage = plan.group("stage0").layout
    elem = sum(math.prod(x.shape[1:]) for x in jax.tree.leaves(layer))
    assert stage.size == elem
    assert sum(stage.shard_sizes) == stage.padded >= elem
    assert stage.p_max == max(stage.shard_sizes) > 0


def test_prefill_then_decode_matches_full_forward():
    """Serving shares the changed attention: prefill, then decode through
    the cache, gives the full forward's logits at every decoded position."""
    cfg = get_arch("stablelm-2-1.6b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # non-zero biases, so that the decode path's bias terms are tested
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(p))), x.shape)
        if getattr(p[-1], "key", "") in ("bq", "bk", "bv", "bias") else x,
        params)
    total, prefix = 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, total), 0,
                              cfg.vocab_size)
    _, caches = M.prefill(cfg, params, toks[:, :prefix], max_len=total)
    decode = jax.jit(lambda p, c, t, q: M.decode_step(cfg, p, c, t, q))
    errs = []
    for pos in range(prefix, total):
        logits, caches = decode(params, caches, toks[:, pos:pos + 1],
                                jnp.full((2,), pos, jnp.int32))
        h, _ = M.forward_hidden(cfg, params, toks[:, : pos + 1],
                                remat="none")
        z_ref = M.head_logits(cfg, params, h[:, -1:])
        errs.append(float(jnp.abs(logits - z_ref).max()))
    assert max(errs) < 2e-3, errs


#: sha256 of the StableHLO text (``Lowered.as_text()``) of the repository's
#: stablelm variant and of mamba2, at reduced size, as lowered before
#: ``rope_fraction``, ``qkv_bias`` and the exchange's named scopes came in:
#: the defaults add no op and no leaf.  A change to either program that is
#: meant changes these.
UNCHANGED = {
    "stablelm-1.6b": {
        "train": "6a0e9e80fd3188697ecef71fed7e158b3af6c84a1caa1d82bb25e231a6bd2b98",
        "prefill": "1eda021b2ce691596d394e9af90b8d1e34cabae5869a73a31b56586016356658",
        "decode": "775ccc085b11921e708414ba645316b434c0558588d83789917443baf75a89e6",
    },
    "mamba2-370m": {
        "train": "c355d6ad9769c20f437883b7c0242d841de81aa34401a303783628b028973c92",
        "prefill": "d46b2e6b17955c74f2ef804d0c5db6c6f4167160bfda4af9119fcda6f59838ff",
        "decode": "fcd9ddfe4300d26c6d2d7b7b739771c699ca20cb30942423429e63b3a6db517b",
    },
}

LOWER = """
import hashlib, json
import jax, jax.numpy as jnp
from repro.configs.base import get_arch
from repro.core.layered_ga import CephaloProgram
from repro.launch.mesh import make_mesh
from repro.models import model as M

cfg = get_arch({arch!r}).reduced()
prog = CephaloProgram(cfg, make_mesh((1,), ("data",)), ell=2, m=1, seq=32)
sds = lambda t: {{k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in t.items()}}
params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
caches = jax.eval_shape(lambda: M.init_cache(cfg, 2, 32))
i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
texts = {{
    "train": jax.jit(prog.build()).lower(sds(prog.state_shapes()),
                                         sds(prog.batch_shapes())),
    "prefill": jax.jit(lambda p, t: M.prefill(cfg, p, t, 32)).lower(
        params, i32(2, 16)),
    "decode": jax.jit(lambda p, c, t, q: M.decode_step(cfg, p, c, t, q))
    .lower(params, caches, i32(2, 1), i32(2)),
}}
print("HASHES", json.dumps({{k: hashlib.sha256(v.as_text().encode())
                            .hexdigest() for k, v in texts.items()}}))
"""


@pytest.mark.parametrize("arch", sorted(UNCHANGED))
def test_default_programs_lower_as_before(subproc, arch):
    """In a fresh interpreter on one device, so that no other test's
    settings reach the lowering."""
    import json
    out = subproc(LOWER.format(arch=arch), n_devices=1)
    line = [ln for ln in out.splitlines() if ln.startswith("HASHES ")][-1]
    assert json.loads(line[len("HASHES "):]) == UNCHANGED[arch]


SCOPES = """
import re
import jax
from repro.configs.base import get_arch
from repro.core import fsdp
from repro.core.layered_ga import CephaloProgram
from repro.launch.mesh import make_mesh

cfg = get_arch("stablelm-2-1.6b").reduced()
prog = CephaloProgram(cfg, make_mesh((4,), ("data",)), ell=2, m=1, seq=32,
                      ratios=[0.126953125, 0.2060546875, 0.3330078125,
                              0.333984375])
sds = lambda t: {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in t.items()}
hlo = jax.jit(prog.build()).lower(sds(prog.state_shapes()),
                                  sds(prog.batch_shapes())).compile().as_text()
ops = re.findall(r"= \\S+ ([a-z\\-]+)\\(.*op_name=\\"([^\\"]*)\\"", hlo)
comps = lambda name: re.split(r"[/()]", name)
gathers = [n for op, n in ops if op.startswith("all-gather")]
scatters = [n for op, n in ops if op.startswith("reduce-scatter")]
assert gathers and scatters, (len(gathers), len(scatters))
assert all(fsdp.GATHER_SCOPE in comps(n) for n in gathers), gathers
assert all(fsdp.SCATTER_SCOPE in comps(n) for n in scatters), scatters
lookups = [n for op, n in ops if n.endswith("/gather")]
assert lookups and not any(fsdp.GATHER_SCOPE in comps(n) for n in lookups)
print("SCOPES-OK", len(gathers), len(scatters), len(lookups))
"""


@pytest.mark.integration
def test_exchange_carries_its_named_scopes(subproc):
    """Every AllGather of the compiled step is under ``unit_gather`` and
    every ReduceScatter under ``unit_scatter``; the lax gathers of the
    embedding lookup and the loss (op names ending ``/gather``) are not."""
    out = subproc(SCOPES, n_devices=4)
    assert "SCOPES-OK" in out, out
