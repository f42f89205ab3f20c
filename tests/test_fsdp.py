"""Flat-unit FSDP layout tests (host-side; collective paths are covered by
tests/integration)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
# real hypothesis when installed; otherwise the deterministic sampling
# shim tests/conftest.py registers in sys.modules before collection
from hypothesis import given, settings, strategies as st

from repro.core import fsdp


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "w1": jax.random.normal(ks[0], (33, 17)),
        "nested": {"b": jax.random.normal(ks[1], (7,)),
                   "w2": jax.random.normal(ks[2], (5, 5, 3))},
        "scalarish": jax.random.normal(ks[3], (1,)),
    }


def test_flatten_roundtrip():
    tree = _tree()
    layout = fsdp.make_layout("t", tree, [0.5, 0.3, 0.2])
    flat = fsdp.flatten_unit(layout, tree)
    assert flat.shape == (layout.padded,)
    back = fsdp.unflatten_unit(layout, flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_shard_concat_identity():
    tree = _tree(1)
    layout = fsdp.make_layout("t", tree, [0.7, 0.1, 0.1, 0.1])
    flat = fsdp.flatten_unit(layout, tree)
    shards = fsdp.shard_unit_ragged(layout, flat)
    assert [len(s) for s in shards] == layout.shard_sizes
    np.testing.assert_allclose(np.concatenate(shards), np.asarray(flat))
    # padded SPMD wire format: valid prefixes match
    for i, r in enumerate(shards):
        p = fsdp.shard_unit(layout, flat, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(p[: len(r)]), r)
        assert p.shape == (layout.p_max,)
        assert not np.any(np.asarray(p[len(r):]))


@given(n=st.integers(1, 32), seed=st.integers(0, 100),
       zero_rank=st.booleans())
@settings(max_examples=50, deadline=None)
def test_layout_properties(n, seed, zero_rank):
    rng = np.random.default_rng(seed)
    ratios = rng.random(n) + 1e-3
    if zero_rank and n > 1:
        ratios[rng.integers(0, n)] = 0.0
    tree = {"w": np.zeros((rng.integers(1, 2000),), np.float32)}
    layout = fsdp.make_layout("t", tree, ratios)
    assert sum(layout.shard_sizes) == layout.padded
    assert layout.padded >= layout.size
    assert layout.padded % fsdp.QUANTUM == 0
    assert all(s % fsdp.QUANTUM == 0 for s in layout.shard_sizes)
    assert all(s >= 0 for s in layout.shard_sizes)


def test_uneven_layout_tracks_ratios():
    tree = {"w": np.zeros((100_000,), np.float32)}
    ratios = [0.5, 0.25, 0.125, 0.125]
    layout = fsdp.make_layout("t", tree, ratios)
    got = np.array(layout.shard_sizes) / layout.padded
    np.testing.assert_allclose(got, ratios, atol=0.01)
