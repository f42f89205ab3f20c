"""Compile the Pallas kernels for a described TPU v5e (no chip needed).

Interpret-mode tests (``test_kernels.py``) check the kernels' arithmetic;
these check that the chip's compiler accepts them at real widths: block
shapes against the tiling rule, memory spaces, VMEM use.  Each compiled
program must hold the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file.  The persistent compilation cache is off while
these compile, because an entry compiled for a described chip cannot be
read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.ssd_scan.ops import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (32, 32, 64),      # stablelm-1.6b
    (32, 8, 128),      # grouped-query attention, 4 queries per KV head
])
def test_flash_attention_compiles_for_v5e(one_chip, heads, kv_heads,
                                          head_dim):
    seq = 4096
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True), one_chip,
        ((1, heads, seq, head_dim), jnp.bfloat16),
        ((1, kv_heads, seq, head_dim), jnp.bfloat16),
        ((1, kv_heads, seq, head_dim), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_for_v5e(one_chip):
    # mamba2-370m: 32 heads of P=64, state N=128, chunk 256
    b, h, l, p, n = 1, 32, 4096, 64, 128
    text = _compiled_text(
        lambda x, dt, a, bm, cm: ssd_scan(x, dt, a, bm, cm, chunk=256),
        one_chip,
        ((b, h, l, p), jnp.bfloat16), ((b, h, l), jnp.float32),
        ((h,), jnp.float32), ((b, l, n), jnp.bfloat16),
        ((b, l, n), jnp.bfloat16))
    assert "tpu_custom_call" in text
