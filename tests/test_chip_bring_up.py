"""Host-side pieces of the chip bring-up: the ``device_kind`` peak lookup,
the compile-cache placement, the mesh helper, and a rehearsal of
``chip_smoke.py`` on the CPU at a small size (Pallas kernels in interpret
mode, the four-chip phase on four forced host devices)."""

import importlib.util
import io
import json
import os
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import get_arch
from repro.core import device_specs as D
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("kind,spec", [
    ("TPU v5 lite", D.TPU_V5E),
    ("TPU v4", D.TPU_V4),
    ("TPU v5", D.TPU_V5P),
])
def test_device_kind_lookup(kind, spec):
    assert D.for_device_kind(kind) is spec


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks known"):
        D.for_device_kind(kind)


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_compile_cache_follows_env(monkeypatch, tmp_path,
                                   restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_into_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_mesh_helper_auto_axes_run_sharded_embed_gather():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    table = jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)
    toks = jnp.asarray([[3, 0, 63, 7]], jnp.int32)
    t_sh = jax.device_put(table, NamedSharding(mesh, P("model", None)))
    k_sh = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    out = jax.jit(lambda t, k: t[k])(t_sh, k_sh)
    assert out.shape == (1, 4, 8)
    assert bool(jnp.all(out == table[toks]))


def test_chip_smoke_refuses_a_host_without_tpu():
    smoke = _chip_smoke()
    out = io.StringIO()
    with redirect_stdout(out):
        rc = smoke.main([])
    assert rc != 0
    assert '"ok"' not in out.getvalue()


def test_chip_smoke_phases_rehearsal():
    smoke = _chip_smoke()
    errs = smoke.kernel_phase(interpret=True, flash_shape=(1, 2, 256, 64),
                              ssd_shape=(1, 2, 512, 64, 128), ssd_chunk=256)
    assert errs["flash_err"] <= smoke.FLASH_TOL
    assert errs["ssd_err"] <= smoke.SSD_TOL
    res = smoke.train_phase(get_arch("stablelm-1.6b").reduced(), seq=64,
                            steps=3)
    assert abs(res["losses"][0] - res["ref_loss"]) <= smoke.FIRST_LOSS_TOL
    assert res["losses"][-1] < res["losses"][0]


def test_chip_smoke_four_chip_rehearsal(subproc):
    out = subproc(f"""
import importlib.util, json
import jax
from repro.configs.base import get_arch
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
full = get_arch("stablelm-1.6b")
plan = smoke.uneven_plan(full, seq=2048, batch=8)
res = smoke.four_chip_phase(full.reduced(), jax.devices()[:4], plan,
                            seq=32, steps=2)
print("RESULT", json.dumps(res["diffs"]))
""", n_devices=4)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][-1]
    diffs = json.loads(line.split(" ", 1)[1])
    assert len(diffs) == 2 and max(diffs) < 1e-4, diffs
