"""The program's own tracing: named scopes in the step's op metadata, and
the host spans of ``SpmdEngine.step`` in the profiler's trace.

Per-layer metrics of the chip benchmark read both by name, so these tests
pin the names, the nesting and the span arguments.
"""

import glob
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.engine import build_train_step, homogeneous_plan

SEQ = 32
SPANS = ("spmd.step", "spmd.grid", "spmd.put", "spmd.dispatch",
         "spmd.loss_wait")
#: the benchmark's own span names, which the program must never write
BENCHMARK_SPANS = ("traffic.next", "engine.step", "sync")


def _engine(arch: str):
    cfg = get_arch(arch).reduced(n_layers=2, d_model=64)
    return build_train_step(cfg, homogeneous_plan(1, 2, 1),
                            substrate="shard_map", seq_len=SEQ)


def _op_names(arch: str):
    eng = _engine(arch)
    state = eng.init_state(jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in eng.program.batch_shapes().items()}
    hlo = eng.program.jit_step().lower(state, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


def _in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a component of the path, bare or wrapped by
    transformations (``transpose(jvp(vmap(ce)))``)."""
    return re.search(r"(^|[/(])%s($|[/)])" % re.escape(scope),
                     op_name) is not None


@pytest.mark.parametrize("arch,scopes", [
    ("stablelm-1.6b", ("attention", "mlp", "ce")),
    ("mamba2-370m", ("ssd", "ce")),
], ids=["dense", "ssm"])
def test_scopes_in_forward_and_backward(arch, scopes):
    names = _op_names(arch)
    for scope in scopes:
        mine = [n for n in names if _in_scope(n, scope)]
        assert any("transpose(" not in n for n in mine), (scope, "forward")
        assert any("transpose(" in n for n in mine), (scope, "backward")
    adam = [n for n in names if _in_scope(n, "adam")]
    assert adam and not any("transpose(" in n for n in adam)
    others = {"stablelm-1.6b": ("ssd",),
              "mamba2-370m": ("attention", "mlp")}[arch]
    assert not any(_in_scope(n, s) for n in names for s in others)


def test_compile_cache_keeps_each_steps_own_scopes(tmp_path):
    """JAX's persistent cache keys on the program without its op metadata
    unless told otherwise: a step whose scopes changed would get the
    executable of the old one, and its trace the old names.  Building
    the step makes the metadata part of the key."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    flag = "jax_compilation_cache_include_metadata_in_key"
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", flag,
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def f(x, scoped):
        if scoped:
            with jax.named_scope("attention"):
                return jax.numpy.sin(x) @ x
        return jax.numpy.sin(x) @ x

    def compiled_text(scoped):
        return jax.jit(lambda x: f(x, scoped)).lower(
            np.ones((8, 8), np.float32)).compile().as_text()

    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update(flag, False)
        compiled_text(False)
        assert "attention" not in compiled_text(True)     # the stale entry
        _engine("stablelm-1.6b").program.jit_step()
        assert getattr(jax.config, flag)
        assert "attention" in compiled_text(True)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _events(logdir):
    files = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two steps of a tiny dense engine under the profiler, each inside a
    caller's span."""
    eng = _engine("stablelm-1.6b")
    state = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    blocks = [rng.integers(0, 512, (2, SEQ + 1)) for _ in range(3)]
    state, _ = eng.step(state, blocks[0])          # compile outside
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        for b in blocks[1:]:
            with jax.profiler.TraceAnnotation("caller"):
                state, _ = eng.step(state, b)
    return _events(logdir)


def test_step_spans_in_order_inside_caller(traced):
    callers = [e for e in traced if e[0] == "caller"]
    steps = [e for e in traced if e[0] == "spmd.step"]
    assert len(callers) == 2 and len(steps) == 2
    for (_, c0, c1, _), (_, s0, s1, stats) in zip(callers, steps):
        assert c0 <= s0 and s1 <= c1
        inner = [e for e in traced if e[0] in SPANS[1:] and s0 <= e[1]
                 and e[2] <= s1]
        assert [e[0] for e in inner] == list(SPANS[1:])
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    assert [s[3]["step_num"] for s in steps] == [1, 2]
    put = next(e for e in traced if e[0] == "spmd.put")
    assert put[3]["bytes"] == 3 * 2 * SEQ * 4      # tokens, labels, weights


def test_program_never_writes_benchmark_span_names(traced):
    names = {e[0] for e in traced}
    assert "spmd.step" in names
    assert not names & set(BENCHMARK_SPANS)


UNEVEN = """
import glob, tempfile, jax, numpy as np
from repro.configs.base import get_arch
from repro.core.engine import build_train_step
from repro.core.partition import Plan, RankPlan
cfg = get_arch("stablelm-1.6b").reduced(n_layers=2, d_model=64)
ranks = [RankPlan(0, "a", m=2, ell=3, state_ratio=0.6),
         RankPlan(1, "b", m=1, ell=1, state_ratio=0.4)]
plan = Plan(model=cfg.name, cluster="2", global_batch=7, ranks=ranks)
eng = build_train_step(cfg, plan, substrate="shard_map", seq_len=16)
state = eng.init_state(jax.random.PRNGKey(0))
block = np.random.default_rng(0).integers(0, 512, (7, 17))
state, _ = eng.step(state, block)
with tempfile.TemporaryDirectory() as d:
    with jax.profiler.trace(d):
        eng.step(state, block)
    f = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(f)
for p in data.planes:
    for l in p.lines:
        for e in l.events:
            if e.name == "spmd.grid":
                s = dict(e.stats)
                print("GRID", s["rows_real"], s["rows_padded"],
                      plan.n, plan.ell_pad, plan.m_pad)
"""


def test_grid_span_counts_real_and_padded_rows(subproc):
    out = subproc(UNEVEN, n_devices=2)
    grid = [ln.split()[1:] for ln in out.splitlines()
            if ln.startswith("GRID")]
    assert len(grid) == 1
    real, padded, n, ell_pad, m_pad = map(int, grid[0])
    assert (n, ell_pad, m_pad) == (2, 3, 2)
    assert real == 7 and padded == n * ell_pad * m_pad - 7 == 5
