"""The program's spans and scopes in a trace, to the per-layer metrics
that read them: on hand-made events, and on traces recorded on a TPU v5e
(an excerpt of a stablelm step and a whole mamba2 step), each split by its
cell's own scope set."""

import json
import lzma
import os

import pytest

import tiny
import registry
import program_trace as P

FIXTURE = os.path.join(tiny.HERE, "fixtures")
READERS = ("engine.host_ms", "engine.exposed_host_ms", "attention.ms",
           "mlp.ms", "ssd.ms", "ce.ms", "adam.ms", "unscoped.ms")
#: every scope a reader names: a cell's split by its own set has to equal
#: the split by all of them
ALL = ("attention", "mlp", "ssd", "ce", "adam")


def _reader(name):
    return registry.load_module(os.path.join(registry.HERE, "metrics",
                                             name + ".py"))


def _ev(op_names, devices=1):
    """One step 0..100 (sync to 120).  Host: grid 0..4, put 4..10,
    dispatch 10..12, loss wait 12..100, then the benchmark's sync.  The
    device idles 0..10 (under grid and put), runs ops 10..90 inside a
    while loop, and idles 90..120 (under the loss wait and the sync)."""
    spans = [("traffic.next", -5, 0), ("engine.step", 0, 100),
             ("spmd.step", 0, 100), ("spmd.grid", 0, 4), ("spmd.put", 4, 10),
             ("spmd.dispatch", 10, 12), ("spmd.loss_wait", 12, 100),
             ("sync", 100, 120)]
    raw = [["%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
            "condition=%cond, body=%body", 10, 90]] + [
        [f"%fusion.{i} = f32[8]{{0:T(1024)}} fusion(f32[8]{{0}} %p), "
         f"kind=kLoop, calls=%fused_computation.{i}", s, s + 20]
        for i, s in enumerate([10, 30, 50, 70])]
    return P.events_from_json({
        "devices": [raw] * devices,
        "op_names": {f"fusion.{i}": n for i, n in enumerate(op_names)},
        "spans": [list(s) for s in spans]})


NAMES = ["jit(step)/jvp()/while/body/closed_call/attention/dot_general",
         "jit(step)/transpose(jvp())/while/body/checkpoint/"
         "rematted_computation/attention/mul",
         "jit(step)/transpose(jvp(vmap(ce)))/closed_call/while/body/add",
         "jit(step)/while/body/dynamic_update_slice"]


def _cell_scopes(cell):
    return registry.scopes(registry.cell(cell)["readers"])


def test_scope_of_strips_transformations():
    assert [P.scope_of(n, ALL) for n in NAMES] == ["attention", "attention",
                                                   "ce", None]
    assert P.scope_of("jit(step)/adam/sub", ALL) == "adam"
    assert P.scope_of("jit(step)/jvp(jit(mlp_apply))/dot_general",
                      ALL) is None
    assert P.scope_of("", ALL) is None
    # the innermost scope of the set wins
    assert P.scope_of("jit(step)/ce/x/attention/y", ALL) == "attention"
    assert P.scope_of("jit(step)/ce/x/attention/y", ("ce",)) == "ce"
    assert P.scope_of("jit(step)/ce/x/attention/y", ()) is None


def test_nested_scope_takes_ops_only_where_listed():
    """A scope nested inside ``mlp`` takes its ops away from ``mlp`` in a
    set that lists it, and in no other."""
    names = ["jit(step)/mlp/router/dot_general",
             "jit(step)/transpose(jvp(mlp))/router/mul",
             "jit(step)/mlp/mul", "jit(step)/add"]
    ev = _ev(names)
    plain = P.reduce(ev, ("mlp",))
    assert plain.scope_ms("mlp") == pytest.approx(60e-6)
    assert plain.scope_ms("router") is None
    nested = P.reduce(ev, ("mlp", "router"))
    assert nested.scope_ms("router") == pytest.approx(40e-6)
    assert nested.scope_ms("mlp") == pytest.approx(20e-6)
    assert nested.unscoped_ms() == plain.unscoped_ms() == pytest.approx(
        20e-6)


def test_idle_under_put_counts_and_under_loss_wait_does_not():
    t = P.reduce(_ev(NAMES), ALL)
    assert t.steps == 1
    assert t.host_ms() == pytest.approx(12e-6)
    # 0..10 idle under grid and put; 90..100 under the loss wait and
    # 100..120 under sync are not the host's own work
    assert t.exposed_host_ms() == pytest.approx(10e-6)
    assert t.idle_ms_under("spmd.put") == pytest.approx(6e-6)
    assert t.idle_ms_under("spmd.loss_wait") == pytest.approx(10e-6)
    assert t.idle_ms_under("sync") == pytest.approx(20e-6)


def test_scopes_and_unscoped_add_up_to_busy():
    t = P.reduce(_ev(NAMES, devices=2), ALL)
    assert t.chips == 2
    assert t.scope_ms("attention") == pytest.approx(40e-6)
    assert t.scope_ms("ce") == pytest.approx(20e-6)
    assert t.unscoped_ms() == pytest.approx(20e-6)     # the container is out
    busy = sum(P.T._length(b) for b in t.busy) / t.chips / t.steps / 1e6
    total = sum(t.scope_ms(s) or 0 for s in ALL) + t.unscoped_ms()
    assert total == pytest.approx(busy)


def test_missing_names_read_none(monkeypatch):
    t = P.reduce(_ev(NAMES), ALL)
    assert t.scope_ms("mlp") is None and t.scope_ms("ssd") is None
    # a program without scopes or spans (the parent of this benchmark's
    # readers) reads None in every reader, and does not raise
    bare = P.reduce(_ev([""] * 4), ALL)
    bare.span_union = {k: v for k, v in bare.span_union.items()
                       if not k.startswith("spmd.")}
    facts = {"scopes": ALL}
    monkeypatch.setattr(P, "load", lambda path=None, scopes=(): bare)
    assert {n: _reader(n).read(None, facts) for n in READERS} == \
        dict.fromkeys(READERS)
    monkeypatch.setattr(P, "load", lambda path=None, scopes=(): t)
    got = {n: _reader(n).read(None, facts) for n in READERS}
    assert got["attention.ms"] == pytest.approx(40e-6)
    assert got["mlp.ms"] is None and got["engine.host_ms"] is not None


def test_no_trace_reads_none(tmp_path):
    assert P.load(str(tmp_path)) is None


def test_op_names_from_the_traces_hlo_protos(tmp_path):
    """The trace's metadata plane holds each module's HLO; its
    instructions' op_names carry the scopes, forward and backward."""
    import glob
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("attention"):
            return jnp.sum(jnp.sin(x) @ x)

    g = jax.jit(jax.grad(f))
    x = jnp.ones((16, 16))
    g(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        g(x).block_until_ready()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    with open(path, "rb") as fh:
        modules = P.hlo_op_names(fh.read())
    names = modules["jit_f"]
    scoped = {P.scope_of(n, ALL) for n in names.values()}
    assert "attention" in scoped
    assert any("transpose(" in n and P.scope_of(n, ALL) == "attention"
               for n in names.values())
    # the module that ran wins where two share an instruction name
    merged = P.merged_op_names({"other": {"sin.1": "x/mlp/sin"},
                                "mine": {"sin.1": "y/ce/sin", "cos": "c"}},
                               [[("%sin.1 = f32[] sine(f32[] %a)", 0, 1),
                                 ("%cos = f32[] cosine(f32[] %a)", 1, 2)]])
    assert merged == {"sin.1": "y/ce/sin", "cos": "c"}


def test_containers_by_their_hlo_text():
    assert P.is_container("%while.3 = (s32[], f32[2]{0}) while((s32[], "
                          "f32[2]{0}) %t), condition=%c, body=%b")
    assert P.is_container("%conditional.1 = f32[] conditional(%p, %a, %b)")
    assert not P.is_container("%fusion.1 = bf16[4,4]{1,0:T(8,128)(2,1)S(1)} "
                              "fusion(%a), kind=kLoop, calls=%f")


def _fixture(name):
    path = os.path.join(FIXTURE, name)
    with (lzma.open(path, "rt") if name.endswith(".xz") else open(path)) as f:
        return json.load(f)


def test_recorded_v5e_excerpt():
    """The first 1200 ops of a stablelm-1.6b-l4.seq2k step on a v5e, with
    the op_names of the trace's HLO proto, split by that cell's scope set:
    the scopes, forward and backward, and the unscoped ops add up to the
    busy time; the idle time before the first op falls under the
    transfer; the numbers are those the reduction by the five fixed scopes
    gave when the fixture was recorded, and adding the scope of another
    cell (``ssd``) moves none of them."""
    fx = _fixture("v5e_stablelm_l4_scopes.json")
    scopes = _cell_scopes("stablelm-1.6b-l4.seq2k")
    assert scopes == ("adam", "attention", "ce", "mlp")
    t = P.reduce(P.events_from_json(fx), scopes)
    assert t.chips == 1 and t.steps == 1
    assert t.scope_ms("attention") == pytest.approx(25.90046)
    assert t.scope_ms("mlp") == pytest.approx(6.518042)
    assert t.scope_ms("ce") == pytest.approx(39.241396)
    assert t.scope_ms("ssd") is None and t.scope_ms("adam") is None
    assert t.unscoped_ms() == pytest.approx(42.477405)
    busy = P.T._length(t.busy[0]) / 1e6
    total = sum(t.scope_ms(s) or 0 for s in scopes) + t.unscoped_ms()
    assert total == pytest.approx(busy, rel=1e-3)
    assert t.host_ms() == pytest.approx(1.896269)
    assert t.exposed_host_ms() == pytest.approx(0.422576)
    assert P.reduce(P.events_from_json(fx), ALL).scope_ns == t.scope_ns
    backward = [n for n in fx["op_names"].values()
                if "transpose(" in n and P.scope_of(n, scopes) == "attention"]
    assert backward


def test_recorded_v5e_mamba2_step():
    """A whole step of mamba2-370m.seq4k on a v5e (``record_fixture.py``),
    split by that cell's scope set: the numbers are those the reduction by
    the five fixed scopes gives; no op carries ``attention`` or ``mlp``, so
    the fixed set and the cell's split alike."""
    fx = _fixture("v5e_mamba2_370m_step.json.xz")
    scopes = _cell_scopes("mamba2-370m.seq4k")
    assert scopes == ("adam", "ce", "ssd")
    ev = P.events_from_json(fx)
    t = P.reduce(ev, scopes)
    assert t.chips == 1 and t.steps == 1
    assert t.scope_ns == [{"ssd": 623853808, "ce": 66586707,
                           "adam": 20447724, None: 309531971}]
    assert t.scope_ms("ssd") == pytest.approx(623.853808)
    assert t.unscoped_ms() == pytest.approx(309.531971)
    assert t.scope_ms("attention") is None and t.scope_ms("mlp") is None
    busy = P.T._length(t.busy[0]) / 1e6
    assert busy == pytest.approx(1021.303964)
    total = sum(t.scope_ms(s) for s in scopes) + t.unscoped_ms()
    assert total == pytest.approx(busy, rel=2e-3)
    assert t.host_ms() == pytest.approx(1.89732)
    assert t.exposed_host_ms() == pytest.approx(0.641331)
    assert P.reduce(ev, ALL).scope_ns == t.scope_ns
