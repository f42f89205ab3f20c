"""The reduction from a trace to the per-layer metrics: on hand-made
events, and on an excerpt of a trace recorded on a TPU v5e."""

import json
import os

import pytest

import tiny
import registry
import trace_reduce as T

FIXTURE = os.path.join(tiny.HERE, "fixtures")

HLO = """
HloModule jit_step

%fused_dot (p0: bf16[4,4], p1: bf16[4,4]) -> bf16[4,4] {
  %p0 = bf16[4,4]{1,0} parameter(0)
  %p1 = bf16[4,4]{1,0} parameter(1)
  ROOT %dot.1 = bf16[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}
}

%fused_add (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %add.2 = f32[4]{0} add(%p0, %p0)
}

ENTRY %main (a: bf16[4,4]) -> (f32[4], bf16[4,4]) {
  %a = bf16[4,4]{1,0} parameter(0)
  %fusion.1 = bf16[4,4]{1,0} fusion(%a, %a), kind=kOutput, calls=%fused_dot
  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_add
  %all-gather-start.3 = (f32[4]{0}, f32[16]{0}) all-gather-start(%fusion.2), dimensions={0}
  %all-gather-done.3 = f32[16]{0} all-gather-done(%all-gather-start.3)
  %convolution.4 = bf16[4,4]{1,0} convolution(%a, %a), dim_labels=bf_io->bf
  ROOT %tuple = (f32[4]{0}, bf16[4,4]{1,0}) tuple(%fusion.2, %fusion.1)
}
"""


def test_op_classes():
    c = T.op_classes(HLO)
    assert c["fusion.1"] == "dot"
    assert c["fusion.2"] == "other"
    assert c["all-gather-start.3"] == "collective"
    assert c["all-gather-done.3"] == "collective"
    assert c["convolution.4"] == "dot"
    assert c["dot.1"] == "dot"


def _events():
    # window: engine.step at 0..100, sync ends at 120; device 0 runs a dot
    # 10..50, a collective 40..70 (exposed 50..70), other 80..90
    ops = [("fusion.1", 10, 50), ("all-gather-done.3", 40, 70),
           ("fusion.2", 80, 90), ("fusion.2", 200, 300)]
    spans = [("traffic.next", -5, 0), ("engine.step", 0, 100),
             ("sync", 100, 120)]
    return T.Events([ops], spans)


def test_reduce_by_hand():
    r = T.reduce(_events(), T.op_classes(HLO))
    assert r.window_s == pytest.approx(120e-9)
    assert r.steps == 1
    assert r.busy_s == [pytest.approx(70e-9)]           # 10..70, 80..90
    assert r.class_s[0]["dot"] == pytest.approx(40e-9)
    assert r.class_s[0]["collective"] == pytest.approx(30e-9)
    assert r.exposed_collective_s == [pytest.approx(20e-9)]
    # idle: 0..10 and 70..80 in engine.step; 90..120, mostly in sync
    assert r.idle_gaps[0] == ("sync", pytest.approx(30e-9))
    assert r.idle_gaps[1] == ("engine.step", pytest.approx(10e-9))
    assert sum(g for _, g in r.idle_gaps) == pytest.approx(50e-9)


def test_readers_by_hand():
    r = T.reduce(_events(), T.op_classes(HLO))
    facts = {"flops_per_step": 2e3, "peak_flops_per_s": 1e12, "scopes": ()}
    m = {n: registry.load_module(os.path.join(registry.HERE, "metrics",
                                              n + ".py")).read(r, facts)
         for n in registry.names("metrics")}
    assert m["device.idle_share"] == pytest.approx(100 * 50 / 120)
    assert m["matmul_roofline"] == pytest.approx(100 * 2e3 / (1e12 * 40e-9))
    assert m["step_mfu"] == pytest.approx(100 * 2e3 / (1e12 * 120e-9))
    assert m["collective.ms"] == pytest.approx(30e-6)
    assert m["collective.exposed_ms"] == pytest.approx(20e-6)


def test_no_collective_reads_nothing():
    ev = _events()
    ev.devices[0] = [o for o in ev.devices[0] if "all-gather" not in o[0]]
    r = T.reduce(ev, T.op_classes(HLO))
    for n in ("collective.ms", "collective.exposed_ms"):
        mod = registry.load_module(os.path.join(registry.HERE, "metrics",
                                                n + ".py"))
        assert mod.read(r, {}) is None


def test_recorded_v5e_excerpt():
    """One step of stablelm-1.6b-l4.seq2k on a v5e, cut after 3000 ops:
    while loops cover their bodies and count for busy only; every other
    op falls in one class; the numbers are those the reduction gave when
    the fixture was recorded."""
    with open(os.path.join(FIXTURE, "v5e_stablelm_l4_step.json")) as f:
        fx = json.load(f)
    r = T.reduce(T.events_from_json(fx), fx["classes"])
    assert r.chips == 1 and r.steps == 1
    assert r.window_s == pytest.approx(0.172384475)
    assert r.busy_s[0] == pytest.approx(0.171856442)
    assert r.class_s[0]["dot"] == pytest.approx(0.086740665)
    assert r.class_s[0]["other"] == pytest.approx(0.08507882)
    assert r.class_s[0]["collective"] == 0.0
    assert sum(r.class_s[0].values()) <= r.busy_s[0] * (1 + 1e-9)
    assert r.top_ops[0] == ("select_add_fusion.9", pytest.approx(0.012743537))
    assert "container" in fx["classes"].values()
    facts = {"flops_per_step": 0.5e12, "peak_flops_per_s": 197e12}
    roof = registry.load_module(os.path.join(
        registry.HERE, "metrics", "matmul_roofline.py")).read(r, facts)
    assert 0 < roof <= 100
