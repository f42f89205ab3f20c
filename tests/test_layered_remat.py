"""Full rematerialization inside a layered-GA element works one microbatch
at a time.

Under ``remat="full"`` the backward of each element recomputes and
transposes one microbatch at a time, so no scan over the ℓ microbatches
stacks per-microbatch residuals; and the unit is still gathered once per
element outside that per-microbatch checkpoint, so the step's collective
bill (loop trip counts included) is that of ℓ = 1.
"""

import pytest

_CASE = r"""
import re
import jax
from repro.configs.base import get_arch
from repro.core.layered_ga import CephaloProgram
from repro.launch.mesh import make_mesh

ELL, M_, SEQ, D = 4, 1, 32, 64
cfg = get_arch(ARCH).reduced(n_layers=2, d_model=D)
mesh = make_mesh((2,), ("data",))


def program(ell):
    prog = CephaloProgram(cfg, mesh, ell=ell, m=M_, seq=SEQ, remat="full")
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.state_shapes().items()}
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in prog.batch_shapes().items()}
    return prog.build(), state, batch


def scans(jaxpr, out):
    # (length, stacked output shapes) of every scan, nested ones included
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            nc = e.params["num_carry"]
            out.append((e.params["length"],
                        [tuple(v.aval.shape) for v in e.outvars[nc:]]))
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    scans(sub, out)
    return out


COLL = ("all-gather", "reduce-scatter", "all-reduce")
COMP_RE = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
CALL_RE = re.compile(
    r"(?:body|calls|to_apply|condition)=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")


def weighted_collectives(hlo):
    # collective instructions executed per step: a while body counts its
    # known trip count times, every other callee once
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = COMP_RE.match(line)
        if m:
            cur = comps.setdefault(m.group(1), ([], []))
            if line.startswith("ENTRY"):
                entry = m.group(1)
            continue
        if cur is None or "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        for op in COLL:
            if re.search(r"\b%s(-start)?\(" % op, rhs):
                cur[0].append(op)
        trip = 1
        if " while(" in rhs:
            t = re.search(r'known_trip_count":\{"n":"(\d+)"', rhs)
            assert t, "while loop without a known trip count"
            trip = int(t.group(1))
        for c in CALL_RE.finditer(rhs):
            if c.group(1):
                cur[1].append((c.group(1),
                               trip if rhs[c.start():].startswith("body=")
                               else 1))
            else:
                cur[1].extend((n.strip().lstrip("%"), 1)
                              for n in c.group(2).split(","))

    def total(name):
        ops, calls = comps[name]
        out = {op: ops.count(op) for op in COLL}
        for callee, k in calls:
            for op, n in total(callee).items():
                out[op] += k * n
        return out

    return total(entry)


step, state, batch = program(ELL)
found = scans(jax.make_jaxpr(step)(state, batch).jaxpr, [])
allowed = {(ELL, M_, SEQ, D), (ELL,)}
over_ell = [outs for length, outs in found if length == ELL]
assert over_ell, "no scan over the microbatches"
stacked = [s for outs in over_ell for s in outs if s not in allowed]
assert not stacked, ("stacked per-microbatch residuals", stacked)

counts = {}
for ell in (1, ELL):
    step, state, batch = program(ell)
    hlo = jax.jit(step).lower(state, batch).compile().as_text()
    counts[ell] = weighted_collectives(hlo)
print(counts)
assert counts[1]["all-gather"] > 0 and counts[1]["reduce-scatter"] > 0
for op in ("all-gather", "reduce-scatter"):
    assert counts[ELL][op] == counts[1][op], (op, counts)
print("ALL-OK")
"""


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mamba2-370m"],
                         ids=["dense", "ssm"])
def test_microbatch_remat_stacks_nothing_and_gathers_once(arch, subproc):
    out = subproc(f"ARCH = {arch!r}\n" + _CASE, n_devices=2, timeout=600)
    assert "ALL-OK" in out
