"""Find a cell's pieces by name.

``BENCHMARK.json`` at the root of the checkout binds each cell to a
configuration and a traffic mix, and lists the per-layer metrics with the
cells they are read in.  Every piece is a file of its own under this
directory, named after its entry:

* ``configs/<config>.json``   the model's sizes, source, cuts and the
  repository architecture it runs as (``arch``, ``program``);
* ``traffic/<traffic>.json``  sequence length, the plan laid over the
  chips (per rank ell, m and state ratio), the pool of blocks, the
  generator's parameters and the optimizer;
* ``limits/<cell>.json``      the limits of ``correct``, with the readings
  they were set from;
* ``metrics/<metric>.py``     one reader per per-layer metric; a reader of
  a named scope declares it as ``SCOPE``, and a cell's ops are split by
  the scopes of its own readers (:func:`scopes`);
* ``flops/<family>.py``       FLOPs per token by part, and optionally
  bytes per token by part, of an architecture family;
* ``reference/<family>.py``   the family's plain reference, and the sizes
  the configuration fixes in the program (``program_sizes``);
* ``peaks.json``              peaks keyed by ``device_kind``.

A new cell, configuration, family, scope or metric is new files and new
entries, with no edit to a file that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

if HERE not in sys.path:
    sys.path.insert(0, HERE)


def _json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str):
    """Import a file by path (metric files have dots in their names)."""
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: Dict[str, Any] = None,
         here: str = HERE) -> Dict[str, Any]:
    """Everything one cell needs, found by the names in ``bench``."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = _json(os.path.join(here, "configs", w["config"] + ".json"))
    family = cfg["family"]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {
        "name": name,
        "chips": w["chips"],
        "config": cfg,
        "traffic": _json(os.path.join(here, "traffic",
                                      w["traffic"] + ".json")),
        "limits": _json(os.path.join(here, "limits", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": per_layer,
        "readers": {m["name"]: load_module(
            os.path.join(here, "metrics", m["name"] + ".py"))
            for m in per_layer},
        "flops": load_module(os.path.join(here, "flops", family + ".py")),
        "reference": load_module(os.path.join(here, "reference",
                                              family + ".py")),
    }


def scopes(readers: Dict[str, Any]) -> Tuple[str, ...]:
    """The named scopes a cell splits its ops by: the ``SCOPE`` of each
    of its readers that declares one."""
    return tuple(sorted({r.SCOPE for r in readers.values()
                         if hasattr(r, "SCOPE")}))


def peaks(device_kind: str, here: str = HERE) -> Dict[str, Any]:
    """Peaks of ``device_kind``; an unknown kind is an error."""
    table = _json(os.path.join(here, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def names(kind: str, here: str = HERE) -> List[str]:
    """Names of the files of one kind (``configs``, ``traffic``,
    ``metrics``, ``limits``), without their suffix."""
    return sorted(os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(here, kind))
                  if not f.startswith(("_", ".")))
