"""Faults planted underneath the timed path, for the tests and for
``calibrate.py``: each breaks the program in one way that ``correct``
has to catch.

* ``stale_state``   the step returns the state it was given;
* ``half_batch``    half of the real rows get no weight and the rest
  twice theirs, so the loss is the mean over the other half;
* ``row_altered``   the tokens of one row are changed (each id + 1) where
  the grid is built, so the program trains on other data than the
  reference;
* ``no_exchange``   the gradient's ReduceScatter is left out: each chip
  keeps its own, unreduced, rows of every unit's gradient.
"""

from __future__ import annotations

import contextlib

import numpy as np

FAULTS = ("stale_state", "half_batch", "row_altered", "no_exchange")


def _real_rows(w: np.ndarray):
    """Indices (rank, ell, m) of the grid's rows that carry weight."""
    return [idx for idx in np.ndindex(w.shape[:3]) if w[idx].any()]


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` for the duration of the block.  Engines built
    before ``no_exchange`` is planted keep their compiled step: build the
    engine inside the block."""
    import jax
    import jax.numpy as jnp
    from repro.core import fsdp
    from repro.core.engine import api
    from repro.data import pipeline

    saved = []

    def patch(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "stale_state":
        step = api.SpmdEngine.step

        def stale(self, state, big):
            _, loss = step(self, jax.tree.map(jnp.copy, state), big)
            return state, loss
        patch(api.SpmdEngine, "step", stale)
    elif name in ("half_batch", "row_altered"):
        grid_of = pipeline.plan_grid_from_block

        def broken(plan, big):
            grid = {k: v.copy() for k, v in grid_of(plan, big).items()}
            rows = _real_rows(grid["weights"])
            if name == "half_batch":
                k = len(rows) // 2
                for idx in rows[k:]:
                    grid["weights"][idx] = 0.0
                for idx in rows[:k]:
                    grid["weights"][idx] *= 2.0
            else:
                grid["tokens"][rows[0]] += 1
            return grid
        patch(pipeline, "plan_grid_from_block", broken)
    elif name == "no_exchange":
        def local_rows(layout, grad_flat, axis_names):
            rows = [jnp.pad(grad_flat[o: o + s], (0, layout.p_max - s))
                    for o, s in zip(layout.offsets(), layout.shard_sizes)]
            return jnp.stack(rows)[jax.lax.axis_index(axis_names)]
        patch(fsdp, "scatter_grad", local_rows)
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)
