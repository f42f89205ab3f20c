"""Host time per step in the engine's own work: the union of its
``spmd.grid``, ``spmd.put`` and ``spmd.dispatch`` spans (the grid, the
transfer, the enqueue of the step), the wait for the loss left out.
None where the program writes no such span."""

import program_trace


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.host_ms() if t else None
