"""Device idle time per step that falls inside the engine's own host work
(``spmd.grid``, ``spmd.put`` or ``spmd.dispatch``), averaged over the
devices: what the host's work per step costs the chip.  None where the
program writes no such span."""

import program_trace


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.exposed_host_ms() if t else None
