"""Device time per step in ops that carry none of the program's scopes:
the embedding, norms and residual adds, the unit gathers, the layered-GA
scans' stacking of per-microbatch results, the loss psum.  Averaged over
the devices.  None where no op carries any scope (a program without
them)."""

import program_trace


def read(r, facts):
    t = program_trace.load()
    return t.unscoped_ms() if t else None
