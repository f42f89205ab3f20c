"""Device time per step in ops that carry none of the cell's scopes (the
``SCOPE`` of each reader listed for the cell): the embedding, norms and
residual adds, the unit gathers, the layered-GA scans' stacking of
per-microbatch results, the loss psum.  Averaged over the devices.  None
where no op carries any of them (a program without them)."""

import program_trace


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.unscoped_ms() if t else None
