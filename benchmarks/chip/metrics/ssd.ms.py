"""Device time per step in ops of the program's ``ssd`` scope
(``ssd_apply``: the whole Mamba2 mixer, its projections, conv, chunked
SSD and gated norm), in the forward, the backward and the recomputed
forward alike, averaged over the devices.  None where no op carries the
scope."""

import program_trace


def read(r, facts):
    t = program_trace.load()
    return t.scope_ms("ssd") if t else None
