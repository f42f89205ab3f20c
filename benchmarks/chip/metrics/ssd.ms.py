"""Device time per step in ops of the program's ``ssd`` scope
(``ssd_apply``: the whole Mamba2 mixer, its projections, conv, chunked
SSD and gated norm), in the forward, the backward and the recomputed
forward alike, averaged over the devices.  None where no op carries the
scope."""

import program_trace

SCOPE = "ssd"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
