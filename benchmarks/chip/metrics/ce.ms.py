"""Device time per step in ops of the program's ``ce`` scope
(``chunked_ce``: the head projection and the chunked cross-entropy), in
the forward, the backward and the recomputed forward alike, averaged
over the devices.  None where no op carries the scope."""

import program_trace


def read(r, facts):
    t = program_trace.load()
    return t.scope_ms("ce") if t else None
