"""Device time per step in ops of the program's ``ce`` scope
(``chunked_ce``: the head projection and the chunked cross-entropy), in
the forward, the backward and the recomputed forward alike, averaged
over the devices.  None where no op carries the scope."""

import program_trace

SCOPE = "ce"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
