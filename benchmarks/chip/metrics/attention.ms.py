"""Device time per step in ops of the program's ``attention`` scope
(``attention_apply``: the q, k, v and output projections, rotary and the
attention itself), in the forward, the backward and the recomputed
forward alike, averaged over the devices.  None where no op carries the
scope."""

import program_trace

SCOPE = "attention"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
