"""Device time per step in ops of the program's ``unit_gather`` scope
(each unit's AllGather with its cast and the reassembly of the flat
buffer, in the forward and again where the backward recomputes it),
averaged over the devices.  None where no op carries the scope."""

import program_trace

SCOPE = "unit_gather"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
