"""Device time per step in ops of the program's ``unit_scatter`` scope
(the casts and the per-rank rows around each unit's gradient exchange,
in the backward), averaged over the devices.  On TPU v5e the exchange
itself (a ``psum_scatter`` of the (N, P_max) rows) compiles to an
all-reduce that carries no op name, so it is not counted here but in
``collective.ms``; where a compiler keeps the ReduceScatter's name it
counts here too.  None where no op carries the scope."""

import program_trace

SCOPE = "unit_scatter"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
