"""Device time per step in ops of the program's ``adam`` scope (the Adam
update of every unit's shard, after the gradients), averaged over the
devices.  None where no op carries the scope."""

import program_trace

SCOPE = "adam"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
