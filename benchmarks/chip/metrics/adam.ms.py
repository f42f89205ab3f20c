"""Device time per step in ops of the program's ``adam`` scope (the Adam
update of every unit's shard, after the gradients), averaged over the
devices.  None where no op carries the scope."""

import program_trace


def read(r, facts):
    t = program_trace.load()
    return t.scope_ms("adam") if t else None
