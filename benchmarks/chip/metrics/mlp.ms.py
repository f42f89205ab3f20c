"""Device time per step in ops of the program's ``mlp`` scope (``_ffn``:
SwiGLU or MoE), in the forward, the backward and the recomputed forward
alike, averaged over the devices.  None where no op carries the scope."""

import program_trace


def read(r, facts):
    t = program_trace.load()
    return t.scope_ms("mlp") if t else None
