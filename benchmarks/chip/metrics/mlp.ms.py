"""Device time per step in ops of the program's ``mlp`` scope (``_ffn``:
SwiGLU or MoE), in the forward, the backward and the recomputed forward
alike, averaged over the devices.  None where no op carries the scope."""

import program_trace

SCOPE = "mlp"


def read(r, facts):
    t = program_trace.load(scopes=facts["scopes"])
    return t.scope_ms(SCOPE) if t else None
