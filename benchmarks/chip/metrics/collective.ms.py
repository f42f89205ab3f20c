"""Device time per step in collective ops (all-gather, reduce-scatter,
all-reduce, and their start/done halves), averaged over the devices.
None where no collective ran (one chip)."""


def read(r, facts):
    coll = sum(c["collective"] for c in r.class_s) / r.chips
    if coll <= 0:
        return None
    return 1000.0 * coll / r.steps
