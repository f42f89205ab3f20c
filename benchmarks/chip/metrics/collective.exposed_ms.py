"""The part of collective.ms during which no other op ran on that device,
per step, averaged over the devices.  None where no collective ran."""


def read(r, facts):
    if sum(c["collective"] for c in r.class_s) <= 0:
        return None
    return 1000.0 * sum(r.exposed_collective_s) / r.chips / r.steps
