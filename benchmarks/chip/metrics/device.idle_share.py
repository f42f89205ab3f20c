"""Share of the traced window in which no op ran on a device, averaged
over the devices: 100 x (1 - busy / window), busy being the union of the
intervals of the device's ops.  It also shows the engine's host work:
the device waits while the host builds the grid and syncs on the loss."""


def read(r, facts):
    return 100.0 * (1.0 - sum(r.busy_s) / len(r.busy_s) / r.window_s)
