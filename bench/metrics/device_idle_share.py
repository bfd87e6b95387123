"""Share of the traced window in which no operation ran on the device
(1 minus the union of device-op intervals), averaged over the chips."""


def read(r):
    if r.trace is None or r.trace.devices == 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
