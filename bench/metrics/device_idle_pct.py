"""Share of the traced window in which no op ran on the device (device
trace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
