"""Device busy time per batch: the union of the device's op intervals in
the traced window over the window's batches (device trace)."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return r.trace.busy_s / r.window.batches * 1e3
