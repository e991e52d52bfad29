"""Operations answered per second: every lookup, insert and delete of the
window's batches over the window, from the first hand-off to the last
answers on the host (host clock)."""


def read(r):
    return r.window.ops / r.window.seconds
