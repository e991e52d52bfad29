"""Mean host time of the client's call into the program's entry
(``DHashEngine.step`` / ``.lookup``: transfer, dispatch and the engine's
one-in-32 poll), over the traced window's batches (host clock)."""


def read(r):
    if r.trace is None:
        return None
    return float(r.window.submit.mean()) * 1e3
