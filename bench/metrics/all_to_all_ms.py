"""Device ms per batch of the all-to-all exchanges, averaged over the
chips: the traced window's ops whose names the chip gives its all-to-alls
(``all-to-all``, its async ``-start``/``-done`` halves), over the window's
batches (device trace).  Read only where such ops ran.  The names are
matched anywhere in the op's name: ``Reduction.all_to_all_s`` counts only
names that start with ``all-to-all`` and reads 0 on four v5e chips."""


def read(r):
    if r.trace is None:
        return None
    s = sum(v for k, v in r.trace.ops_s.items()
            if "all-to-all" in k.lower() or "all_to_all" in k.lower())
    if s <= 0:
        return None
    return s / r.window.batches * 1e3
