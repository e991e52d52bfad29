"""Seconds from process start to the first timed batch (host clock)."""


def read(r):
    return r.setup_s
