"""The client: a closed loop with two batches in flight.

It hands batch ``i + 1`` to the program's entry, then fetches batch ``i``'s
answers, as a double-buffered front end whose callers each wait for their
reply.  A batch's latency runs from the hand-off to the moment its answers
are on the host.  Each call is a host span (``bench.generate``,
``bench.submit``, ``bench.fetch``), timed on the host clock and, while a
trace runs, written into it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Window:
    batches: int
    ops: int
    t0: float                        # first hand-off (host clock, s)
    t1: float                        # last answers on the host
    latency: np.ndarray              # per batch, s
    submit: np.ndarray               # per batch, the entry call's span, s

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Reservoir:
    """A uniform sample of ``k`` batches' lookup answers, drawn from the
    seed (the same seed and batch count keep the same batches)."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.slots = k, 0, []
        self.rng = np.random.default_rng([seed, 0x5A])

    def offer(self, i: int, answers) -> None:
        if len(self.slots) < self.k:
            self.slots.append((i, answers))
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.slots[j] = (i, answers)
        self.seen += 1

    @property
    def items(self) -> dict:
        return dict(self.slots)


@dataclasses.dataclass
class Record:
    """What the check needs of every batch handed to the program."""

    lookups: Reservoir
    acks: dict = dataclasses.field(default_factory=dict)   # i -> (ins, del)
    batches: int = 0


def drive(service, traffic, record: Record, *, batches=None, seconds=None,
          annotate: bool = False) -> Window:
    """Run batches ``record.batches, ...`` until ``batches`` are done or
    ``seconds`` have passed since the first hand-off, then drain."""
    span = _annotation() if annotate else (lambda _: contextlib.nullcontext())
    first = record.batches
    lat, sub, ops = [], [], 0
    pending = None
    t0 = None

    def collect(p):
        i, h, t_hand = p
        with span("bench.fetch"):
            ans = service.fetch(h)
        t = time.perf_counter()
        lat.append(t - t_hand)
        if ans.ins_ok is not None:
            record.acks[i] = (ans.ins_ok, ans.del_ok)
        record.lookups.offer(i, (ans.found, ans.vals))
        return t

    t1 = None
    while True:
        i = record.batches
        if batches is not None and i - first >= batches:
            break
        if seconds is not None and t0 is not None \
                and time.perf_counter() - t0 >= seconds:
            break
        with span("bench.generate"):
            b = traffic.batch(i)
        t = time.perf_counter()
        t0 = t if t0 is None else t0
        with span("bench.submit"):
            h = service.submit(b)
        sub.append(time.perf_counter() - t)
        record.batches += 1
        ops += b.ops
        if pending is not None:
            t1 = collect(pending)
        pending = (i, h, t)
    if pending is not None:
        t1 = collect(pending)
    return Window(record.batches - first, ops, t0, t1,
                  np.asarray(lat), np.asarray(sub))


def _annotation():
    import jax
    return jax.profiler.TraceAnnotation
