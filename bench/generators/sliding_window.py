"""A sliding live window (the traffic of ``chip_smoke.py``).

The table holds ``width`` live ids ``[lo, lo + width)`` (``width`` is the
configuration's keys, over all shards).  Each batch inserts the next
``inserts - duplicate_inserts`` ids plus ``duplicate_inserts`` in-batch
duplicates of them (with other values: set semantics refuses them), and
deletes the oldest as many ids plus ``absent_deletes`` ids deleted before
(refused), so the live set keeps its size.  A mix with no updates never
slides.  Lookups fall in ``lookup_range`` (in units of ``width``, relative
to ``lo``: ``[-0.5, 1.5]`` reaches half a window of deleted and of future
ids), drawn by the module ``bench/distributions/<distribution>.py``.

The mix's parameters, per chip and batch: ``lookups``, ``inserts``,
``deletes``, ``duplicate_inserts``, ``absent_deletes``; and
``lookup_range``, ``distribution``.

The random part of the lookups is drawn from the seed before any batch, as
``POOL_BATCHES`` batches of offsets that later batches reuse in turn, so
batch ``i`` is a pure function of the seed and ``i`` and every seed gives
the same sizes.
"""
from __future__ import annotations

import numpy as np

from bench import cells, roofline
from bench.keys import DUP_VAL, Batch, Keys

POOL_BATCHES = 64


class Traffic:
    """Batch ``i`` of a mix over ``shards`` chips of ``keys`` live keys."""

    def __init__(self, params: dict, keys: int, seed: int, *,
                 shards: int = 1):
        self.shards = shards
        n = {k: int(params.get(k, 0)) for k in (
            "lookups", "inserts", "deletes", "duplicate_inserts",
            "absent_deletes")}
        self.ops = roofline.Ops(
            lookups=n["lookups"], inserts=n["inserts"], deletes=n["deletes"],
            inserts_acked=n["inserts"] - n["duplicate_inserts"],
            deletes_acked=n["deletes"] - n["absent_deletes"])
        if self.ops.inserts_acked != self.ops.deletes_acked:
            raise ValueError("the live set would not keep its size: "
                             f"{n}")
        self.dup, self.absent = n["duplicate_inserts"], n["absent_deletes"]
        self.width = keys * shards
        self.ids = Keys(seed)
        self.fresh = shards * self.ops.inserts_acked
        lo, hi = params.get("lookup_range", (0.0, 1.0))
        self.look_lo = int(round(lo * self.width))
        span = int(round((hi - lo) * self.width))
        draw = cells.module("distributions",
                            params.get("distribution", "uniform")).draw
        size = POOL_BATCHES * shards * n["lookups"]
        self.offs = draw(np.random.default_rng([seed, 0x10]), size,
                         span).astype(np.int64).reshape(POOL_BATCHES, -1)

    def populate_range(self) -> tuple[int, int]:
        """The ids the table holds after set-up: ``[width, 2 width)``."""
        return self.width, 2 * self.width

    def batch(self, i: int) -> Batch:
        s = self.shards
        lo = self.width + i * self.fresh
        hi = lo + self.width
        look = lo + self.look_lo + self.offs[i % POOL_BATCHES]
        new = np.arange(hi, hi + self.fresh, dtype=np.int64)
        dup = new[: s * self.dup]
        ins = np.concatenate([new, dup])
        iv = np.concatenate([self.ids.val(new),
                             self.ids.val(dup) ^ np.int32(DUP_VAL)])
        dels = np.concatenate([
            np.arange(lo, lo + self.fresh, dtype=np.int64),
            np.arange(lo - s * self.absent, lo, dtype=np.int64)])
        return Batch(look, ins, iv, dels, self.ids.key(look),
                     self.ids.key(ins), self.ids.key(dels))
