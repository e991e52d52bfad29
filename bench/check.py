"""The comparison that decides ``correct``: the program's answers against
the plain model (``bench/keys.py``), replayed over the same batches once
the window has closed.

Compared, each as a count of wrong answers with the limit 0 (the answers
are exact):

* ``populate_refused``: set-up inserts of distinct new keys not
  acknowledged;
* ``found_wrong`` / ``value_wrong``: the lookup answers of a seeded sample
  of batches (all of them where the run has fewer than the mix's
  ``checked_batches``); a value is compared where the model finds the key;
* ``insert_ack_wrong`` / ``delete_ack_wrong``: every batch's insert and
  delete acknowledgements (set semantics, the first occurrence in a batch
  winning, lookups before inserts before deletes);
* ``live_gap``: the live keys the program counts at the end against the
  model's.
"""
from __future__ import annotations

import numpy as np

from bench.keys import Model

LIMIT = 0


def compare(traffic, record, populate_acked: int, live: int) -> dict:
    """``({name: (number, limit)}, the model's live keys)`` of every
    number compared."""
    model = Model()
    lo, hi = traffic.populate_range()
    model.fill(lo, hi, traffic.ids)
    looked = record.lookups.items
    wrong = dict.fromkeys(("found_wrong", "value_wrong", "insert_ack_wrong",
                           "delete_ack_wrong"), 0)
    for i in range(record.batches):
        b = traffic.batch(i)
        if i in looked:
            f, v = looked[i]
            ef, ev = model.lookup(b.look_ids)
            wrong["found_wrong"] += _wrong(f, ef)
            wrong["value_wrong"] += _wrong(v, ev, ef)
        if b.ins_ids.size or b.del_ids.size:
            ei = model.insert(b.ins_ids, b.ins_vals)
            ed = model.delete(b.del_ids)
            oi, od = record.acks.get(i, (None, None))   # None: never came
            wrong["insert_ack_wrong"] += _wrong(oi, ei)
            wrong["delete_ack_wrong"] += _wrong(od, ed)
    numbers = {"populate_refused": (hi - lo) - populate_acked, **wrong,
               "live_gap": abs(live - model.live())}
    return {k: (v, LIMIT) for k, v in numbers.items()}, model.live()


def _wrong(got, want, where=True) -> int:
    """Answers in ``got`` that differ from ``want`` (where ``where``); all
    of them when they never came or came in another shape."""
    if got is None or got.shape != want.shape:
        return int(np.broadcast_to(where, want.shape).sum())
    return int(((got != want) & where).sum())


def passed(checks: dict) -> bool:
    return all(v <= limit for v, limit in checks.values())
