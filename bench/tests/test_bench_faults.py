"""A run with the timed path broken underneath comes out not correct, for
each fault a cell can have."""
from __future__ import annotations

import pytest

from bench.tests import rehearsal


class StateUnchanged:
    """The step answers, but its state is put back as it was."""

    def __init__(self, inner):
        self.inner = inner

    def submit(self, b):
        import jax
        import jax.numpy as jnp
        eng = self.inner.engine
        before = jax.tree_util.tree_map(jnp.copy, eng.state)
        h = self.inner.submit(b)
        eng.state = before
        return h

    def fetch(self, h):
        return self.inner.fetch(h)

    def live(self):
        return self.inner.live()


class HalfBatch(StateUnchanged):
    """Only the first half of each batch's lookups reach the program."""

    def submit(self, b):
        import dataclasses
        half = b.look.size // 2
        return self.inner.submit(dataclasses.replace(b, look=b.look[:half]))

    def fetch(self, h):
        import numpy as np
        a = self.inner.fetch(h)
        a.found = np.concatenate([a.found, np.zeros_like(a.found)])
        a.vals = np.concatenate([a.vals, np.zeros_like(a.vals)])
        return a


class AnswerAltered(StateUnchanged):
    """One answer of each batch is changed where it is produced."""

    def submit(self, b):
        return self.inner.submit(b)

    def fetch(self, h):
        a = self.inner.fetch(h)
        a.found = a.found.copy()
        a.found[0] = ~a.found[0]
        return a


def _cases():
    """Each cell with each fault it can have: a state left unchanged only
    where the cell's batches change the state."""
    for name in rehearsal.workloads():
        ops = rehearsal.traffic(name).ops
        if ops.inserts or ops.deletes:
            yield name, StateUnchanged
        yield name, HalfBatch
        yield name, AnswerAltered


@pytest.mark.parametrize("name, fault", list(_cases()))
def test_fault_is_not_correct(name, fault, monkeypatch):
    out = rehearsal.run(name, monkeypatch=monkeypatch, wrap=fault)
    assert not out["correct"], out["checks"]
