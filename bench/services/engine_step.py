"""Each batch through ``DHashEngine.step``: its lookups, inserts and deletes
in that order, and one rebuild transition while a rehash runs."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import table


@dataclasses.dataclass
class Answers:
    found: np.ndarray
    vals: np.ndarray
    ins_ok: np.ndarray | None = None
    del_ok: np.ndarray | None = None


class EngineStep:
    def __init__(self, engine):
        self.engine = engine

    def submit(self, b):
        return self.engine.step(b.look, b.ins, b.ins_vals, b.dels)

    def fetch(self, handle) -> Answers:
        import jax
        return Answers(*jax.device_get(handle))

    def live(self) -> int:
        return self.engine.count()


def build(cell, traffic, seed: int):
    eng, facts = table.engine(cell, traffic, seed)
    return EngineStep(eng), facts
