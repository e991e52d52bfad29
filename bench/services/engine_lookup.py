"""Each batch's lookups through ``DHashEngine.lookup`` (a mix with no
updates and no rehash)."""
from __future__ import annotations

from bench import cells, table

step = cells.module("services", "engine_step")


class EngineLookup(step.EngineStep):
    def submit(self, b):
        return self.engine.lookup(b.look)


def build(cell, traffic, seed: int):
    if cell.traffic.get("rehash") or traffic.ops.inserts \
            or traffic.ops.deletes:
        raise ValueError(f"{cell.name}: the lookup entry takes no updates "
                         "and runs no rehash")
    eng, facts = table.engine(cell, traffic, seed)
    return EngineLookup(eng), facts
