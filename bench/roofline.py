"""Peaks by ``device_kind`` and the bytes a batch necessarily moves.

The bytes come from the cell's shapes and op counts alone (slots, probe
bound, rebuild chunk, batch sizes, whether a rehash is in flight), never
from which op set or kernels ran, so a roofline share reads the same work
whatever implements it.  Counted, per chip and batch:

* each query's probe window: ``window_rows(max_probes)`` rows of 128 slots
  of the key and the state array, plus one row of the value array for a
  lookup, in every table the op consults (old and new during a rehash for
  lookups and deletes; an insert probes only the table it writes);
* the row of each array a claim writes (key, value, state) per
  acknowledged insert, and the state row of each acknowledged delete;
* the rebuild transition, half an extract (the chunk read, its states
  marked, the hazard buffer written) and half a landing (each landed entry
  probes and claims in the new table) per batch;
* the hazard buffer read by lookups and deletes during a rehash;
* the batch's inputs and outputs.

Full-table selects and copies are not counted: no operation needs them.
"""
from __future__ import annotations

import dataclasses

LANES, WORD = 128, 4
ROW = LANES * WORD              # bytes of one 128-slot row of an i32 array
HAZARD_ENTRY = 2 * WORD + 1     # hazard key, value (i32) and live bit


@dataclasses.dataclass(frozen=True)
class Peak:
    hbm_bytes_per_s: float
    hbm_bytes: float
    bf16_flops_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(819e9, 16e9, 197e12,
                        "Google Cloud documentation, TPU v5e"),
}


def peak(device_kind: str) -> Peak:
    """The peaks of a device; a device not in the table is an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Ops:
    """A batch's operations on one chip, and how many of its updates the
    table acknowledges (the rest are refused by set semantics)."""

    lookups: int
    inserts: int
    deletes: int
    inserts_acked: int
    deletes_acked: int


@dataclasses.dataclass(frozen=True)
class TableShape:
    slots: int
    max_probes: int
    chunk: int


def table_shape(state) -> TableShape:
    """The shape of a linear-probing DHash state (one shard)."""
    return TableShape(int(state.old.capacity), int(state.old.max_probes),
                      int(state.chunk))


def window_rows(max_probes: int) -> int:
    """128-slot rows that hold a probe of ``max_probes`` slots wherever in
    its row it starts."""
    return -(-(LANES - 1 + max_probes) // LANES)


def batch_bytes(shape: TableShape, ops: Ops, keys: int,
                rehash: bool) -> int:
    """Necessary HBM bytes of one batch of ``ops`` on one chip holding
    ``keys`` live keys in a table of ``shape``, with or without a rehash in
    flight."""
    probe = 2 * window_rows(shape.max_probes) * ROW       # key + state
    tables = 2 if rehash else 1
    hazard = shape.chunk * HAZARD_ENTRY if rehash else 0
    total = ops.lookups * tables * (probe + ROW) + hazard
    total += ops.inserts_acked * (probe + 3 * ROW)
    total += ops.deletes * tables * probe + ops.deletes_acked * ROW
    total += hazard if ops.deletes else 0
    if rehash:
        landed = shape.chunk * keys // shape.slots
        extract = shape.chunk * (3 * WORD + WORD) + hazard
        land = landed * (probe + 3 * ROW) + hazard
        total += (extract + land) // 2
    total += ops.lookups * (WORD + WORD + 1)
    total += ops.inserts * (2 * WORD + 2) + ops.deletes * (WORD + 2)
    return total
