"""Keys, values, batches and the plain reference model of the table's
semantics.

Every id maps to a key and a value by seeded bijections of uint32, so every
key is distinct and every looked-up value is known.  The model is plain
numpy over the id universe (a presence bitmap and a value array) with set
semantics: the first occurrence of an id in a batch wins.  It shares no code
with the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KEY_MUL, VAL_MUL = 0x9E3779B1, 0x85EBCA77
VAL_SALT = 0x165667B1
DUP_VAL = 0x5BD1E995          # xor that marks a duplicate insert's value


def salt_of(seed: int) -> int:
    """The uint32 salt of a seed (any whole number, however large)."""
    return (seed * 0x2545F491 + 0x3C6EF372) & 0xFFFFFFFF


def _mix(x: np.ndarray, mul: int, add: int) -> np.ndarray:
    """A bijection of uint32 (odd multiply, add, xor-shift)."""
    x = x.astype(np.uint32) * np.uint32(mul) + np.uint32(add)
    return x ^ (x >> np.uint32(16))


class Keys:
    """Key and value of each id, as int32."""

    def __init__(self, seed: int):
        self.salt = salt_of(seed)

    def key(self, ids: np.ndarray) -> np.ndarray:
        return _mix(ids, KEY_MUL, self.salt).view(np.int32)

    def val(self, ids: np.ndarray) -> np.ndarray:
        return _mix(ids, VAL_MUL, self.salt ^ VAL_SALT).view(np.int32)


@dataclasses.dataclass
class Batch:
    """One batch of operations, as ids (for the model) and as int32 keys and
    values (for the program)."""

    look_ids: np.ndarray
    ins_ids: np.ndarray
    ins_vals: np.ndarray
    del_ids: np.ndarray
    look: np.ndarray
    ins: np.ndarray
    dels: np.ndarray

    @property
    def ops(self) -> int:
        return self.look.size + self.ins.size + self.dels.size


class Model:
    """The reference: presence and value of every id seen so far."""

    def __init__(self, universe: int = 0):
        self.present = np.zeros(universe, bool)
        self.value = np.zeros(universe, np.int32)

    def _grow(self, ids: np.ndarray) -> None:
        top = int(ids.max(initial=-1)) + 1
        if top > self.present.size:
            n = max(top, 2 * self.present.size)
            self.present = np.concatenate(
                [self.present, np.zeros(n - self.present.size, bool)])
            self.value = np.concatenate(
                [self.value, np.zeros(n - self.value.size, np.int32)])

    def fill(self, lo: int, hi: int, keys: Keys) -> None:
        """Ids ``[lo, hi)`` present with their values (the populate)."""
        ids = np.arange(lo, hi, dtype=np.int64)
        self._grow(ids)
        self.present[lo:hi] = True
        self.value[lo:hi] = keys.val(ids)

    def insert(self, ids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        self._grow(ids)
        ok = np.zeros(ids.shape, bool)
        _, first = np.unique(ids, return_index=True)
        ok[first] = ~self.present[ids[first]]
        self.present[ids[ok]] = True
        self.value[ids[ok]] = vals[ok]
        return ok

    def delete(self, ids: np.ndarray) -> np.ndarray:
        self._grow(ids)
        ok = np.zeros(ids.shape, bool)
        _, first = np.unique(ids, return_index=True)
        ok[first] = self.present[ids[first]]
        self.present[ids[ok]] = False
        return ok

    def lookup(self, ids: np.ndarray):
        self._grow(ids)
        found = self.present[ids]
        return found, np.where(found, self.value[ids], 0)

    def live(self) -> int:
        return int(self.present.sum())
