"""The table hash-partitioned over four devices, served through
``DHashEngine``, against a plain dict oracle (four virtual CPU devices in a
subprocess, so the device-count flag never reaches other tests).

The oracle shares no code with ``core/``: a dict with set semantics, the
first occurrence of a key in a batch winning, lookups before inserts
before deletes.  Like ``test_differential.py`` the batches never re-insert
a live key (mid-rehash that is the paper's new-copy-wins corner).  Each
case checks the engine's answers batch by batch:

* ``swaps``: uniform batches through two epoch swaps at the poll;
* ``cross_chip_duplicates``: a key twice in one insert (and one delete)
  batch, the copies in the slices of different devices: the earlier wins;
* ``full_skew``: a batch whose keys all belong to one shard is served
  whole, and the poll reads that owner's load;
* the live count against the oracle's size, in every case.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import distributed as dd, hashing
from repro.core.engine import DHashEngine

CASE = sys.argv[1]
S, KEYS, CHUNK, POLL = 4, 1024, 64, 4
Q, U = 64, 16                      # per device: lookups, inserts / deletes
rng = np.random.default_rng(7)
owner = hashing.fresh("mix32", 11)
sharding = NamedSharding(Mesh(np.array(jax.devices()[:S]), ("shard",)),
                         P("shard"))
eng = DHashEngine(dd.make_stacked(S, "linear", capacity=KEYS, chunk=CHUNK,
                                  seed=3, sharding=sharding),
                  continuous_rebuild=True, rebuild_seed=5, poll_every=POLL,
                  owner=owner)


class Oracle:
    def __init__(self):
        self.d = {}

    def step(self, look, ins, iv, dels):
        found = [k in self.d for k in look]
        vals = [self.d.get(k, 0) for k in look]
        ok_i, ok_d = [], []
        for k, v in zip(ins.tolist(), iv.tolist()):
            ok_i.append(k not in self.d)
            if ok_i[-1]:
                self.d[k] = v
        for k in dels.tolist():
            ok_d.append(k in self.d)
            self.d.pop(k, None)
        return [np.asarray(x) for x in (found, vals, ok_i, ok_d)]


oracle = Oracle()
universe = np.arange(1, 6 * S * KEYS // 4, dtype=np.int32)


def fresh(n):
    live = np.fromiter(oracle.d, np.int32, len(oracle.d))
    return rng.choice(np.setdiff1d(universe, live), n, replace=False)


def batch(look=None, ins=None, dels=None):
    look = rng.choice(universe, S * Q) if look is None else look
    ins = fresh(S * U) if ins is None else ins
    iv = rng.integers(0, 1 << 30, ins.size).astype(np.int32)
    if dels is None:                # mostly live keys: the load holds
        live = np.fromiter(oracle.d, np.int32, len(oracle.d))
        n = min(S * U - 4, live.size)
        dels = np.concatenate([rng.choice(live, n, replace=False),
                               rng.choice(universe, S * U - n)])
    return [np.asarray(x, np.int32) for x in (look, ins, iv, dels)]


def run(b, tag):
    got = jax.device_get(eng.step(*b))
    want = oracle.step(*b)
    f = want[0]
    for name, g, w, where in zip(("found", "vals", "insert ok", "delete ok"),
                                 got, want, (True, f, True, True)):
        bad = np.flatnonzero((np.asarray(g) != w) & where)
        assert bad.size == 0, (tag, name, bad[:8])


def check_count(tag):
    assert eng.count() == len(oracle.d), (tag, eng.count(), len(oracle.d))


# a first fill, with its answers checked like every later batch's
for i in range(8):
    run(batch(), f"fill {i}")
check_count("fill")

if CASE == "swaps":
    step = 8
    while eng.stats.rebuilds_completed < 2:
        run(batch(), f"step {step}")
        step += 1
        assert step < 400, "no second epoch swap"
    for i in range(8):
        run(batch(), f"after {i}")
    assert int(np.asarray(jax.device_get(eng.state.epoch))[0]) >= 2
    assert eng.state.cursor.sharding.spec == P("shard")
    check_count("swaps")

elif CASE == "cross_chip_duplicates":
    prev = []
    for i in range(6):
        look, ins, iv, dels = batch()
        # device 0's slice holds the first copy, device 2's the second;
        # the next batch looks the inserted key up (the first value won)
        ins[2 * U + 3] = ins[1]
        dels[3 * U + 5] = dels[0]
        look[Q + 7 : Q + 7 + len(prev)] = prev
        run([look, ins, iv, dels], f"dup {i}")
        prev = [ins[1]]
    check_count("duplicates")

elif CASE == "full_skew":
    def owned_by(keys, s):
        return keys[np.asarray(dd.shard_of(keys, S, owner)) == s]
    for i, s in enumerate((2, 0)):
        while eng.stats.steps % POLL != POLL - 1:
            run(batch(), "to the poll")
        look = rng.choice(owned_by(universe, s), S * Q)
        ins = owned_by(fresh(8 * S * U), s)[: S * U]
        dels = rng.choice(owned_by(universe, s), S * U, replace=False)
        assert ins.size == S * U
        run(batch(look, ins, dels), f"skew {s}")
        # the step before the poll sent every key to owner s
        assert eng.stats.routed_peak_load == S * (Q + 2 * U), eng.stats
        assert eng.stats.routed_lanes == S * (Q + 2 * U), eng.stats
        check_count(f"skew {s}")
    run(batch(), "after skew")
    check_count("after skew")

print("SHARDED-OK", CASE, eng.stats.rebuilds_completed)
"""


@pytest.mark.parametrize("case", ["swaps", "cross_chip_duplicates",
                                  "full_skew"])
def test_sharded_engine_matches_dict_oracle(case):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT, case],
                       capture_output=True, text=True, env=env, cwd=root)
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"SHARDED-OK {case}" in r.stdout
